"""Model-level introspection: per-head gradient diagnostics, task-conflict
tracking, and the per-run hardware-efficiency ledger.

The paper's defining feature is the multi-headed decoder — one shared
encoder trained against N simultaneous tasks — yet a per-task loss number
is all the flight record used to say about the multi-task optimization.
This module makes two more questions answerable from the run's own
artifact (docs/OBSERVABILITY.md "Model-level diagnostics"):

**Is the multi-task optimization healthy?**
  Per sampled step: per-head gradient norms (one forward + one ``jax.vjp``
  linearization shared by H one-hot cotangent pulls, :func:`linearize_heads`
  — not H separate backward passes over a re-traced forward), the pairwise
  inter-task gradient cosine matrix (the conflict matrix: persistently
  negative entries mean two heads fight over the shared encoder), and the
  global update-to-param norm ratio (the effective step size the optimizer
  is actually taking): :func:`head_diagnostics`. :class:`HeadDiagnostics`
  samples every ``Training.diag_every`` steps (default: once per epoch)
  so the hot path gains no per-step host syncs. Where the loop scans its
  epochs, the sampled step is the epoch's first TRAIN step, run once by
  the program that diagnoses it (``train/state.py:
  make_diagnosed_first_step``: the update is built from the same
  linearisation). Elsewhere :func:`make_diagnostics_step` is a separate
  observer program dispatched before the sampled step; the train step
  itself is untouched (pinned by the zero-unexpected-recompile test).

**How efficiently did the hardware run?**
  :class:`HardwareLedger` records the compiled train step's analytic
  FLOPs/bytes (XLA cost model, obtained from the LOWERED module — no
  second compile) plus the chip's bf16 peak at ``run_start``, and turns
  each epoch's wall time into achieved TFLOP/s + MFU, alongside the
  device-memory watermark (``memory_stats()`` where the backend exposes
  it, ``available: false`` degradation elsewhere — same discipline as the
  compile monitor). ``bench.py`` imports :func:`peak_flops` /
  :func:`cost_analysis` from here (single source for the cost math).

Everything host-side in this module is numpy-only; jax is imported
lazily inside the functions that need it so ``tools/obs_report.py`` can
use the series/anomaly helpers without touching a backend.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

# bf16 MXU peak per chip, by device_kind substring (public specs).
# Moved from bench.py so training and bench MFU share one table.
PEAK_BF16_TFLOPS = (
    ("v5 lite", 197.0),
    ("v5e", 197.0),
    ("v5p", 459.0),
    ("v4", 275.0),
    ("v6", 918.0),
    ("trillium", 918.0),
)


def peak_flops(device) -> Optional[float]:
    """The device's bf16 peak in FLOP/s, or None when the chip is not in
    the table (CPU, unknown accelerators) — MFU is then unavailable."""
    kind = getattr(device, "device_kind", "").lower()
    for sub, tf in PEAK_BF16_TFLOPS:
        if sub in kind:
            return tf * 1e12
    return None


# HBM bandwidth per chip, by device_kind substring (public specs) — the
# denominator of the per-kernel roofline attribution (bench.py): an op
# running near this number is bandwidth-bound and further kernel fusion
# cannot speed it up; one far below it while off the MXU is
# overhead/serial-bound — the class the fused kernels exist to kill.
PEAK_HBM_GBPS = (
    ("v5 lite", 819.0),
    ("v5e", 819.0),
    ("v5p", 2765.0),
    ("v4", 1228.0),
    ("v6", 1638.0),
    ("trillium", 1638.0),
)


def peak_hbm_bw(device) -> Optional[float]:
    """The device's HBM bandwidth in bytes/s, or None off-table."""
    kind = getattr(device, "device_kind", "").lower()
    for sub, gb in PEAK_HBM_GBPS:
        if sub in kind:
            return gb * 1e9
    return None


def cost_analysis(compiled_or_lowered) -> Tuple[Optional[float], Optional[float]]:
    """(flops, bytes) per execution from XLA's cost model, or Nones.

    Accepts either a ``jax.stages.Compiled`` or a ``jax.stages.Lowered``
    — the lowered path analyzes the unoptimized HLO WITHOUT compiling,
    which is what training uses (a second compile of the train step
    would churn the compile monitor's zero-unexpected-recompile
    contract)."""
    try:
        c = compiled_or_lowered.cost_analysis()
        if isinstance(c, (list, tuple)):
            c = c[0]
        flops = float(c.get("flops", 0.0)) or None
        nbytes = float(c.get("bytes accessed", 0.0)) or None
        return flops, nbytes
    except Exception:
        return None, None


def pad_waste_from_batch(batch) -> Dict[str, Any]:
    """Pad-occupancy accounting for one loader batch: how much of the
    static edge/node pad the batch actually fills. Uses the loader's
    occupancy fields (``GraphBatch.edge_occupancy`` — the fused
    kernel's actual chunk-loop bound, which under run_align includes
    the interleaved masked self-loops below it — and ``n_real_nodes``)
    when present, the masks otherwise. Works on single batches and
    device-stacked ones (means over the leading device axis)."""
    senders = np.asarray(batch.senders)
    edge_pad = int(senders.shape[-1])
    nmask = np.asarray(batch.node_mask)
    node_pad = int(nmask.shape[-1])
    occ = getattr(batch, "edge_occupancy", None)
    if occ is not None:
        real_e = float(np.asarray(occ).mean())
    else:
        real_e = float(np.asarray(batch.edge_mask).sum(axis=-1).mean())
    nrn = getattr(batch, "n_real_nodes", None)
    if nrn is not None:
        real_n = float(np.asarray(nrn).mean())
    else:
        real_n = float(nmask.sum(axis=-1).mean())
    return {
        "edge_pad": edge_pad,
        "node_pad": node_pad,
        "real_edges_mean": round(real_e, 1),
        "real_nodes_mean": round(real_n, 1),
        "edge_waste_frac": round(1.0 - real_e / max(edge_pad, 1), 4),
        "node_waste_frac": round(1.0 - real_n / max(node_pad, 1), 4),
    }


def conv_traffic_model(
    node_pad: int,
    edge_pad: int,
    hidden: int,
    layers: int,
    real_edges: Optional[float] = None,
) -> Dict[str, Any]:
    """Analytic bytes/step of the conv hot path under each kernel mode
    (docs/PERF.md r08) — the useful-vs-padded byte accounting the XLA
    cost model cannot provide (it prices custom-calls from operand
    SHAPES, so occupancy skipping and the bf16 activation path are
    invisible to it).

    Prices, per conv layer, what the fused kernel physically moves:
    edge-id chunk DMAs (3 int32 streams in whole CE-edge chunks),
    sender gather windows (BW rows x padded width, ~one window per
    chunk — the loader's locality contract), the layer's params, and
    the f32 output write. ``fused_skip`` bounds the chunk loop at
    ``real_edges`` (GraphBatch.edge_occupancy); ``fused_skip_bf16``
    additionally moves activations as bf16; ``resident_skip`` loads the
    features once and keeps them in VMEM across layers (intermediate
    out-block flushes counted honestly). ``xla_unfused`` is the
    materialized gather->message->scatter chain for scale."""
    from hydragnn_tpu.ops.segment_pallas import ALIGN, BN, BW, CE

    hp = ((int(hidden) + 127) // 128) * 128
    node_pad = int(node_pad)
    edge_pad = int(edge_pad)
    layers = max(int(layers), 1)
    n_pad_out = ((node_pad + BN - 1) // BN) * BN
    n_res = max(((node_pad + ALIGN - 1) // ALIGN) * ALIGN, BW, n_pad_out)
    e_eff = edge_pad if real_edges is None else min(float(real_edges), edge_pad)

    def chunks(e: float) -> int:
        return -(-int(e) // CE) if e > 0 else 0

    def fused(e: float, act_bytes: int) -> int:
        per_layer = (
            3 * chunks(e) * CE * 4        # send/recv/mask id streams
            + chunks(e) * BW * hp * act_bytes  # sender gather windows
            + (hp * hp + hp) * 4          # layer params (f32 always)
            + n_pad_out * hp * 4          # f32 output write
        )
        return layers * per_layer

    xla = layers * (
        node_pad * hp * 4        # x read
        + 4 * edge_pad * hp * 4  # gather write+read, message write+read
        + 2 * edge_pad * 4       # id reads
        + n_pad_out * hp * 4     # scatter output
    )
    padded = fused(edge_pad, 4)
    skip = fused(e_eff, 4)
    skip_bf16 = fused(e_eff, 2)
    resident_skip = n_res * hp * 4 + layers * (
        3 * chunks(e_eff) * CE * 4 + (hp * hp + hp) * 4 + n_pad_out * hp * 4
    )

    def drop(b: int) -> float:
        return round(1.0 - b / max(padded, 1), 4)

    return {
        "hidden_padded": hp,
        "edge_pad": edge_pad,
        "real_edges": None if real_edges is None else int(real_edges),
        "assumption": "one BW-row gather window per CE-edge chunk (loader locality)",
        "bytes_per_step": {
            "xla_unfused": int(xla),
            "fused_padded": int(padded),
            "fused_skip": int(skip),
            "fused_skip_bf16": int(skip_bf16),
            "resident_skip": int(resident_skip),
        },
        "drop_vs_fused_padded": {
            "fused_skip": drop(skip),
            "fused_skip_bf16": drop(skip_bf16),
            "resident_skip": drop(resident_skip),
        },
    }


def device_memory_stats(device=None) -> Dict[str, Any]:
    """Device-memory watermark with the compile-monitor-style
    ``available`` degradation: CPU (and any backend without
    ``memory_stats``) reports ``{"available": False}`` rather than
    raising or lying."""
    try:
        import jax

        if device is None:
            device = jax.local_devices()[0]
        stats = device.memory_stats()
    except Exception:
        stats = None
    if not stats:
        return {"available": False}
    out: Dict[str, Any] = {"available": True}
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
        if key in stats:
            out[key] = int(stats[key])
    return out


# ---------------------------------------------------------------------------
# per-head gradient diagnostics (the on-device half)
# ---------------------------------------------------------------------------


def linearize_heads(loss_fn, params, weights, remat: bool = False, last_from_total: bool = False):
    """ONE forward and ONE ``jax.vjp`` linearisation of the stacked task
    losses, then H + 1 pulls through it: a one-hot cotangent per head
    (each head's UNWEIGHTED loss gradient w.r.t. the full parameter tree;
    not H backward passes over a re-traced forward) and the task weights
    as cotangent, which is the gradient of the train loss: the operation
    the train step's backward is, so an update built from it is the train
    step's update to rounding.

    ``last_from_total``: pull the first H - 1 heads only; the last head's
    gradient is the total's less the others', weighted, which
    :func:`head_diagnostics` uses through their dot products alone. One
    gradient tree fewer is alive at once (the token stacks, whose
    parameters are most of the device's memory).

    ``loss_fn``: ``models/base.py:train_loss_closure``'s ``params ->
    (loss, (tasks[H], mutated))``. Returns ``(loss, tasks, mutated, head_grads,
    total_grad)``; ``head_grads`` holds H - 1 trees under
    ``last_from_total``."""
    import jax
    import jax.numpy as jnp

    def tasks_fn(p):
        loss, (tasks, mutated) = loss_fn(p)
        return tasks, (loss, mutated)

    fn = jax.checkpoint(tasks_fn) if remat else tasks_fn
    tasks, vjp_fn, (loss, mutated) = jax.vjp(fn, params, has_aux=True)
    num_heads = tasks.shape[0]
    head_grads = []
    for ihead in range(num_heads - 1 if last_from_total and num_heads > 1 else num_heads):
        cot = jnp.zeros((num_heads,), tasks.dtype).at[ihead].set(1.0)
        (g,) = vjp_fn(cot)
        head_grads.append(g)
    if num_heads == 1 and float(weights[0]) == 1.0:
        # one head of weight 1: its gradient IS the train loss's, and a
        # second pull would hold a second tree of gradients for nothing
        return loss, tasks, mutated, head_grads, head_grads[0]
    (total_grad,) = vjp_fn(jnp.asarray(weights, tasks.dtype))
    return loss, tasks, mutated, head_grads, total_grad


def head_diagnostics(tasks, head_grads, total_grad, params, updates, weights=None) -> Dict[str, Any]:
    """The diagnostics arithmetic: a pure function of one step's
    linearisation (:func:`linearize_heads`) and update, which the observer
    (:func:`make_diagnostics_step`) and the diagnosed train step
    (``train/state.py:make_diagnosed_first_step``) both call. Where
    ``head_grads`` holds one head fewer than ``tasks`` (``last_from_total``),
    the last head's dot products come from the total's and the task
    ``weights``: ``g_last = (total - sum_i w_i g_i) / w_last``. Returned
    (device) dict:

      - ``tasks_loss`` [H]: the forward's per-head losses;
      - ``grad_norms`` [H]: global norm of each head's UNWEIGHTED loss
        gradient w.r.t. the full parameter tree;
      - ``cosine`` [H, H]: pairwise cosine similarity between per-head
        gradients (1 on the diagonal; negative entries = conflicting
        tasks pulling the shared encoder in opposing directions);
      - ``grad_norm_total``: norm of the task-weighted total gradient
        (what the optimizer actually consumes);
      - ``param_norm`` / ``update_norm`` / ``update_ratio``: global
        parameter norm, optax update norm, and their ratio — the
        effective relative step size."""
    import jax
    import jax.numpy as jnp
    import optax

    def _tree_dot(a, b) -> jnp.ndarray:
        leaves = jax.tree_util.tree_map(
            lambda x, y: jnp.vdot(x.astype(jnp.float32), y.astype(jnp.float32)), a, b
        )
        return sum(jax.tree_util.tree_leaves(leaves), jnp.zeros((), jnp.float32))

    num_heads = tasks.shape[0]
    vectors = list(head_grads) + ([total_grad] if len(head_grads) < num_heads else [])
    dots = jnp.stack([jnp.stack([_tree_dot(gi, gj) for gj in vectors]) for gi in vectors])
    if len(head_grads) < num_heads:  # the heads' gram matrix from the pulled heads' and the total's
        w = jnp.asarray(weights, jnp.float32)
        last = jnp.concatenate([-w[:-1], jnp.ones((1,), jnp.float32)]) / w[-1]
        coef = jnp.concatenate([jnp.eye(num_heads, dtype=jnp.float32)[: num_heads - 1], last[None, :]])
        dots = coef @ dots @ coef.T
    norms = jnp.sqrt(jnp.clip(jnp.diagonal(dots), 0.0, None))
    denom = jnp.maximum(norms[:, None] * norms[None, :], 1e-30)
    param_norm = optax.global_norm(params)
    update_norm = optax.global_norm(updates)
    return {
        "tasks_loss": tasks,
        "grad_norms": norms,
        "cosine": dots / denom,
        "grad_norm_total": optax.global_norm(total_grad),
        "param_norm": param_norm,
        "update_norm": update_norm,
        "update_ratio": update_norm / jnp.maximum(param_norm, 1e-30),
    }


def make_diagnostics_step(
    model,
    tx,
    compute_dtype=None,
    remat: bool = False,
) -> Callable[..., Dict[str, Any]]:
    """The diagnostics OBSERVER: jitted ``(state, batch) -> diagnostics
    dict`` (:func:`head_diagnostics`) over the SAME loss the train step
    optimizes (``models/base.py:train_loss_closure``: the same dropout-rng
    split, the same mixed-precision casts), without touching the state:
    no donation, no mutation. The path where the loop does not own a
    scanned step: per-step dispatch samples it BEFORE the train step,
    which then computes the forward, the gradient and the update a second
    time. A loop that scans its epochs runs ``train/state.py:
    make_diagnosed_first_step`` in its place: the same two functions of
    this module around the same closure, and the update lands.

    Cost: one forward + (H+1) cotangent pulls through one shared
    ``jax.vjp`` linearization (:func:`linearize_heads`), plus one
    ``tx.update`` whose new opt_state is discarded.
    """
    import jax

    from hydragnn_tpu.models.base import train_loss_closure

    weights = model.cfg.normalized_weights

    def diagnostics_step(state, batch) -> Dict[str, Any]:
        # identical split to the train step body: the diagnosed gradient
        # is the gradient THIS step's update is built from
        _, dropout_rng = jax.random.split(state.rng)
        loss_fn = train_loss_closure(model, compute_dtype, state.batch_stats, batch, dropout_rng)
        lean = model.cfg.is_token_stack
        _, tasks, _, head_grads, grads = linearize_heads(
            loss_fn, state.params, weights, remat=remat, last_from_total=lean
        )
        updates, _ = tx.update(grads, state.opt_state, state.params)
        return head_diagnostics(tasks, head_grads, grads, state.params, updates, weights)

    return jax.jit(diagnostics_step)


class HeadDiagnostics:
    """Which steps are diagnosed, and their diagnostics until the epoch's
    record is written. Two callers:

    - a per-step loop calls ``maybe_sample(state, batch)`` once per
      training step BEFORE the (buffer-donating) train step consumes the
      state; on sampled steps (every ``every`` steps, starting with the
      very first — so the one diagnostics compile lands in epoch 0
      alongside the train step's) it dispatches the observer ``diag_fn``;
      on the others it is a counter increment and nothing else;
    - a loop that scans its epochs counts in epochs (``every`` is then an
      epoch stride, ``train/loop.py:DispatchPlan.diag_stride``) and
      dispatches nothing here: where :attr:`due`, its first train step is
      the diagnosed one (``train/state.py:make_diagnosed_first_step``) and
      ``count_step`` is handed that program's dictionary; ``diag_fn`` is
      None.

    Either way the DEVICE results are kept; no host sync happens until
    :meth:`epoch_snapshot` materializes them at the epoch boundary, where
    the epoch metrics sync anyway."""

    def __init__(self, diag_fn, head_names: Sequence[str], every: int):
        self.fn = diag_fn
        self.head_names = list(head_names)
        self.every = max(int(every), 1)
        self._n = 0
        self._pending = None
        self._pending_step = None

    @property
    def due(self) -> bool:
        """Is the step (scan: the epoch) about to be counted a sampled one?"""
        return self._n % self.every == 0

    def count_step(self, diagnostics=None) -> None:
        """One step (scan: one epoch) ran; ``diagnostics``: its device
        dictionary where it was a diagnosed one."""
        if diagnostics is not None:
            self._pending = diagnostics
            self._pending_step = self._n
        self._n += 1

    def maybe_sample(self, state, batch) -> None:
        self.count_step(self.fn(state, batch) if self.due else None)

    def epoch_snapshot(self) -> Optional[Dict[str, Any]]:
        """Materialize the epoch's sampled diagnostics (one D2H sync),
        keyed by head name — flight-record-ready. None when no step was
        sampled this epoch (``diag_every`` longer than the epoch)."""
        if self._pending is None:
            return None
        import jax

        vals = jax.device_get(self._pending)
        self._pending = None
        names = self.head_names
        grad_norms = np.asarray(vals["grad_norms"], np.float64)
        snap = {
            "available": True,
            "sampled_step": self._pending_step,
            "grad_norm": {n: float(g) for n, g in zip(names, grad_norms)},
            "task_loss": {
                n: float(v) for n, v in zip(names, np.asarray(vals["tasks_loss"]))
            },
            "cosine": np.asarray(vals["cosine"], np.float64).round(6).tolist(),
            "grad_norm_total": float(vals["grad_norm_total"]),
            "param_norm": float(vals["param_norm"]),
            "update_norm": float(vals["update_norm"]),
            "update_ratio": float(vals["update_ratio"]),
        }
        self._pending_step = None
        return snap


# ---------------------------------------------------------------------------
# per-head eval quality metrics
# ---------------------------------------------------------------------------


def per_head_error_metrics(
    trues: Sequence[np.ndarray],
    preds: Sequence[np.ndarray],
    names: Sequence[str],
) -> Dict[str, Dict[str, float]]:
    """MAE/RMSE per head over the gathered (true, predicted) value
    arrays the ``test_epoch`` sample path returns — pure numpy, runs on
    every execution mode (per-step, scan, sharded)."""
    out: Dict[str, Dict[str, float]] = {}
    for name, tv, pv in zip(names, trues, preds):
        if np.issubdtype(np.asarray(tv).dtype, np.integer):
            # a vocabulary head (models/token_stack.py): the targets are
            # ids and a prediction is (log p(target), arg-max); an error in
            # ids means nothing, the share of rows it gets right does
            ids, pv = np.asarray(tv).reshape(-1), np.asarray(pv, np.float64).reshape(-1, 2)
            n = min(ids.size, pv.shape[0])
            out[name] = {"mae": None, "rmse": None, "count": int(n),
                         "accuracy": float((pv[:n, 1] == ids[:n]).mean()) if n else None}
            continue
        tv = np.asarray(tv, np.float64).reshape(-1)
        pv = np.asarray(pv, np.float64).reshape(-1)
        n = min(tv.size, pv.size)
        if n == 0:
            out[name] = {"mae": None, "rmse": None, "count": 0}
            continue
        diff = pv[:n] - tv[:n]
        out[name] = {
            "mae": float(np.abs(diff).mean()),
            "rmse": float(np.sqrt((diff * diff).mean())),
            "count": int(n),
        }
    return out


# ---------------------------------------------------------------------------
# hardware-efficiency ledger
# ---------------------------------------------------------------------------


class HardwareLedger:
    """Per-run hardware-efficiency accounting for the train loop.

    Built once at run start from the train step's LOWERED module (no
    extra compile; ``available: false`` when lowering or the cost model
    is not supported for the step in use — sharded shard_map steps and
    the scan path degrade rather than fail). Per epoch,
    :meth:`epoch_record` turns measured wall seconds into achieved
    TFLOP/s and MFU against the chip's bf16 peak, plus the device
    memory watermark."""

    def __init__(
        self,
        flops_per_step: Optional[float],
        bytes_per_step: Optional[float],
        peak: Optional[float],
        device=None,
        reason: Optional[str] = None,
    ):
        self.flops_per_step = flops_per_step
        self.bytes_per_step = bytes_per_step
        self.peak = peak
        self.device = device
        self.reason = reason
        self.pad_waste: Optional[Dict[str, Any]] = None
        self.conv_traffic: Optional[Dict[str, Any]] = None
        self._mfus: List[float] = []
        self._peak_mem: Optional[int] = None

    @classmethod
    def from_step(cls, step_fn, args: tuple, device=None, reason: Optional[str] = None):
        """Lower ``step_fn`` on example args and read the cost model.
        Any failure (non-jitted callable, shard_map lowering quirks,
        missing cost analysis on this backend) degrades to an
        unavailable ledger carrying the failure class as ``reason``."""
        import jax

        if device is None:
            try:
                device = jax.devices()[0]
            except Exception:
                device = None
        flops = nbytes = None
        if reason is None:
            try:
                lowered = step_fn.lower(*args)
                flops, nbytes = cost_analysis(lowered)
                if flops is None:
                    reason = "cost_analysis_unavailable"
            except Exception as exc:
                reason = f"lowering_failed:{type(exc).__name__}"
        return cls(flops, nbytes, peak_flops(device), device=device, reason=reason)

    @classmethod
    def disabled(cls, reason: str = "disabled"):
        return cls(None, None, None, reason=reason)

    @property
    def available(self) -> bool:
        return self.flops_per_step is not None

    def set_conv_traffic(
        self,
        pad_waste: Optional[Dict[str, Any]],
        conv_traffic: Optional[Dict[str, Any]],
    ) -> None:
        """Attach the batch pad-occupancy accounting and the analytic
        conv-traffic model (useful vs padded bytes) — computed by the
        loop from the example batch; lands in the flight manifest."""
        self.pad_waste = pad_waste
        self.conv_traffic = conv_traffic

    def manifest(self) -> Dict[str, Any]:
        """The ``run_start`` ledger fields: what one step costs and what
        the chip could do."""
        out: Dict[str, Any] = {"available": self.available}
        if not self.available and self.reason:
            out["reason"] = self.reason
        if self.flops_per_step is not None:
            out["flops_per_step"] = self.flops_per_step
        if self.bytes_per_step is not None:
            out["bytes_per_step"] = self.bytes_per_step
        out["peak_bf16_tflops"] = (
            round(self.peak / 1e12, 1) if self.peak else None
        )
        if self.pad_waste is not None:
            out["pad_waste"] = self.pad_waste
        if self.conv_traffic is not None:
            out["conv_traffic"] = self.conv_traffic
        return out

    def epoch_record(self, steps: int, wall_s: float) -> Dict[str, Any]:
        """One epoch's efficiency: achieved TFLOP/s + MFU from the
        epoch's train wall time (an end-to-end number — data waits and
        dispatch gaps count against it, which is the honest production
        MFU), and the memory watermark."""
        out: Dict[str, Any] = {"available": self.available}
        if not self.available and self.reason:
            out["reason"] = self.reason
        out["steps"] = int(steps)
        out["train_wall_s"] = round(float(wall_s), 6)
        if self.available and steps > 0 and wall_s > 0:
            achieved = self.flops_per_step * steps / wall_s
            # 9 decimals: a CPU smoke run's sub-GFLOP/s rate must not
            # round to zero (the TPU range is unaffected)
            out["achieved_tflops"] = round(achieved / 1e12, 9)
            if self.peak:
                mfu = achieved / self.peak
                out["mfu"] = round(mfu, 6)
                self._mfus.append(mfu)
            else:
                out["mfu"] = None
        mem = device_memory_stats(self.device)
        out["memory"] = mem
        if mem.get("peak_bytes_in_use") is not None:
            self._peak_mem = max(self._peak_mem or 0, mem["peak_bytes_in_use"])
        return out

    def run_summary(self) -> Dict[str, Any]:
        """The ``run_end`` rollup: mean/max MFU over epochs and the
        run's high-water memory mark."""
        out: Dict[str, Any] = {"available": self.available}
        if self._mfus:
            out["mfu_mean"] = round(float(np.mean(self._mfus)), 6)
            out["mfu_max"] = round(float(np.max(self._mfus)), 6)
        if self._peak_mem is not None:
            out["peak_bytes_in_use"] = self._peak_mem
        return out


# ---------------------------------------------------------------------------
# flight-record series + anomaly heuristics (numpy-only, used by
# tools/obs_report.py --heads)
# ---------------------------------------------------------------------------


def collect_head_series(events: List[dict]) -> Dict[str, Any]:
    """Extract per-head trajectories from a flight record's epoch
    events: losses (v1 positional lists and v2 name-keyed dicts both
    accepted), sampled grad norms, conflict matrices, eval MAE.

    Returns ``{"names", "epochs", "train_loss", "grad_norm", "mae",
    "rmse", "cosine", "update_ratio"}`` where the per-head entries map
    name -> aligned list (None where an epoch carried no sample)."""
    epochs = [e for e in events if e.get("kind") == "epoch"]
    names: List[str] = []
    for e in epochs:
        heads = e.get("heads") or {}
        if heads.get("names"):
            names = list(heads["names"])
            break
        tt = e.get("train_tasks")
        if isinstance(tt, dict) and not names:
            names = list(tt)
    if not names and epochs:
        tt = epochs[0].get("train_tasks")
        if isinstance(tt, list):
            names = [f"task{i}" for i in range(len(tt))]
    series: Dict[str, Any] = {
        "names": names,
        "epochs": [e.get("epoch") for e in epochs],
        "train_loss": {n: [] for n in names},
        "grad_norm": {n: [] for n in names},
        "mae": {n: [] for n in names},
        "rmse": {n: [] for n in names},
        "cosine": [],
        "update_ratio": [],
    }

    def _per_head(container, key) -> Dict[str, Optional[float]]:
        val = (container or {}).get(key)
        if isinstance(val, dict):
            return {n: val.get(n) for n in names}
        if isinstance(val, list):
            return {n: (val[i] if i < len(val) else None) for i, n in enumerate(names)}
        return {n: None for n in names}

    for e in epochs:
        heads = e.get("heads") or {}
        tl = _per_head(e, "train_tasks")
        gn = _per_head(heads, "grad_norm")
        mae = _per_head(heads, "mae")
        rmse = _per_head(heads, "rmse")
        for n in names:
            series["train_loss"][n].append(tl[n])
            series["grad_norm"][n].append(gn[n])
            series["mae"][n].append(mae[n])
            series["rmse"][n].append(rmse[n])
        series["cosine"].append(heads.get("cosine"))
        series["update_ratio"].append(heads.get("update_ratio"))
    return series


def flag_anomalies(
    series: Dict[str, Any],
    spike_factor: float = 3.0,
    imbalance_factor: float = 10.0,
    negative_persistence: float = 0.5,
) -> List[str]:
    """Heuristic diagnosis over :func:`collect_head_series` output —
    human-readable flags, empty when the multi-task optimization looks
    healthy:

      - **loss spike**: a head's train loss exceeds ``spike_factor`` x
        the rolling median of its previous (up to 5) epochs;
      - **task conflict**: a head pair whose gradient cosine is negative
        in more than ``negative_persistence`` of the sampled epochs AND
        whose mean cosine is below -0.02 (persistently opposed, not a
        near-orthogonal pair flickering around zero);
      - **gradient imbalance**: the mean grad-norm ratio between the
        largest and smallest head exceeds ``imbalance_factor`` — one
        task's gradient drowns the others in the shared encoder.
    """
    flags: List[str] = []
    names = series.get("names") or []
    for n in names:
        losses = series["train_loss"].get(n) or []
        for i in range(1, len(losses)):
            cur = losses[i]
            window = [v for v in losses[max(0, i - 5) : i] if v is not None]
            if cur is None or not window:
                continue
            med = float(np.median(window))
            if med > 0 and cur > spike_factor * med:
                flags.append(
                    f"loss spike: head '{n}' epoch {series['epochs'][i]} "
                    f"train loss {cur:.4g} > {spike_factor:g}x rolling "
                    f"median {med:.4g}"
                )
    mats = [np.asarray(m, np.float64) for m in series.get("cosine") or [] if m is not None]
    if mats:
        h = len(names)
        for i in range(h):
            for j in range(i + 1, h):
                vals = np.asarray([m[i, j] for m in mats if m.shape == (h, h)])
                if (
                    vals.size >= 2
                    and (vals < 0).mean() > negative_persistence
                    and vals.mean() < -0.02
                ):
                    flags.append(
                        f"task conflict: heads '{names[i]}' vs "
                        f"'{names[j]}' gradient cosine negative in "
                        f"{int((vals < 0).sum())}/{vals.size} sampled epochs "
                        f"(mean {vals.mean():+.3f})"
                    )
    means = {}
    for n in names:
        gn = [v for v in (series["grad_norm"].get(n) or []) if v is not None]
        if gn:
            means[n] = float(np.mean(gn))
    if len(means) >= 2:
        hi = max(means, key=means.get)
        lo = min(means, key=means.get)
        if means[lo] > 0 and means[hi] / means[lo] > imbalance_factor:
            flags.append(
                f"gradient imbalance: head '{hi}' mean grad norm "
                f"{means[hi]:.4g} is {means[hi] / means[lo]:.1f}x head "
                f"'{lo}' ({means[lo]:.4g}) — exceeds {imbalance_factor:g}x"
            )
    return flags
