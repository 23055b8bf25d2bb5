"""On-chip TPU kernel selfcheck.

Every Pallas test in the default suite runs interpret-mode on the CPU
mesh; ``HYDRAGNN_PALLAS=auto`` means kernel-on-TPU, so the path real
training takes needs a check on the chip itself. This module exercises
the DEFAULT TPU kernel path on the actual chip:

  1. family kernel vs the fused XLA pass — f32 and bf16 data, boolean
     and float-weight masks, two CSR shapes (multi-chunk included);
  2. sum-only kernel (the VJP hot path) vs ``jax.ops.segment_sum``;
  3. gather kernels (bit-exact), the local-window pair, and the fused
     gather + K-group statistics vs their jnp compositions;
  4. one flagship-shaped PNA train step, Pallas vs XLA dispatch — loss
     must agree to mixed-precision tolerance.

It runs IN the process that holds the chip: ``chip_smoke.py`` calls
:func:`check_kernels` and :func:`check_train_step` as one of its phases
(a chip belongs to one process; a child started from a parent that has
touched JAX cannot have it). Directly:
``python -m hydragnn_tpu.tools.tpu_selfcheck``. Exit code 0 = all checks
passed. Prints one JSON line per check.
"""

from __future__ import annotations

import json


def _fail(name: str, **kw) -> None:
    print(json.dumps({"check": name, "ok": False, **kw}))


def _ok(name: str, **kw) -> None:
    print(json.dumps({"check": name, "ok": True, **kw}))


def _allclose(a, b, rtol, atol) -> bool:
    import numpy as np

    return bool(
        np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=rtol, atol=atol)
    )


def check_kernels() -> bool:
    """Family + sum kernels vs XLA on-chip, multiple dtypes/masks."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.ops.segment_pallas import (
        segment_sum_family_pallas,
        segment_sum_family_xla,
        segment_sum_pallas,
    )

    ok = True
    rng = np.random.default_rng(0)
    shapes = [(4096, 128, 1024), (120_000, 128, 5136)]  # (E, H, N); 2nd = bench shape
    for e, h, n in shapes:
        recv = np.sort(rng.integers(0, n, e)).astype(np.int32)
        data32 = rng.normal(size=(e, h)).astype(np.float32)
        bmask = rng.random(e) > 0.2
        wmask = rng.random(e).astype(np.float32)
        for dtype, rtol, atol in ((jnp.float32, 1e-5, 1e-4), (jnp.bfloat16, 1e-2, 1e-2)):
            data = jnp.asarray(data32).astype(dtype)
            for mask, mname in ((None, "none"), (jnp.asarray(bmask), "bool"), (jnp.asarray(wmask), "float")):
                s, sq, c = segment_sum_family_pallas(
                    data, jnp.asarray(recv), n, mask, indices_are_sorted=True
                )
                rs, rsq, rc = segment_sum_family_xla(
                    # XLA reference on the SAME (possibly bf16-rounded) data
                    data, jnp.asarray(recv), n, mask, indices_are_sorted=True
                )
                good = (
                    _allclose(s, rs, rtol, atol)
                    and _allclose(sq, rsq, rtol, max(atol, 1e-2))
                    and _allclose(c, rc, 1e-6, 1e-6)
                )
                name = f"family_E{e}_{dtype.__name__}_mask-{mname}"
                (_ok if good else _fail)(name)
                ok &= good
        # sum-only kernel: one representative config per shape
        out = segment_sum_pallas(
            jnp.asarray(data32), jnp.asarray(recv), n,
            jnp.asarray(bmask), indices_are_sorted=True,
        )
        ref = jax.ops.segment_sum(
            jnp.asarray(data32 * bmask[:, None]), jnp.asarray(recv), n,
            indices_are_sorted=True,
        )
        good = _allclose(out, ref, 1e-5, 1e-4)
        (_ok if good else _fail)(f"sum_E{e}_f32_mask-bool")
        ok &= good
    # CSR-broadcast row gather (r03: the backward's widening gathers):
    # must be bit-exact vs indexing on-chip — dense, jumpy (low-degree,
    # multi-window chunks), f32 and bf16
    from hydragnn_tpu.ops.segment_pallas import _bcast_kernel_call

    for e, n, h, tag in ((120_000, 5136, 128, "dense"), (8192, 60_000, 128, "jumpy")):
        ids = jnp.asarray(np.sort(rng.integers(0, n, e)).astype(np.int32))
        table32 = jnp.asarray(rng.normal(size=(n, h)).astype(np.float32))
        for dtype in (jnp.float32, jnp.bfloat16):
            table = table32.astype(dtype)
            out = _bcast_kernel_call(table, ids, interpret=False)
            good = bool(np.array_equal(np.asarray(out), np.asarray(table[ids])))
            (_ok if good else _fail)(f"bcast_{tag}_{dtype.__name__}")
            ok &= good
    # TINY-MAGNITUDE table rows (r03 advisor): the extremum backward's
    # tie detection (data == gather(out)) needs the f32 3x-bf16-split
    # gather to be bit-exact. Probed on v5e (r04): exactness holds down
    # to |x| ~ 1e-35 — below that the split's residual terms fall under
    # bf16's subnormal floor (9.2e-41 x 2^16) and degrade to hi-term
    # (8-bit) accuracy; under bf16's subnormal min the value flushes
    # CLEANLY to 0. Segments whose extremum sits below 1e-35 therefore
    # drop their extremum gradient — numerically-zero segments, a
    # documented non-issue for training. The gate asserts the VERIFIED
    # contract so a regression of either half (exactness in range,
    # clean flush below) is caught at startup.
    # Measured decay curve (v5e probe, r04): bit-exact >= ~1e-30 (all
    # three split terms stay bf16-NORMAL); the lo term flushes first
    # (rel error ~2^-16 by 1e-33), then the mid term (~2^-8 by 3e-36);
    # below bf16's min normal (1.18e-38) even the hi term is a flushed
    # subnormal and the value reads back exactly 0. Each band is
    # asserted with margin so EITHER a range shrink or garbage (vs
    # clean flush) fails the gate.
    sub = np.zeros((256, 128), dtype=np.float32)
    for j, mag in enumerate((1e-30, 1e-34, 1e-36, 1e-39)):
        sub[j::4] = np.float32(mag) * (
            1 + rng.random((64, 128)).astype(np.float32)
        )
    ids = jnp.asarray(np.sort(rng.integers(0, 256, 2048)).astype(np.int32))
    table = jnp.asarray(sub)
    out = np.asarray(_bcast_kernel_call(table, ids, interpret=False))
    ref = np.asarray(table)[np.asarray(ids)]
    a = np.abs(ref)
    exact_b = a >= 1e-30
    lo_b = (a >= 1e-35) & ~exact_b  # lo-term flushed: <= 2^-9 rel
    mid_b = (a >= 3e-38) & (a < 1e-35)  # mid-term flushed too: <= 2^-6 rel
    flush_b = a < 1.1e-38
    good = bool(
        np.array_equal(out[exact_b], ref[exact_b])
        and np.all(np.abs(out[lo_b] - ref[lo_b]) <= 2.0 ** -9 * a[lo_b])
        and np.all(np.abs(out[mid_b] - ref[mid_b]) <= 2.0 ** -6 * a[mid_b])
        and np.all((out[flush_b] == 0) | (out[flush_b] == ref[flush_b]))
    )
    (_ok if good else _fail)("bcast_tiny_magnitude_f32")
    ok &= good
    # Same decay-band contract for the f32 SUM kernel's 3-term bf16
    # split (r04 advisor: only the gather was gated). All elements of a
    # segment share sign and magnitude band here, so the segment sum's
    # relative error is bounded by the per-element band.
    seg_ids = jnp.asarray(np.sort(rng.integers(0, 256, 2048)).astype(np.int32))
    vals = np.zeros((2048, 128), dtype=np.float32)
    band_of = np.asarray(seg_ids) % 4
    mags = (1e-28, 1e-34, 1e-36, 1e-39)
    for j, mag in enumerate(mags):
        sel = band_of == j
        vals[sel] = np.float32(mag) * (
            1 + rng.random((int(sel.sum()), 128)).astype(np.float32)
        )
    ssum_tiny = np.asarray(
        segment_sum_pallas(
            jnp.asarray(vals), seg_ids, 256, None, indices_are_sorted=True
        )
    )
    sref_tiny = np.asarray(
        jax.ops.segment_sum(jnp.asarray(vals), seg_ids, 256, indices_are_sorted=True)
    )
    seg_band = np.arange(256) % 4
    amag = np.abs(sref_tiny)
    err = np.abs(ssum_tiny - sref_tiny)
    with np.errstate(invalid="ignore", divide="ignore"):
        rel = np.where(amag > 0, err / np.maximum(amag, 1e-45), 0.0)
    good = bool(
        np.all(rel[seg_band == 0] <= 2.0 ** -12)  # all terms normal
        and np.all(rel[seg_band == 1] <= 2.0 ** -7)  # lo term flushed
        and np.all(rel[seg_band == 2] <= 2.0 ** -5)  # mid term flushed
        and np.all(
            (ssum_tiny[seg_band == 3] == 0) | (rel[seg_band == 3] <= 1.0)
        )  # below bf16 min normal: clean flush or hi-term remnant
    )
    (_ok if good else _fail)("sum_tiny_magnitude_f32")
    ok &= good
    # local-window variant (r04: unsorted-but-local ids — the sender
    # gather/scatter path): bit-exact gather + exact-sum scatter
    from hydragnn_tpu.ops.segment_pallas import segment_sum_local_pallas
    from hydragnn_tpu.graph.batch import _block_windows

    g_of = np.sort(rng.integers(0, 64, 20_000))
    lsend = (g_of * 80 + rng.integers(0, 80, 20_000)).astype(np.int32)
    lperm = np.argsort(lsend, kind="stable").astype(np.int32)
    win = jnp.asarray(_block_windows(lsend, lperm, 5136))
    ltab = jnp.asarray(rng.normal(size=(5136, 128)).astype(np.float32))
    lout = _bcast_kernel_call(ltab, jnp.asarray(lsend), False, False)
    good = bool(np.array_equal(np.asarray(lout), np.asarray(ltab[lsend])))
    (_ok if good else _fail)("bcast_local_unsorted_f32")
    ok &= good
    data = jnp.asarray(rng.normal(size=(20_000, 128)).astype(np.float32))
    ssum = segment_sum_local_pallas(data, jnp.asarray(lsend), win, 5136)
    sref = jax.ops.segment_sum(data, jnp.asarray(lsend), 5136)
    good = _allclose(ssum, sref, 1e-5, 1e-4)
    (_ok if good else _fail)("segment_sum_local_f32")
    ok &= good
    # fused gather + K-group pre-reduction (r05): stats and extremum
    # outputs vs the unfused composition over a materialized gather —
    # f32 and bf16, with partial and whole-group masking
    from hydragnn_tpu.ops.segment_pallas import (
        _gather_stats_call,
        _presum_stats_ref,
    )

    e_f, n_f, h_f, kk = 8192, 2048, 128, 8
    gtab32 = np.round(rng.normal(size=(n_f, h_f)) * 4).astype(np.float32) / 4
    ggrp = np.sort(rng.integers(0, 64, e_f))
    gsend = (ggrp * 32 + rng.integers(0, 32, e_f)).astype(np.int32)
    gmask = rng.random(e_f) > 0.25
    gmask[128:136] = False  # one whole K-group masked
    for dtype in (jnp.float32, jnp.bfloat16):
        gt = jnp.asarray(gtab32).astype(dtype)
        s_k, b_k = _gather_stats_call(
            gt, jnp.asarray(gsend), jnp.asarray(gmask), kk, interpret=False
        )
        s_r, b_r = _presum_stats_ref(
            gt[jnp.asarray(gsend)], jnp.asarray(gmask), kk
        )
        good = _allclose(s_k, s_r, 1e-5, 1e-4) and bool(
            np.array_equal(
                np.asarray(b_k, np.float32), np.asarray(b_r, np.float32)
            )
        )
        (_ok if good else _fail)(f"gather_presum_{dtype.__name__}")
        ok &= good
    return ok


def check_train_step() -> bool:
    """Flagship-shaped PNA train step: Pallas dispatch vs forced-XLA
    must produce the same loss (the end-to-end gate: VJPs, gathers,
    extremum backwards all route differently)."""
    import contextlib

    import numpy as np
    import jax.numpy as jnp

    from hydragnn_tpu.flagship import build_flagship
    from hydragnn_tpu.train import create_train_state, make_train_step, select_optimizer

    config, model, variables, loader = build_flagship(
        n_samples=160, hidden_dim=128, num_conv_layers=2, batch_size=128,
        unit_cells=(2, 4),
    )
    tx = select_optimizer(config["NeuralNetwork"]["Training"])
    batch = next(iter(loader))

    from hydragnn_tpu.ops.segment_pallas import xla_segment_ops

    losses = {}
    kernel_in_hlo = {}
    for knob in ("auto", "0"):
        # "0" = the forced-XLA arm: xla_segment_ops() is trace-time
        # scoped, and .lower() below is where the step is traced
        with xla_segment_ops() if knob == "0" else contextlib.nullcontext():
            step = make_train_step(model, tx, compute_dtype=jnp.bfloat16)
            state = create_train_state(variables, tx, seed=0)
            compiled = step.lower(state, batch).compile()
        # positive control: the kernel must actually BE in the auto
        # step (pallas lowers to the Mosaic "tpu_custom_call" target —
        # plain "custom_call" also matches unrelated XLA custom calls)
        # and absent from the forced-XLA step: equal losses alone can't
        # tell a working A/B from two identical dispatches
        kernel_in_hlo[knob] = "tpu_custom_call" in compiled.as_text()
        _, loss, _ = compiled(state, batch)
        losses[knob] = float(np.asarray(loss))
    diff = abs(losses["auto"] - losses["0"]) / max(abs(losses["0"]), 1e-9)
    good = diff < 5e-3  # bf16 mixed precision
    if kernel_in_hlo.get("auto") is False:
        good = False  # auto on TPU must dispatch the kernel
    if kernel_in_hlo.get("0") is True:
        good = False  # forced-XLA arm must NOT contain it, or the A/B is vacuous
    (_ok if good else _fail)(
        "train_step_pallas_vs_xla",
        losses=losses,
        rel_diff=diff,
        kernel_in_hlo=kernel_in_hlo,
    )
    return good


def main() -> int:
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(json.dumps({"check": "backend", "ok": False, "backend": backend,
                          "note": "selfcheck requires a real TPU"}))
        return 2
    _ok("backend", device=getattr(jax.devices()[0], "device_kind", "?"))
    ok = check_kernels()
    ok &= check_train_step()
    print(json.dumps({"check": "ALL", "ok": bool(ok)}))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
