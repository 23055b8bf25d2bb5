"""Plain reference: data preparation, chassis, loss, AdamW, the step loop.

Written from the published descriptions (HydraGNN ``Base``: conv ->
BatchNorm -> ReLU per layer, global mean pool, shared graph trunk, one MLP
per head, task-weighted MSE; AdamW as Loshchilov & Hutter with PyTorch's
and optax's defaults). float32 throughout, ``highest`` matmul precision,
``jax.ops.segment_*`` over the raw edge list, no custom VJP, no layout
fields, no Pallas. It imports nothing of ``hydragnn_tpu`` and takes from
the program only WHICH samples went into each step (the loader's shuffle
is the program's free choice); samples, edges, normalization, padding,
weights and every number are made here or by the harness from the seed.

Padding, of its own kind and only so that every step has one shape: one
extra node, one extra graph; padded edges are self-loops of the extra
node; ``node_w`` / ``edge_w`` / ``graph_w`` are 1 on real rows and 0 on
padding, and every statistic, pool and loss is weighted by them.

``quant`` switches every dense layer's operands to a lower precision:
``"fp8"`` (float8_e4m3fn, per-tensor scale) is the control for a
configuration that states bfloat16; ``"bf16"`` rounds operands to
bfloat16. ``fault`` plants what a broken step would do (tests, readings).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, ADAM_EPS, WEIGHT_DECAY = 0.9, 0.999, 1e-8, 1e-4
BN_EPS = 1e-5


# --------------------------------------------------------------------------
# data preparation (host, numpy)
# --------------------------------------------------------------------------


def radius_edges(pos: np.ndarray, r: float, cap: Optional[int]) -> np.ndarray:
    """[2, E] (senders, receivers): every ordered pair within ``r``, no
    self-loops; at most ``cap`` nearest senders per receiver."""
    p = pos.astype(np.float64)
    d = np.sqrt(((p[:, None, :] - p[None, :, :]) ** 2).sum(-1))
    np.fill_diagonal(d, np.inf)
    recv, send = np.nonzero(d.T <= r)  # row = receiver
    if cap is not None:
        keep = np.ones(len(recv), bool)
        for i in np.unique(recv):
            idx = np.nonzero(recv == i)[0]
            if len(idx) > cap:
                far = idx[np.argsort(d[send[idx], i], kind="stable")[cap:]]
                keep[far] = False
        recv, send = recv[keep], send[keep]
    return np.stack([send, recv]).astype(np.int32)


def prepare(raw: Sequence[Dict[str, np.ndarray]], run_config: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Min-max normalize every feature over the WHOLE data set, pick the
    input column and the targets as ``Variables_of_interest`` says, and
    build the radius graph."""
    arch = run_config["NeuralNetwork"]["Architecture"]
    voi = run_config["NeuralNetwork"]["Variables_of_interest"]
    xs = np.concatenate([s["x"] for s in raw])
    gy = np.stack([s["graph_y"] for s in raw])
    x_lo, x_hi = xs.min(0), xs.max(0)
    g_lo, g_hi = gy.min(0), gy.max(0)

    def norm(v, lo, hi):
        span = hi - lo
        return np.where(span == 0, 0.0, (v - lo) / np.where(span == 0, 1.0, span))

    edge_cache: Dict[bytes, np.ndarray] = {}
    out = []
    for s in raw:
        x = norm(s["x"], x_lo, x_hi)
        g = norm(s["graph_y"], g_lo, g_hi)
        key = s["pos"].tobytes()
        if key not in edge_cache:
            edge_cache[key] = radius_edges(s["pos"], float(arch["radius"]), arch.get("max_neighbours"))
        targets = {}
        for typ, idx, name in zip(voi["type"], voi["output_index"], voi["output_names"]):
            targets[name] = (g[idx : idx + 1] if typ == "graph" else x[:, idx : idx + 1]).astype(np.float32)
        out.append(
            {
                "x": x[:, voi["input_node_features"]].astype(np.float32),
                "pos": s["pos"].astype(np.float32),
                "edges": edge_cache[key],
                "targets": targets,
            }
        )
    return out


def degree_stats(prepared: Sequence[Dict[str, Any]], ids: Sequence[int]) -> Dict[str, float]:
    """PNA's delta: mean in-degree and mean log(in-degree + 1) over the
    nodes of the training split (Corso et al., eq. 5)."""
    deg = np.concatenate(
        [np.bincount(prepared[i]["edges"][1], minlength=len(prepared[i]["x"])) for i in ids]
    ).astype(np.float64)
    return {"lin": float(deg.mean()), "log": float(np.log(deg + 1.0).mean())}


@dataclasses.dataclass
class Batch:
    x: Any
    pos: Any
    send: Any
    recv: Any
    node_graph: Any
    node_w: Any
    edge_w: Any
    graph_w: Any
    graph_group: Any  # which device's shard a graph belongs to
    node_group: Any
    targets: Dict[str, Any]
    groups: int = dataclasses.field(metadata=dict(static=True), default=1)


jax.tree_util.register_dataclass(Batch)


def assemble(prepared, groups: Sequence[Sequence[int]], head_types: Dict[str, str],
             n_pad: int, e_pad: int, g_pad: int) -> Batch:
    """One step's graphs, concatenated; ``groups`` lists the samples of
    each device's shard (one group on one chip)."""
    xs, ps, se, re, ng, gg, ngr = [], [], [], [], [], [], []
    tg: Dict[str, list] = {k: [] for k in head_types}
    n0 = g0 = 0
    for d, ids in enumerate(groups):
        for i in ids:
            s = prepared[i]
            n = len(s["x"])
            xs.append(s["x"]); ps.append(s["pos"])
            se.append(s["edges"][0] + n0); re.append(s["edges"][1] + n0)
            ng.append(np.full(n, g0, np.int32)); ngr.append(np.full(n, d, np.int32))
            gg.append(d)
            for k in head_types:
                tg[k].append(s["targets"][k].reshape(-1, 1) if head_types[k] == "node" else s["targets"][k].reshape(1, 1))
            n0 += n; g0 += 1
    e0 = sum(len(a) for a in se)
    if n0 >= n_pad or e0 > e_pad or g0 >= g_pad:
        raise ValueError(f"step of {n0} nodes, {e0} edges, {g0} graphs exceeds pad {n_pad}, {e_pad}, {g_pad}")

    def pad(parts, size, fill, dtype, width=None):
        a = np.concatenate(parts) if parts else np.zeros((0,) + ((width,) if width else ()), dtype)
        out = np.full((size,) + a.shape[1:], fill, dtype)
        out[: len(a)] = a
        return out

    targets = {
        k: pad(v, n_pad if head_types[k] == "node" else g_pad, 0.0, np.float32) for k, v in tg.items()
    }
    return Batch(
        x=pad(xs, n_pad, 0.0, np.float32), pos=pad(ps, n_pad, 0.0, np.float32),
        send=pad(se, e_pad, n_pad - 1, np.int32), recv=pad(re, e_pad, n_pad - 1, np.int32),
        node_graph=pad(ng, n_pad, g_pad - 1, np.int32),
        node_w=pad([np.ones(n0, np.float32)], n_pad, 0.0, np.float32),
        edge_w=pad([np.ones(e0, np.float32)], e_pad, 0.0, np.float32),
        graph_w=pad([np.ones(g0, np.float32)], g_pad, 0.0, np.float32),
        graph_group=pad([np.asarray(gg, np.int32)], g_pad, 0, np.int32),
        node_group=pad(ngr, n_pad, 0, np.int32),
        targets=targets, groups=len(groups),
    )


# --------------------------------------------------------------------------
# layers
# --------------------------------------------------------------------------


def _quantize(t, quant):
    """Round a matmul operand to ``quant``'s grid (straight-through: the
    backward pass sees the rounded operands and an identity here)."""
    if quant is None:
        return t
    if quant == "bf16":
        q = t.astype(jnp.bfloat16).astype(jnp.float32)
    elif quant == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / 448.0
        q = (t / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    else:
        raise ValueError(quant)
    return t + jax.lax.stop_gradient(q - t)


def dense(p, x, quant=None, bias=True):
    y = _quantize(x, quant) @ _quantize(p["kernel"], quant)
    return y + p["bias"] if bias else y


def mlp(p, x, n_layers: int, relu_last: bool, quant=None):
    for i in range(n_layers):
        x = dense(p[f"Dense_{i}"], x, quant)
        if i < n_layers - 1 or relu_last:
            x = jax.nn.relu(x)
    return x


def batchnorm(p, x, w, group=None, groups: int = 1):
    """Training-mode BatchNorm over the real rows (biased variance). With
    ``group`` given, each device's shard is normalized by its own
    statistics (no SyncBatchNorm); without, by the whole step's."""
    if group is None or groups == 1:
        cnt = jnp.maximum(w.sum(), 1.0)
        mean = (x * w[:, None]).sum(0) / cnt
        var = jnp.maximum((x * x * w[:, None]).sum(0) / cnt - mean * mean, 0.0)
    else:
        cnt = jnp.maximum(jax.ops.segment_sum(w, group, groups), 1.0)[:, None]
        mean = jax.ops.segment_sum(x * w[:, None], group, groups) / cnt
        var = jnp.maximum(jax.ops.segment_sum(x * x * w[:, None], group, groups) / cnt - mean * mean, 0.0)
        mean, var = mean[group], var[group]
    return (x - mean) * jax.lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]


def forward(conv: Callable, params, b: Batch, mcfg: Dict[str, Any], quant=None):
    n_graphs = b.graph_w.shape[0]
    x = b.x
    for layer in range(mcfg["num_conv_layers"]):
        x = jax.checkpoint(lambda p, x_: conv(p, x_, b, mcfg, quant))(params[f"conv_{layer}"], x)
        x = jax.nn.relu(batchnorm(params[f"MaskedBatchNorm_{layer}"], x, b.node_w,
                                  None if mcfg["sync_bn"] else b.node_group, b.groups))
    cnt = jax.ops.segment_sum(b.node_w, b.node_graph, n_graphs)
    pooled = jax.ops.segment_sum(x * b.node_w[:, None], b.node_graph, n_graphs) / jnp.maximum(cnt, 1.0)[:, None]
    outs = []
    shared = None
    if "graph" in mcfg["head_types"]:
        shared = mlp(params["graph_shared"], pooled, mcfg["graph_shared_layers"], True, quant)
    for i, typ in enumerate(mcfg["head_types"]):
        if typ == "graph":
            outs.append(mlp(params[f"graph_head_{i}"], shared, mcfg["graph_head_layers"] + 1, False, quant))
        else:
            outs.append(mlp(params[f"node_head_{i}"], x, mcfg["node_head_layers"] + 1, False, quant))
    return outs


def loss_fn(conv, params, b: Batch, mcfg, quant=None, fault=None):
    """Mean over the device shards of each shard's task-weighted MSE (on
    one chip: one shard). Returns (loss the gradient is taken of,
    (graph-weighted reported loss, per-shard graph counts))."""
    gw, nw = b.graph_w, b.node_w
    if fault == "half_batch":
        # the second half of every shard's graphs is left out
        order = jnp.cumsum(gw) - 1.0
        per = gw.sum() / b.groups
        keep_g = ((order % per) < per / 2).astype(jnp.float32) * gw
        gw, nw = keep_g, nw * keep_g[b.node_graph]
        b = dataclasses.replace(b, graph_w=gw, node_w=nw)
    outs = forward(conv, params, b, mcfg, quant)
    wsum = sum(abs(w) for w in mcfg["task_weights"])
    per_group = jnp.zeros((b.groups,), jnp.float32)
    for i, (name, typ) in enumerate(zip(mcfg["head_names"], mcfg["head_types"])):
        w, grp = (gw, b.graph_group) if typ == "graph" else (nw, b.node_group)
        sq = ((outs[i] - b.targets[name]) ** 2).sum(-1) * w
        num = jax.ops.segment_sum(sq, grp, b.groups)
        den = jnp.maximum(jax.ops.segment_sum(w, grp, b.groups) * outs[i].shape[1], 1.0)
        per_group = per_group + (mcfg["task_weights"][i] / wsum) * num / den
    graphs = jax.ops.segment_sum(gw, b.graph_group, b.groups)
    reported = (per_group * graphs).sum() / jnp.maximum(graphs.sum(), 1.0)
    if fault == "no_exchange":
        return per_group[0], reported  # the first device keeps its own gradient
    return per_group.mean(), reported


def adamw(params, grads, mu, nu, t, lr):
    mu = jax.tree_util.tree_map(lambda m, g: B1 * m + (1 - B1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: B2 * v + (1 - B2) * g * g, nu, grads)
    c1, c2 = 1 - B1**t, 1 - B2**t
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS) + WEIGHT_DECAY * p),
        params, mu, nu,
    )
    return params, mu, nu


def make_step(conv, mcfg, quant=None, fault=None):
    """One jitted optimizer step: (params, mu, nu, t, rate, batch) ->
    (params, mu, nu, reported loss). The learning rate is an argument, so
    that the pass at 0 and the real steps are one compiled program."""

    @jax.jit
    def step(params, mu, nu, t, rate, b):
        (_, reported), grads = jax.value_and_grad(
            lambda p: loss_fn(conv, p, b, mcfg, quant, fault), has_aux=True
        )(params)
        if fault == "state_unchanged":
            return params, mu, nu, reported
        params, mu, nu = adamw(params, grads, mu, nu, t, rate)
        return params, mu, nu, reported

    return step


def follow(step, params0, batches: Sequence[Batch], lr: float, capture_at: Sequence[int]):
    """Drive ``len(batches)`` optimizer steps from ``params0``. Returns
    the per-step reported losses and, for each step count in
    ``capture_at``, the parameters and Adam moments after that step."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), params0)
        mu = jax.tree_util.tree_map(jnp.zeros_like, params)
        nu = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses, states = [], {}
        for k, b in enumerate(batches, start=1):
            params, mu, nu, loss = step(params, mu, nu, jnp.float32(k), jnp.float32(lr), b)
            losses.append(float(loss))
            if k in capture_at:
                states[k] = jax.device_get({"params": params, "mu": mu, "nu": nu})
    return losses, states


def model_cfg(run_config: Dict[str, Any], deg: Optional[Dict[str, float]] = None) -> Dict[str, Any]:
    arch = run_config["NeuralNetwork"]["Architecture"]
    voi = run_config["NeuralNetwork"]["Variables_of_interest"]
    heads = arch["output_heads"]
    return {
        "num_conv_layers": int(arch["num_conv_layers"]),
        "head_names": list(voi["output_names"]),
        "head_types": list(voi["type"]),
        "task_weights": [float(w) for w in arch["task_weights"]],
        "graph_shared_layers": int(heads["graph"]["num_sharedlayers"]),
        "graph_head_layers": int(heads["graph"]["num_headlayers"]),
        "node_head_layers": int(heads["node"]["num_headlayers"]),
        "sync_bn": bool(arch.get("SyncBatchNorm", False)),
        "radius": float(arch["radius"]),
        "num_gaussians": arch.get("num_gaussians"),
        "deg": deg,
    }
