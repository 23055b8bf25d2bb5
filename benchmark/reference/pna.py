"""PNA convolution (Corso et al. 2020, as PyG ``PNAConv`` with towers=1,
pre_layers=1, post_layers=1, divide_input=False, and HydraGNN's
``PNAStack`` choice of aggregators mean/min/max/std and scalers
identity/amplification/attenuation/linear).

Per edge j -> i: m_ij = W_pre [x_i, x_j] + b_pre. Per node: the four
aggregates of its incoming messages, each under the four degree scalers,
then out_i = W_post [x_i, 16 scaled aggregates] + b_post. The message is
formed per edge, as published: no node-level factoring, no shift trick.
(``assemble`` lists a step's edges receiver-major, so the segment ids are
sorted: said to XLA, because an unsorted scatter on the TPU is slow.)
"""

import jax
import jax.numpy as jnp

from reference.common import _quantize


def conv(p, x, b, mcfg, quant=None):
    n = x.shape[0]
    w = b.edge_w[:, None]
    h = jnp.concatenate([x[b.recv], x[b.send]], axis=-1)
    msg = _quantize(h, quant) @ _quantize(p["pre_kernel"], quant) + p["pre_bias"]
    cnt = jax.ops.segment_sum(b.edge_w, b.recv, n, indices_are_sorted=True)
    has = (cnt > 0)[:, None]
    safe = jnp.maximum(cnt, 1.0)[:, None]
    mean = jax.ops.segment_sum(msg * w, b.recv, n, indices_are_sorted=True) / safe
    mean_sq = jax.ops.segment_sum(msg * msg * w, b.recv, n, indices_are_sorted=True) / safe
    big = jnp.finfo(jnp.float32).max
    mx = jax.ops.segment_max(jnp.where(w > 0, msg, -big), b.recv, n, indices_are_sorted=True)
    mn = jax.ops.segment_min(jnp.where(w > 0, msg, big), b.recv, n, indices_are_sorted=True)
    std = jnp.sqrt(jax.nn.relu(mean_sq - mean * mean) + 1e-5)
    agg = jnp.concatenate(
        [jnp.where(has, mean, 0.0), jnp.where(has, mn, 0.0), jnp.where(has, mx, 0.0), std], axis=-1
    )
    deg = jnp.where(b.node_w > 0, jnp.maximum(cnt, 1.0), 1.0)[:, None]
    log_deg = jnp.log(deg + 1.0)
    scaled = jnp.concatenate(
        [agg, agg * (log_deg / mcfg["deg"]["log"]), agg * (mcfg["deg"]["log"] / log_deg),
         agg * (deg / mcfg["deg"]["lin"])], axis=-1,
    )
    out = jnp.concatenate([x, scaled], axis=-1)
    return _quantize(out, quant) @ _quantize(p["Dense_0"]["kernel"], quant) + p["Dense_0"]["bias"]
