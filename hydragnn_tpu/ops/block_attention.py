"""Attention among the rows of packed token documents under the
block-diffusion mask, which is a function of three integers a row.

Every row carries ``doc`` (its document: ``GraphBatch.node_graph``), ``blk``
(its index in the document divided by the block length) and ``cpy`` (1 in
the noised copy, 0 in the clean copy). A query row ``i`` may look at a key
row ``j`` (:func:`allowed`) never across documents, and inside one

  noised -> noised   where ``blk[j] == blk[i]``   (bidirectional in a block)
  noised -> clean    where ``blk[j] <  blk[i]``
  clean  -> clean    where ``blk[j] <= blk[i]``
  clean  -> noised   never

(BD3-LM's training mask, arXiv:2503.09573). Documents of a batch change
from step to step, so the mask is no static function of the row index
(which is what ``jax.experimental.pallas.ops.tpu.splash_attention`` wants);
it is data.

Two paths, one contract (``[N, Hq, Dk]`` queries, ``[N, Hkv, Dk]`` keys,
``[N, Hkv, Dv]`` values and ``[N, Hq, Dv]`` outputs, grouped-query: ``Hq /
Hkv`` query heads share a key-value head; latent attention's heads score at
one width and carry values at another, ``Dk != Dv``):

- :func:`block_attention_xla`: dense scores ``[Hq, N, N]``; the CPU path and
  the one the kernels are tested against. Never at a size where that matters.
- the Pallas kernels ``block_attention_fwd``, ``block_attention_dq``,
  ``block_attention_dkv`` (the names a device trace carries) under one
  ``jax.custom_vjp``: rows in tiles of ``tile`` on both sides, online softmax
  over the key tiles, the backward recomputing the probabilities from the
  saved log-sum-exp. ``[N, N]`` never exists: the wrapper reduces the mask
  to tile pairs (:func:`_tile_pairs`; one fused compare-and-reduce over the
  row integers, no attention-sized array) and hands the kernels the LIST of
  pairs that hold an allowed entry, so an empty tile pair costs neither a
  copy nor a product. A grid step works a block of key-value heads with all
  of their query heads (:func:`kv_heads_per_step`: about
  ``HEADS_PER_STEP`` query heads, whatever the grouping), on one mask tile
  and one copy of each key and value tile; a query head's arithmetic does
  not depend on the block it is worked in.

``HYDRAGNN_PALLAS`` decides as for every kernel of this package
(``ops/segment_pallas.py``): kernels on a TPU, interpreted anywhere under
``interpret``, never under ``0``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from hydragnn_tpu.ops.segment_pallas import _interpret_mode, _kernel_eligible

try:  # as ops/segment_pallas.py: a backend without Pallas keeps the XLA path
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pl = pltpu = None

TILE = 512  # rows a tile, queries and keys alike
HEADS_PER_STEP = 8  # query heads a grid step: one mask tile and one grid step serve them all
META = 8  # the row integers travel as [N, META] and [META, N] int32: doc, blk, cpy, then zeros
NEG = -1e30


def allowed(qdoc, qblk, qcpy, kdoc, kblk, kcpy):
    """The mask, for any shapes that broadcast."""
    to_noised = (kcpy == 1) & (qcpy == 1) & (qblk == kblk)
    to_clean = (kcpy != 1) & (kblk < qblk + 1 - qcpy)
    return (qdoc == kdoc) & (to_noised | to_clean)


def dense_mask(doc, blk, cpy):
    """``[N, N]``: query row i, key row j. For the dense path and for the
    reduction to tile pairs, where XLA fuses it into the reduce."""
    return allowed(doc[:, None], blk[:, None], cpy[:, None], doc[None, :], blk[None, :], cpy[None, :])


def kernel_mode() -> str:
    """``"pallas"``, ``"interpret"`` or ``"xla"``, by the knob contract that
    ``ops/segment_pallas.py`` owns."""
    if not _kernel_eligible(indices_are_sorted=True):
        return "xla"
    return "interpret" if _interpret_mode() else "pallas"


def block_attention_xla(q, k, v, doc, blk, cpy, scale: float):
    """Dense reference path: scores in float32, ``[Hq, N, N]``."""
    n, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qg = q.reshape(n, hkv, g, d)
    s = jnp.einsum("ihgd,jhd->hgij", qg, k, preferred_element_type=jnp.float32) * scale
    s = jnp.where(dense_mask(doc, blk, cpy)[None, None], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hgij,jhd->ihgd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    return o.reshape(n, hq, v.shape[-1]).astype(q.dtype)


# --------------------------------------------------------------------------
# which tile pairs hold an allowed entry
# --------------------------------------------------------------------------


def _pair_list(active):
    """``active`` [nt, nt] bool, rows = the major side. The pairs in
    row-major order as five int32 arrays for scalar prefetch: major tile,
    minor tile, first-of-its-major, last-of-its-major (each ``[nt * nt]``;
    past the end the last pair is repeated, so that the skipped steps ask
    for no new block) and the count ``[1]``."""
    nt = active.shape[0]
    size = nt * nt
    flat = active.reshape(-1)
    count = flat.sum().astype(jnp.int32)
    idx = jnp.nonzero(flat, size=size, fill_value=0)[0].astype(jnp.int32)
    step = jnp.arange(size, dtype=jnp.int32)
    idx = jnp.where(step < count, idx, idx[jnp.maximum(count - 1, 0)])
    major, minor = idx // nt, idx % nt
    first = (step == 0) | (major != jnp.concatenate([major[:1], major[:-1]]))
    last = (step == count - 1) | (major != jnp.concatenate([major[1:], major[-1:]]))
    return major, minor, first.astype(jnp.int32), last.astype(jnp.int32), count[None]


def _tile_pairs(doc, blk, cpy, tile: int):
    """(pairs by query tile, pairs by key tile). The diagonal is always
    active, so every tile of either side is visited and written."""
    n = doc.shape[0]
    nt = n // tile
    active = dense_mask(doc, blk, cpy).reshape(nt, tile, nt, tile).any(axis=(1, 3)) | jnp.eye(nt, dtype=bool)
    return _pair_list(active), _pair_list(active.T)


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------


def _mask_tile(qm_ref, km_ref):
    qm, km = qm_ref[...], km_ref[...]  # [T, META], [META, T]
    return allowed(qm[:, 0:1], qm[:, 1:2], qm[:, 2:3], km[0:1, :], km[1:2, :], km[2:3, :])


def _each_head(k_ref, v_ref, group: int, kvb: int, body):
    """``body(h, j, k, v)`` for each query head ``h`` of the step's block:
    ``j`` indexes its key-value head in the key-side blocks and scratch
    (``...`` where the step works ONE key-value head: those blocks then hold
    that head alone), ``k`` and ``v`` are that head's tiles."""
    if kvb == 1:
        k, v = k_ref[...], v_ref[...]

        def head(h, carry):
            body(h, ..., k, v)
            return carry

        jax.lax.fori_loop(0, group, head, 0)
        return

    def head(h, carry):
        j = jax.lax.div(h, group) if group > 1 else h
        body(h, j, k_ref[j], v_ref[j])
        return carry

    jax.lax.fori_loop(0, kvb * group, head, 0)


def _fwd_kernel(qt_ref, kt_ref, first_ref, last_ref, count_ref, q_ref, k_ref, v_ref, qm_ref, km_ref,
                o_ref, lse_ref, m_sc, l_sc, acc_sc, *, scale: float, group: int, kvb: int):
    s = pl.program_id(1)

    @pl.when(s < count_ref[0])
    def _():
        @pl.when(first_ref[s] == 1)
        def _():
            m_sc[...] = jnp.full(m_sc.shape, NEG, jnp.float32)
            l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
            acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

        mask = _mask_tile(qm_ref, km_ref)

        def head(h, j, k, v):
            sc = jax.lax.dot_general(q_ref[h], k, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32) * scale
            sc = jnp.where(mask, sc, NEG)
            m_prev = m_sc[h]
            m_new = jnp.maximum(m_prev, sc.max(axis=1, keepdims=True))
            p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_sc[h] = alpha * l_sc[h] + p.sum(axis=1, keepdims=True)
            acc_sc[h] = alpha * acc_sc[h] + jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_sc[h] = m_new

        _each_head(k_ref, v_ref, group, kvb, head)

        @pl.when(last_ref[s] == 1)
        def _():
            l = l_sc[...]
            o_ref[...] = (acc_sc[...] / l).astype(o_ref.dtype)
            lse_ref[...] = m_sc[...] + jnp.log(l)


def _dq_kernel(qt_ref, kt_ref, first_ref, last_ref, count_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
               qm_ref, km_ref, dq_ref, dq_sc, *, scale: float, group: int, kvb: int):
    s = pl.program_id(1)

    @pl.when(s < count_ref[0])
    def _():
        @pl.when(first_ref[s] == 1)
        def _():
            dq_sc[...] = jnp.zeros(dq_sc.shape, jnp.float32)

        mask = _mask_tile(qm_ref, km_ref)

        def head(h, j, k, v):
            sc = jax.lax.dot_general(q_ref[h], k, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32) * scale
            p = jnp.where(mask, jnp.exp(sc - lse_ref[h]), 0.0)
            dp = jax.lax.dot_general(do_ref[h], v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            ds = p * (dp - dl_ref[h]) * scale
            dq_sc[h] = dq_sc[h] + jnp.dot(ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

        _each_head(k_ref, v_ref, group, kvb, head)

        @pl.when(last_ref[s] == 1)
        def _():
            dq_ref[...] = dq_sc[...].astype(dq_ref.dtype)


def _dkv_kernel(kt_ref, qt_ref, first_ref, last_ref, count_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                qm_ref, km_ref, dk_ref, dv_ref, dk_sc, dv_sc, *, scale: float, group: int, kvb: int):
    s = pl.program_id(1)

    @pl.when(s < count_ref[0])
    def _():
        @pl.when(first_ref[s] == 1)
        def _():
            dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)
            dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

        mask = _mask_tile(qm_ref, km_ref)

        def head(h, j, k, v):
            q, do = q_ref[h], do_ref[h]
            sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
            p = jnp.where(mask, jnp.exp(sc - lse_ref[h]), 0.0)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            ds = p * (dp - dl_ref[h]) * scale
            dv_sc[j] += jax.lax.dot_general(p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32)
            dk_sc[j] += jax.lax.dot_general(ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32)

        _each_head(k_ref, v_ref, group, kvb, head)

        @pl.when(last_ref[s] == 1)
        def _():
            dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
            dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


_VMEM_LIMIT = 64 * 2**20  # the backward's blocks and [T, T] temporaries pass the 16 MiB default at T = 512


def _vmem_bytes(qh: int, kh: int, tile: int, dk: int, dv: int) -> int:
    """What the hungriest of the three kernels holds in VMEM for a grid step
    of ``qh`` query and ``kh`` key-value heads: every block twice (the
    pipeline's two buffers) with bfloat16 operands, each width padded to
    whole 128-lane vregs (192 takes 256), the float32 ``[.., tile, 1]`` rows
    padded to 128 lanes, the row integers, the float32 scratch, and four
    ``[tile, tile]`` float32 temporaries. (For a described v5e at tile 512
    this is 1.5 to 3.5 MiB above the least limit the compiler accepts, over
    4 to 16 query heads at widths 192 / 128 and 8 to 16 at 128 / 128.)"""
    dk, dv = -(-dk // 128) * 128, -(-dv // 128) * 128
    row = qh * tile * 128 * 4
    meta = tile * 128 * 4 + META * tile * 4
    q_side, kv_side = qh * tile * (dk + dv) * 2, kh * tile * (dk + dv) * 2  # q with o or do; k with v
    fwd = 2 * (q_side + kv_side + row + meta) + 2 * row + qh * tile * dv * 4
    dq = 2 * (q_side + kv_side + 2 * row + meta + qh * tile * dk * 2) + qh * tile * dk * 4
    dkv = 2 * (q_side + 2 * kv_side + 2 * row + meta) + kh * tile * (dk + dv) * 4
    return max(fwd, dq, dkv) + 4 * tile * tile * 4


def kv_heads_per_step(hq: int, hkv: int, dk: int, dv: int, tile: int = TILE) -> int:
    """Key-value heads a grid step of the kernels works, with all of their
    ``hq / hkv`` query heads: the largest divisor of ``hkv`` that gives at
    most ``HEADS_PER_STEP`` query heads and whose blocks fit the kernels'
    VMEM (:func:`_vmem_bytes`); 1 where none does."""
    group = hq // hkv
    fits = [b for b in range(1, hkv + 1)
            if hkv % b == 0 and b * group <= HEADS_PER_STEP and _vmem_bytes(b * group, b, tile, dk, dv) <= _VMEM_LIMIT]
    return max(fits, default=1)


def attention_grid(hq: int, hkv: int, dk: int, dv: int, rows: int, tile: int = TILE) -> dict:
    """The kernels' grid for ``rows`` row slots: the tile, the query and
    key-value heads a grid step works, and the grid steps of one call
    (head blocks times the ``nt * nt`` slots of a pair list, skipped ones
    included)."""
    kvb = kv_heads_per_step(hq, hkv, dk, dv, tile)
    nt = -(-rows // tile)
    return {"tile": tile, "query_heads_per_step": kvb * (hq // hkv), "kv_heads_per_step": kvb,
            "grid_steps_per_call": hkv // kvb * nt * nt}


def _params(interpret: bool):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT)}


def _specs(group: int, kvb: int, tile: int, dk: int, dv: int, q_side_major: bool):
    """Block specs of a head-major call over ``kvb`` key-value heads and
    their ``kvb * group`` query heads a grid step: queries and keys at the
    scoring width ``dk``, values and outputs (and their cotangents) at
    ``dv``; each block takes the whole last dimension, and the key side is
    squeezed to one head where ``kvb`` is 1. ``a`` / ``b`` are the
    prefetched major / minor tile lists: with ``q_side_major`` the query
    tile is the major one (forward, dq), else the key tile (dkv). Returns
    (q, k, v, o, row, qm, km)."""

    def qi(g, s, a, b, *_):
        return (a if q_side_major else b)[s]

    def ki(g, s, a, b, *_):
        return (b if q_side_major else a)[s]

    def query_side(d):
        return pl.BlockSpec((kvb * group, tile, d), lambda g, s, *p: (g, qi(g, s, *p), 0))

    def key_side(d):
        return pl.BlockSpec((None if kvb == 1 else kvb, tile, d), lambda g, s, *p: (g, ki(g, s, *p), 0))

    row = pl.BlockSpec((kvb * group, tile, 1), lambda g, s, *p: (g, qi(g, s, *p), 0))
    qm = pl.BlockSpec((tile, META), lambda g, s, *p: (qi(g, s, *p), 0))
    km = pl.BlockSpec((META, tile), lambda g, s, *p: (0, ki(g, s, *p)))
    return query_side(dk), key_side(dk), key_side(dv), query_side(dv), row, qm, km


def _forward(q, k, v, qmeta, kmeta, pairs, scale, tile, interpret, kvb):
    hq, n, dk = q.shape
    hkv, _, dv = v.shape
    group = hq // hkv
    heads = kvb * group
    qs, ks, vs, os_, rows, qm, km = _specs(group, kvb, tile, dk, dv, True)
    steps = pairs[0].shape[0]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, group=group, kvb=kvb),
        out_shape=(jax.ShapeDtypeStruct((hq, n, dv), q.dtype), jax.ShapeDtypeStruct((hq, n, 1), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(hkv // kvb, steps),
            in_specs=[qs, ks, vs, qm, km], out_specs=(os_, rows),
            scratch_shapes=[pltpu.VMEM((heads, tile, 1), jnp.float32), pltpu.VMEM((heads, tile, 1), jnp.float32),
                            pltpu.VMEM((heads, tile, dv), jnp.float32)],
        ),
        interpret=interpret, name="block_attention_fwd", **_params(interpret),
    )(*pairs, q, k, v, qmeta, kmeta)


def _dq(q, k, v, do, lse, delta, qmeta, kmeta, pairs_q, scale, tile, interpret, kvb):
    hq, n, dk = q.shape
    hkv, _, dv = v.shape
    group = hq // hkv
    qs, ks, vs, os_, rows, qm, km = _specs(group, kvb, tile, dk, dv, True)
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, group=group, kvb=kvb),
        out_shape=jax.ShapeDtypeStruct((hq, n, dk), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(hkv // kvb, pairs_q[0].shape[0]),
            in_specs=[qs, ks, vs, os_, rows, rows, qm, km], out_specs=qs,
            scratch_shapes=[pltpu.VMEM((kvb * group, tile, dk), jnp.float32)],
        ),
        interpret=interpret, name="block_attention_dq", **_params(interpret),
    )(*pairs_q, q, k, v, do, lse, delta, qmeta, kmeta)


def _dkv(q, k, v, do, lse, delta, qmeta, kmeta, pairs_k, scale, tile, interpret, kvb):
    hq, n, dk = q.shape
    hkv, _, dv = v.shape
    group = hq // hkv
    qs, ks, vs, os_, rows, qm, km = _specs(group, kvb, tile, dk, dv, False)
    acc = (tile,) if kvb == 1 else (kvb, tile)  # an accumulator a key-value head of the block
    return pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, group=group, kvb=kvb),
        out_shape=(jax.ShapeDtypeStruct((hkv, n, dk), k.dtype), jax.ShapeDtypeStruct((hkv, n, dv), v.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(hkv // kvb, pairs_k[0].shape[0]),
            in_specs=[qs, ks, vs, os_, rows, rows, qm, km], out_specs=(ks, vs),
            scratch_shapes=[pltpu.VMEM(acc + (dk,), jnp.float32), pltpu.VMEM(acc + (dv,), jnp.float32)],
        ),
        interpret=interpret, name="block_attention_dkv", **_params(interpret),
    )(*pairs_k, q, k, v, do, lse, delta, qmeta, kmeta)


def _head_block(q, v, tile):
    return kv_heads_per_step(q.shape[0], v.shape[0], q.shape[2], v.shape[2], tile)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _attend(q, k, v, qmeta, pairs, scale, tile, interpret):
    """Head-major: q ``[Hq, N, Dk]``, k ``[Hkv, N, Dk]``, v ``[Hkv, N, Dv]``, ``N`` a multiple of ``tile``."""
    return _forward(q, k, v, qmeta, qmeta.T, pairs[0], scale, tile, interpret, _head_block(q, v, tile))[0]


def _attend_fwd(q, k, v, qmeta, pairs, scale, tile, interpret):
    o, lse = _forward(q, k, v, qmeta, qmeta.T, pairs[0], scale, tile, interpret, _head_block(q, v, tile))
    return o, (q, k, v, o, lse, qmeta, pairs)


def _attend_bwd(scale, tile, interpret, res, do):
    q, k, v, o, lse, qmeta, pairs = res
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1, keepdims=True)
    kvb = _head_block(q, v, tile)
    args = (q, k, v, do, lse, delta, qmeta, qmeta.T)
    dq = _dq(*args, pairs[0], scale, tile, interpret, kvb)
    dk, dv = _dkv(*args, pairs[1], scale, tile, interpret, kvb)
    return dq, dk, dv, None, None


_attend.defvjp(_attend_fwd, _attend_bwd)


def attention_plan(doc, blk, cpy, tile: int = TILE):
    """What the kernels need of a batch's rows, ONCE a step (every layer's
    mask is the same): the row integers padded to whole tiles (a padding
    row is a document of its own kind, -1, and sees its own tile's padding
    only) and the two pair lists. ``(qmeta [Np, META], pairs)``."""
    n = doc.shape[0]
    pad = -n % tile
    cols = [jnp.pad(doc.astype(jnp.int32), (0, pad), constant_values=-1),
            jnp.pad(blk.astype(jnp.int32), (0, pad)), jnp.pad(cpy.astype(jnp.int32), (0, pad))]
    qmeta = jnp.stack(cols + [jnp.zeros_like(cols[0])] * (META - 3), axis=1)
    return qmeta, _tile_pairs(*cols, tile)


def block_attention(q, k, v, doc, blk, cpy, scale: float, plan=None, tile: int = TILE) -> jnp.ndarray:
    """``softmax(q k^T * scale + mask) v``: q ``[N, Hq, Dk]``, k ``[N, Hkv,
    Dk]``, v ``[N, Hkv, Dv]``, the three row integers ``[N]``; returns
    ``[N, Hq, Dv]`` in q's dtype. ``plan``: :func:`attention_plan` of the same rows, where
    the caller has several layers to run over them. On the chip float32
    operands are rounded to bfloat16 first (what a float32 product is at
    the default precision there); the softmax and every sum are float32."""
    mode = kernel_mode()
    if mode == "xla" or pl is None:
        return block_attention_xla(q, k, v, doc, blk, cpy, scale)
    interpret = mode == "interpret"
    n = q.shape[0]
    if plan is None:
        plan = attention_plan(doc, blk, cpy, tile)
    qmeta, pairs = plan
    pad = qmeta.shape[0] - n
    dtype = q.dtype
    if not interpret and dtype == jnp.float32:
        q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))

    def heads_first(a):
        return jnp.pad(a, ((0, pad), (0, 0), (0, 0))).transpose(1, 0, 2)

    o = _attend(heads_first(q), heads_first(k), heads_first(v), qmeta, pairs, float(scale), int(tile), interpret)
    return o.transpose(1, 0, 2)[:n].astype(dtype)
