"""Fully fused message-passing conv layer: gather -> edge MLP -> scatter
in ONE Pallas kernel.

Every conv flavor in ``models/convs.py`` bottoms out in the same
three-op chain over the edge set:

    v_e  = x[send_e]                      (CSR row gather, [E, Hin])
    m_e  = f(v_e)                         (edge network: matmul+bias+act,
                                           gating product, or identity)
    out  = segment_sum(mask_e * m_e)      (scatter into receivers)

Unfused, that chain materializes v and m in HBM and reads them back —
2-4 full [E, H] HBM round trips per conv layer plus XLA's serial
per-row scatter (docs/PERF.md r03-r05 traces put these at the top of
every step profile). This kernel runs the whole chain inside VMEM:

  - grid over receiver node blocks with scalar-prefetched CSR block
    pointers (receivers arrive sorted — the loader contract every conv
    already relies on);
  - per edge chunk, the sender rows are fetched with the windowed
    gather (senders are unsorted-but-local for batched graphs: a
    scalar-prefetched per-chunk window plan bounds each chunk's row
    span, the same plan machinery as ``segment_pallas``'s bcast
    kernel) and reduced to output rows by one-hot MXU matmuls;
  - the edge network runs on the gathered chunk in registers/VMEM:
    up to two linear branches ``act_k(v @ W_k + b_k + rtab_k[recv_e]
    + eterm_k)`` combined by elementwise product (the CGCNN
    sigmoid*softplus gate), an optional per-edge ``scale`` factor
    (the SchNet filter), or plain identity (GIN/SAGE/MFC
    aggregation). Receiver-side terms are gathered from the
    node-blocked ``rtab`` operand with the transpose of the scatter
    one-hot — they never touch edge-space HBM;
  - DOUBLE-BUFFERED HBM->VMEM DMA at two levels: edge-chunk operands
    (ids, mask, eterm, scale) prefetch chunk k+1 while chunk k
    computes, and the sender-window DMA for chunk k+1 is issued
    BEFORE chunk k's MLP/scatter matmuls so the gather of the next
    chunk overlaps the compute of the current one.

Training rides a hand-written VJP built from the existing fast
machinery (``segment_pallas``): the cotangent gather is the sorted
CSR-broadcast kernel, grad_x scatters through the local-window segment
sum (no edge permute), rtab grads are a sorted segment sum, and W/b
grads are plain MXU contractions. The forward's XLA fallback
(`use_kernel=False`) computes the identical composition with plain
jnp ops — the numerical contract the kernel is tested against in
interpret mode — and both paths share the same custom VJP, so
gradient semantics cannot diverge between them.

SPMD: the kernel call is wrapped in ``custom_partitioning`` with an
edge-axis rule — GSPMD sharding the edge-space operands on their
leading axis runs the kernel per shard (contiguous receiver-sorted
slices keep the CSR contract) and one ``psum`` combines the node-space
partials. Inside ``shard_map`` the operands are already local and the
wrapper lowers to the plain kernel. ``vmap`` contexts force the XLA
path via the shared ``HYDRAGNN_PALLAS`` knob machinery
(``xla_segment_ops``), exactly like the segment kernels.

Knob contract: ``HYDRAGNN_PALLAS`` as in ``segment_pallas`` (auto =
kernel on TPU, ``interpret`` forces interpret mode on any backend for
CPU tests, ``0`` forces XLA). The BN/CE block/chunk sizes are imported
from ``segment_pallas``, whose import-time defaults come from the
committed sweep table ``TUNE_TILES.json`` (``tools/tune_tiles.py
--save``; explicit HYDRAGNN_BN/CE env knobs always win). Widths are
lane-padded to 128 in and sliced back out. Output is float32 (the
segment-sum accumulation contract); callers cast.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from hydragnn_tpu.utils import knobs

from hydragnn_tpu.ops.segment_pallas import (
    ALIGN,
    BN,
    BW,
    CE,
    _interpret_mode,
    _kernel_eligible,
    _match_vma,
    _vma_of,
    _window_plan_local,
    gather_rows_local_fast,
    gather_rows_sorted_fast,
    pallas_available,
    segment_sum_fast,
    segment_sum_local_fast,
)

# Edge-network activations: (f, df) where df takes (pre, f(pre)) so the
# derivative can reuse the forward value (sigmoid, tanh). All run in f32
# inside the kernel; the XLA fallback applies them in the compute dtype.
_ACTS = {
    "none": (lambda x: x, lambda x, a: jnp.ones_like(x)),
    "relu": (
        lambda x: jnp.maximum(x, jnp.zeros_like(x)),
        lambda x, a: (x > 0).astype(x.dtype),
    ),
    "sigmoid": (jax.nn.sigmoid, lambda x, a: a * (1.0 - a)),
    "softplus": (jax.nn.softplus, lambda x, a: jax.nn.sigmoid(x)),
    "tanh": (jnp.tanh, lambda x, a: 1.0 - a * a),
    "silu": (
        jax.nn.silu,
        lambda x, a: jax.nn.sigmoid(x) * (1.0 + x * (1.0 - jax.nn.sigmoid(x))),
    ),
}


def fused_conv_active() -> bool:
    """Would :func:`fused_conv` lower to the Pallas kernel here? Shares
    the segment kernels' knob/backend contract (sorted receivers are
    the caller contract, so only the knob/backend part is checked)."""
    return pallas_available() and _kernel_eligible(indices_are_sorted=True)


def _pad128(h: int) -> int:
    return ((h + 127) // 128) * 128


def _pad_cols(a: Optional[jnp.ndarray], w: int) -> Optional[jnp.ndarray]:
    if a is None or a.shape[-1] == w:
        return a
    return jnp.concatenate(
        [a, jnp.zeros(a.shape[:-1] + (w - a.shape[-1],), a.dtype)], axis=-1
    )


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------


def _make_fused_kernel(k_br, acts, has_rtab, has_eterm, has_scale, hp, hop,
                       x_bf16):
    """Build the kernel closure for one static layout. Ref layout (after
    the two scalar-prefetch refs):

      inputs : x, send, recv, mask, [w, b], [rtab], [eterm], [scale]
      outputs: out
      scratch: win(2,BW,hp), send(2,1,CE), recv(2,1,CE), mask(2,1,CE),
               [eterm(2,CE,k*hop)], [scale(2,CE,hop)], gacc(CE,hp) f32,
               sem_ids(2,S), sem_win(2,)
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_id_streams = 3 + (1 if has_eterm else 0) + (1 if has_scale else 0)

    def kernel(ptr_ref, plan_ref, *refs):
        it = iter(refs)
        x_hbm = next(it)
        send_hbm = next(it)
        recv_hbm = next(it)
        mask_hbm = next(it)
        w_ref = next(it) if k_br else None
        b_ref = next(it) if k_br else None
        rtab_ref = next(it) if has_rtab else None
        eterm_hbm = next(it) if has_eterm else None
        scale_hbm = next(it) if has_scale else None
        out_ref = next(it)
        win_vmem = next(it)
        send_vmem = next(it)
        recv_vmem = next(it)
        mask_vmem = next(it)
        eterm_vmem = next(it) if has_eterm else None
        scale_vmem = next(it) if has_scale else None
        gacc_ref = next(it)
        sem_ids = next(it)
        sem_win = next(it)

        i = pl.program_id(0)
        # Occupancy clamp (ISSUE 10): plan row 3 carries the index after
        # the last slot that can hold a REAL edge. Everything past it is
        # padding whose messages the mask would zero anyway — bounding
        # [lo, hi) at the occupancy makes fully-padded tail chunks cost
        # zero DMAs and zero MXU work while leaving every contributing
        # term bit-identical (skipped chunks contributed exact +0: the
        # mask factor zeroes their messages before the scatter, and the
        # bf16 split of 0 is 0).
        occ = plan_ref[3, 0]
        lo = jnp.minimum(ptr_ref[i], occ)
        hi = jnp.minimum(ptr_ref[i + 1], occ)
        n_clamp = plan_ref[2, 0]
        out_ref[:] = jnp.zeros_like(out_ref)
        k0 = lo // CE
        k1 = (hi + CE - 1) // CE

        def id_dmas(slot, k):
            start = pl.multiple_of(k * CE, CE)
            cps = [
                pltpu.make_async_copy(
                    send_hbm.at[:, pl.ds(start, CE)], send_vmem.at[slot],
                    sem_ids.at[slot, 0],
                ),
                pltpu.make_async_copy(
                    recv_hbm.at[:, pl.ds(start, CE)], recv_vmem.at[slot],
                    sem_ids.at[slot, 1],
                ),
                pltpu.make_async_copy(
                    mask_hbm.at[:, pl.ds(start, CE)], mask_vmem.at[slot],
                    sem_ids.at[slot, 2],
                ),
            ]
            s = 3
            if has_eterm:
                cps.append(
                    pltpu.make_async_copy(
                        eterm_hbm.at[pl.ds(start, CE), :], eterm_vmem.at[slot],
                        sem_ids.at[slot, s],
                    )
                )
                s += 1
            if has_scale:
                cps.append(
                    pltpu.make_async_copy(
                        scale_hbm.at[pl.ds(start, CE), :], scale_vmem.at[slot],
                        sem_ids.at[slot, s],
                    )
                )
            return cps

        def win_dma(slot, wstart):
            return pltpu.make_async_copy(
                x_hbm.at[
                    pl.ds(
                        pl.multiple_of(jnp.minimum(wstart, n_clamp), ALIGN), BW
                    ),
                    :,
                ],
                win_vmem.at[slot],
                sem_win.at[slot],
            )

        @pl.when(k0 < k1)
        def _warmup():
            for cp in id_dmas(k0 % 2, k0):
                cp.start()
            win_dma(k0 % 2, plan_ref[0, k0]).start()

        def chunk_body(k, _):
            slot = k % 2

            @pl.when(k + 1 < k1)
            def _prefetch_ids():
                for cp in id_dmas((k + 1) % 2, k + 1):
                    cp.start()

            for cp in id_dmas(slot, k):
                cp.wait()
            send = send_vmem[slot][0, :]  # [CE]
            astart = plan_ref[0, k]
            wcnt = plan_ref[1, k]
            gacc_ref[:] = jnp.zeros_like(gacc_ref)

            # -- windowed sender gather (exact one-hot row copies) --
            def window_body(w, _):
                wslot = (k + w) % 2
                wstart = astart + w * BW

                @pl.when(w + 1 < wcnt)
                def _prefetch_win():
                    win_dma((k + w + 1) % 2, wstart + BW).start()

                win_dma(wslot, wstart).wait()
                cstart = jnp.minimum(wstart, n_clamp)
                local = send - cstart
                in_range = (send >= wstart) & (send < wstart + BW)
                local = jnp.where(in_range, local, -1)
                onehot = (
                    local[:, None]
                    == jax.lax.broadcasted_iota(jnp.int32, (CE, BW), 1)
                )
                win = win_vmem[wslot]
                if win.dtype == jnp.float32:
                    gacc_ref[:] += jax.lax.dot_general(
                        onehot.astype(jnp.float32), win,
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST,
                    )
                else:
                    gacc_ref[:] += jax.lax.dot_general(
                        onehot.astype(win.dtype), win,
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                return 0

            jax.lax.fori_loop(0, wcnt, window_body, 0)

            # Issue chunk k+1's gather DMA BEFORE the MLP/scatter
            # matmuls below: the next chunk's HBM read overlaps this
            # chunk's compute (the tentpole's cross-block double
            # buffering; its target buffer's previous DMA was waited
            # inside the window loop above).
            @pl.when(k + 1 < k1)
            def _prefetch_next_win():
                win_dma((k + 1) % 2, plan_ref[0, k + 1]).start()

            v = gacc_ref[:]  # [CE, hp] f32, exact copies of x rows
            rows = jax.lax.broadcasted_iota(jnp.int32, (BN, CE), 0) + i * BN
            onehot_r = recv_vmem[slot] == rows  # [BN, CE]
            mf = mask_vmem[slot][0, :].astype(jnp.float32)[:, None]  # [CE,1]

            if k_br:
                # edge MLP in VMEM: f32 accumulation throughout; bf16
                # models round only the operands/messages (matching the
                # XLA fallback's compute dtype within tolerance)
                if x_bf16:
                    pre = jax.lax.dot_general(
                        v.astype(jnp.bfloat16), w_ref[:],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                else:
                    pre = jax.lax.dot_general(
                        v, w_ref[:], (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST,
                    )
                pre = pre + b_ref[:]  # [1, k*hop] broadcasts
                if has_rtab:
                    # receiver-side term: transpose of the scatter
                    # one-hot against the node-blocked table — exact
                    # row copies for in-block receivers; stray edges
                    # (chunk overhang) get garbage rows but never
                    # scatter into this block
                    rt = rtab_ref[:]
                    if rt.dtype == jnp.float32:
                        pre = pre + jax.lax.dot_general(
                            onehot_r.astype(jnp.float32), rt,
                            (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST,
                        )
                    else:
                        pre = pre + jax.lax.dot_general(
                            onehot_r.astype(rt.dtype), rt,
                            (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        )
                if has_eterm:
                    pre = pre + eterm_vmem[slot].astype(jnp.float32)
                msg = None
                for kk in range(k_br):
                    p = pre[:, kk * hop : (kk + 1) * hop]
                    a = _ACTS[acts[kk]][0](p)
                    msg = a if msg is None else msg * a
            else:
                msg = v
            if has_scale:
                msg = msg * scale_vmem[slot].astype(jnp.float32)
            msg = msg * mf

            # -- masked one-hot scatter into the out block (f32 acc) --
            onehot_t = onehot_r.astype(jnp.bfloat16)
            if x_bf16:
                # bf16 models: the XLA fallback's message is bf16 too,
                # so rounding here matches; products are then native-MXU
                out_ref[:] += jax.lax.dot_general(
                    onehot_t, msg.astype(jnp.bfloat16),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            else:
                # f32 messages: 3-term bf16 split (hi+mid+lo carries the
                # full f32 significand) x exact 0/1 one-hot — the same
                # scheme as segment_pallas._csr_chunk_loop's f32 path
                r = msg
                hi_t = r.astype(jnp.bfloat16)
                r1 = r - hi_t.astype(jnp.float32)
                mid_t = r1.astype(jnp.bfloat16)
                lo_t = (r1 - mid_t.astype(jnp.float32)).astype(jnp.bfloat16)
                for term in (hi_t, mid_t, lo_t):
                    out_ref[:] += jax.lax.dot_general(
                        onehot_t, term, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
            return 0

        jax.lax.fori_loop(k0, k1, chunk_body, 0)

    return kernel, n_id_streams


def _fused_kernel_call(x, senders, receivers, mask, w_cat, b_cat, rtab,
                       eterm, scale, real_edges, num_segments, spec,
                       interpret):
    """Shard-local fused kernel invocation. Operands are pre-padded to
    128-lane widths by the dispatcher; receivers sorted ascending.
    ``real_edges`` ([1] int32 or None) bounds the chunk loop — None
    processes the full edge pad (always correct; `ptr <= e` already)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k_br, acts = spec
    e = senders.shape[0]
    n, hp = x.shape
    hop = (w_cat.shape[1] // k_br) if k_br else hp
    xd = x.dtype

    n_pad_out = ((num_segments + BN - 1) // BN) * BN
    # sender gather table padding: window DMAs need BW rows headroom
    n_pad_t = max(((n + ALIGN - 1) // ALIGN) * ALIGN, BW)
    if n_pad_t != n:
        x = jnp.concatenate([x, jnp.zeros((n_pad_t - n, hp), xd)], axis=0)
    e_pad = ((e + CE - 1) // CE) * CE
    send = jnp.concatenate(
        [senders.astype(jnp.int32), jnp.full((e_pad - e,), n_pad_t, jnp.int32)]
    )
    recv = jnp.concatenate(
        [receivers.astype(jnp.int32), jnp.full((e_pad - e,), n_pad_out, jnp.int32)]
    )
    mask_i = jnp.concatenate(
        [mask.astype(jnp.int32), jnp.zeros((e_pad - e,), jnp.int32)]
    )
    n_blocks = n_pad_out // BN
    boundaries = jnp.arange(n_blocks + 1, dtype=jnp.int32) * BN
    block_ptr = jnp.searchsorted(recv[:e], boundaries, side="left").astype(jnp.int32)
    n_chunks = e_pad // CE
    plan = _window_plan_local(send, n_pad_t, n_chunks, ce=CE)
    # plan row 3: the occupancy bound for the kernel's chunk-loop clamp.
    # Defaults to e (a no-op: block_ptr <= e by construction); clamped
    # to e so a stale/overshooting caller value cannot read past the pad.
    occ = (
        jnp.full((1,), e, jnp.int32)
        if real_edges is None
        else jnp.minimum(real_edges.reshape(1).astype(jnp.int32), e)
    )
    plan = jnp.concatenate(
        [plan, jnp.broadcast_to(occ, (1, n_chunks))], axis=0
    )

    operands = [x, send[None, :], recv[None, :], mask_i[None, :]]
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),  # x (manual windowed DMA)
        pl.BlockSpec(memory_space=pl.ANY),  # send
        pl.BlockSpec(memory_space=pl.ANY),  # recv
        pl.BlockSpec(memory_space=pl.ANY),  # mask
    ]
    if k_br:
        operands += [w_cat, b_cat.astype(jnp.float32)]
        in_specs += [
            pl.BlockSpec((hp, k_br * hop), lambda i, p, q: (0, 0)),
            pl.BlockSpec((1, k_br * hop), lambda i, p, q: (0, 0)),
        ]
    has_rtab = rtab is not None
    if has_rtab:
        rt = jnp.concatenate(
            [rtab, jnp.zeros((n_pad_out - rtab.shape[0], rtab.shape[1]), rtab.dtype)],
            axis=0,
        )
        operands.append(rt)
        in_specs.append(
            pl.BlockSpec((BN, k_br * hop), lambda i, p, q: (i, 0))
        )
    has_eterm = eterm is not None
    if has_eterm:
        et = jnp.concatenate(
            [eterm, jnp.zeros((e_pad - e, eterm.shape[1]), eterm.dtype)], axis=0
        )
        operands.append(et)
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    has_scale = scale is not None
    if has_scale:
        sc = jnp.concatenate(
            [scale, jnp.zeros((e_pad - e, scale.shape[1]), scale.dtype)], axis=0
        )
        operands.append(sc)
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))

    kernel, n_id_streams = _make_fused_kernel(
        k_br, acts, has_rtab, has_eterm, has_scale, hp, hop,
        x_bf16=(xd == jnp.bfloat16),
    )
    scratch = [
        pltpu.VMEM((2, BW, hp), xd),
        pltpu.VMEM((2, 1, CE), jnp.int32),
        pltpu.VMEM((2, 1, CE), jnp.int32),
        pltpu.VMEM((2, 1, CE), jnp.int32),
    ]
    if has_eterm:
        scratch.append(pltpu.VMEM((2, CE, k_br * hop), et.dtype))
    if has_scale:
        scratch.append(pltpu.VMEM((2, CE, hop), sc.dtype))
    scratch += [
        pltpu.VMEM((CE, hp), jnp.float32),
        pltpu.SemaphoreType.DMA((2, n_id_streams)),
        pltpu.SemaphoreType.DMA((2,)),
    ]

    vma = _vma_of(*operands)
    operands = [_match_vma(o, vma) for o in operands]
    block_ptr = _match_vma(block_ptr, vma)
    plan = _match_vma(plan, vma)
    out_sds = jax.ShapeDtypeStruct((n_pad_out, hop), jnp.float32, vma=vma)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_blocks,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((BN, hop), lambda i, p, q: (i, 0)),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        out_shape=out_sds,
        grid_spec=grid_spec,
        interpret=interpret,
        name="fused_conv",
    )(block_ptr, plan, *operands)
    return out[:num_segments]


# ---------------------------------------------------------------------------
# custom_partitioning wrapper (edge-axis rule, like the segment kernels)
# ---------------------------------------------------------------------------

_FUSED_OPS: dict = {}


def _get_partitioned_fused(layout: Tuple[str, ...]):
    """One custom_partitioning op per operand layout. ``layout`` tags
    each tensor operand's leading-axis kind: "n" node-space (replicated
    under edge sharding), "e"/"t"/"s" edge-space (ids/mask, eterm,
    scale — all sharded on the edge mesh axis), "p" parameter
    (replicated). Statics (spec, num_segments, interpret) ride as
    trailing static args."""
    from jax.experimental.custom_partitioning import custom_partitioning
    from jax.sharding import NamedSharding, PartitionSpec as P

    if layout in _FUSED_OPS:
        return _FUSED_OPS[layout]

    n_ops = len(layout)

    def base(*args):
        operands = args[:n_ops]
        spec, num_segments, interpret = args[n_ops], args[n_ops + 1], args[n_ops + 2]
        return _fused_kernel_call(
            *_unflatten_operands(layout, operands), num_segments, spec, interpret
        )

    op = custom_partitioning(base, static_argnums=(n_ops, n_ops + 1, n_ops + 2))

    def infer(spec, num_segments, interpret, mesh, arg_shapes, result_shape):
        return NamedSharding(mesh, P())

    def partition(spec, num_segments, interpret, mesh, arg_shapes, result_shape):
        senders_spec = arg_shapes[1].sharding.spec
        edge_axis = senders_spec[0] if len(senders_spec) >= 1 else None

        def lower_fn(*operands):
            out = _fused_kernel_call(
                *_unflatten_operands(layout, operands), num_segments, spec,
                interpret,
            )
            if edge_axis is not None:
                out = jax.lax.psum(out, edge_axis)
            return out

        arg_sh = []
        for kind, shp in zip(layout, arg_shapes):
            nd = len(shp.shape)
            if kind in ("e", "t", "s"):
                arg_sh.append(
                    NamedSharding(mesh, P(*((edge_axis,) + (None,) * (nd - 1))))
                )
            else:
                arg_sh.append(NamedSharding(mesh, P(*((None,) * nd))))
        return mesh, lower_fn, NamedSharding(mesh, P()), tuple(arg_sh)

    # shardy rule (newer jax): edge-dim operands share factor "e",
    # node-space the output's "n"; distinct width factors per operand.
    # The occupancy scalar ("o", [1]) is replicated — its one dim gets
    # its own private factor.
    parts = []
    for idx, kind in enumerate(layout):
        if kind in ("e", "t", "s"):
            parts.append("e" if idx in (1, 2, 3) else f"e w{idx}")
        elif kind == "n":
            parts.append(f"n w{idx}")
        elif kind == "o":
            parts.append(f"o{idx}")
        else:
            parts.append(f"p{idx} w{idx}")
    op.def_partition(
        partition=partition,
        infer_sharding_from_operands=infer,
        sharding_rule=", ".join(parts) + " -> n h",
    )
    _FUSED_OPS[layout] = op
    return op


def _flatten_operands(x, senders, receivers, mask, w_cat, b_cat, rtab, eterm,
                      scale, real_edges):
    """(layout, operands) with absent optionals dropped — the layout is
    the partitioned-op cache key and the unflatten schema. The occupancy
    scalar travels last as kind "o" ([1] int32, replicated: a shard's
    local real-edge positions are <= their global positions, so the
    global bound never clips a shard-local real edge)."""
    layout = ["n", "e", "e", "e"]
    operands = [x, senders, receivers, mask]
    for a, kind in ((w_cat, "p"), (b_cat, "p"), (rtab, "n"), (eterm, "t"),
                    (scale, "s"), (real_edges, "o")):
        if a is not None:
            layout.append(kind)
            operands.append(a)
    return tuple(layout), operands


def _unflatten_operands(layout, operands):
    """Inverse of :func:`_flatten_operands` for the op body: positions
    4+ are (w, b, rtab, eterm, scale, real_edges) in order, present or
    None."""
    it = list(operands[4:])
    x, senders, receivers, mask = operands[:4]
    kinds = list(layout[4:])
    # w/b always travel together (both "p", w first)
    w_cat = it.pop(0) if "p" in kinds else None
    b_cat = it.pop(0) if "p" in kinds else None
    rtab = it.pop(0) if "n" in kinds else None
    eterm = it.pop(0) if "t" in kinds else None
    scale = it.pop(0) if "s" in kinds else None
    real_edges = it.pop(0) if "o" in kinds else None
    return (x, senders, receivers, mask, w_cat, b_cat, rtab, eterm, scale,
            real_edges)


# ---------------------------------------------------------------------------
# forward impl + hand-written VJP
# ---------------------------------------------------------------------------


def _branch_pres(v, branches, recv_gather):
    """Per-branch pre-activations of the edge network, compute dtype."""
    pres = []
    for (W, b, rtab, eterm) in branches:
        pre = v @ W.astype(v.dtype)
        if b is not None:
            pre = pre + b.astype(pre.dtype)
        if rtab is not None:
            pre = pre + recv_gather(rtab.astype(pre.dtype))
        if eterm is not None:
            pre = pre + eterm.astype(pre.dtype)
        pres.append(pre)
    return pres


def _fused_ref(spec, num_segments, x, senders, receivers, mask, branches,
               scale):
    """The bit-compatible XLA fallback: the identical composition in
    plain jnp — also the contract the kernel is tested against."""
    k_br, acts = spec
    v = x[senders]
    if k_br:
        pres = _branch_pres(v, branches, lambda t: t[receivers])
        msg = None
        for kk in range(k_br):
            a = _ACTS[acts[kk]][0](pres[kk])
            msg = a if msg is None else msg * a
    else:
        msg = v
    if scale is not None:
        msg = msg * scale.astype(msg.dtype)
    msg = jnp.where(mask[:, None], msg, 0).astype(jnp.float32)
    return jax.ops.segment_sum(
        msg, receivers, num_segments, indices_are_sorted=True
    )


def _cat_branches(branches):
    """Stack the K branches' params on the output axis for the kernel:
    W_cat [Hin, K*Hout], b_cat [1, K*Hout] (zeros where absent),
    rtab_cat [N, K*Hout] / eterm_cat [E, K*Hout] (zeros for branches
    without one; None when NO branch has one)."""
    if not branches:
        return None, None, None, None
    ws = [W for (W, _, _, _) in branches]
    hout = ws[0].shape[1]
    w_cat = jnp.concatenate(ws, axis=1)
    b_cat = jnp.concatenate(
        [
            (b if b is not None else jnp.zeros((hout,), w_cat.dtype)).reshape(1, -1)
            for (_, b, _, _) in branches
        ],
        axis=1,
    )
    rtab_cat = eterm_cat = None
    if any(r is not None for (_, _, r, _) in branches):
        n = next(r for (_, _, r, _) in branches if r is not None).shape[0]
        rtab_cat = jnp.concatenate(
            [
                r if r is not None else jnp.zeros((n, hout), w_cat.dtype)
                for (_, _, r, _) in branches
            ],
            axis=1,
        )
    if any(e is not None for (_, _, _, e) in branches):
        ne = next(e for (_, _, _, e) in branches if e is not None).shape[0]
        eterm_cat = jnp.concatenate(
            [
                e if e is not None else jnp.zeros((ne, hout), w_cat.dtype)
                for (_, _, _, e) in branches
            ],
            axis=1,
        )
    return w_cat, b_cat, rtab_cat, eterm_cat


def _fused_impl(spec, num_segments, use_kernel, interpret, x, senders,
                receivers, mask, win, real_edges, branches, scale):
    if not use_kernel or senders.shape[0] == 0:
        # the reference path ignores the occupancy bound: skipped chunks
        # only ever held masked edges, whose messages the jnp.where
        # zeroes — the two paths are definitionally identical
        return _fused_ref(
            spec, num_segments, x, senders, receivers, mask, branches, scale
        )
    w_cat, b_cat, rtab_cat, eterm_cat = _cat_branches(branches)
    layout, operands = _flatten_operands(
        x, senders.astype(jnp.int32), receivers.astype(jnp.int32),
        jax.lax.stop_gradient(mask), w_cat, b_cat, rtab_cat, eterm_cat, scale,
        None if real_edges is None
        else jax.lax.stop_gradient(real_edges).reshape(1).astype(jnp.int32),
    )
    op = _get_partitioned_fused(layout)
    return op(*operands, spec, num_segments, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _fused_conv(spec, num_segments, use_kernel, interpret, x, senders,
                receivers, mask, win, real_edges, branches, scale):
    return _fused_impl(spec, num_segments, use_kernel, interpret, x, senders,
                       receivers, mask, win, real_edges, branches, scale)


def _fused_conv_fwd(spec, num_segments, use_kernel, interpret, x, senders,
                    receivers, mask, win, real_edges, branches, scale):
    out = _fused_impl(spec, num_segments, use_kernel, interpret, x, senders,
                      receivers, mask, win, real_edges, branches, scale)
    return out, (x, senders, receivers, mask, win, real_edges, branches, scale)


def _fused_conv_bwd(spec, num_segments, use_kernel, interpret, res, g):
    """Hand-written backward from the closed-form chain, built on the
    fast machinery: sorted CSR-broadcast for the node->edge cotangent
    gathers, local-window segment sum for the sender scatter (no edge
    permute), sorted CSR sum for rtab grads, MXU contractions for W/b.
    Recomputes v (one gather) and the branch pre-activations instead of
    saving [E, *] residuals — the same recompute-over-HBM trade as the
    PNA presum backward."""
    k_br, acts = spec
    x, senders, receivers, mask, win, real_edges, branches, scale = res
    dt = x.dtype
    n = x.shape[0]
    f0 = jax.dtypes.float0

    def egather(t):
        if use_kernel and t.ndim == 2:
            return gather_rows_sorted_fast(t, receivers)
        return t[receivers]

    def sgather(t):
        if use_kernel and win is not None and t.ndim == 2:
            return gather_rows_local_fast(t, senders)
        return t[senders]

    def sender_scatter(grad_v):
        if use_kernel and win is not None:
            return segment_sum_local_fast(grad_v, senders, win, n)
        return jax.ops.segment_sum(grad_v.astype(jnp.float32), senders, n)

    ge = egather(g.astype(dt))  # [E, Hout]
    mfac = mask[:, None].astype(dt)
    g_msg = ge * mfac
    g_scale = None

    if k_br:
        v = sgather(x)
        pres = _branch_pres(v, branches, egather)
        a = [_ACTS[acts[kk]][0](pres[kk]) for kk in range(k_br)]
        if scale is not None:
            prod_all = a[0]
            for kk in range(1, k_br):
                prod_all = prod_all * a[kk]
            g_scale = (g_msg * prod_all).astype(scale.dtype)
            g_msg = g_msg * scale.astype(g_msg.dtype)
        g_branches = []
        grad_v = None
        for kk in range(k_br):
            others = None
            for jj in range(k_br):
                if jj == kk:
                    continue
                others = a[jj] if others is None else others * a[jj]
            g_pre = g_msg if others is None else g_msg * others
            g_pre = g_pre * _ACTS[acts[kk]][1](pres[kk], a[kk])
            W, b, rtab, eterm = branches[kk]
            term = g_pre @ W.astype(g_pre.dtype).T
            grad_v = term if grad_v is None else grad_v + term
            gW = (
                v.astype(jnp.float32).T @ g_pre.astype(jnp.float32)
            ).astype(W.dtype)
            gb = (
                g_pre.astype(jnp.float32).sum(axis=0).astype(b.dtype)
                if b is not None
                else None
            )
            grtab = (
                segment_sum_fast(
                    g_pre, receivers, n, indices_are_sorted=True
                ).astype(rtab.dtype)
                if rtab is not None
                else None
            )
            geterm = g_pre.astype(eterm.dtype) if eterm is not None else None
            g_branches.append((gW, gb, grtab, geterm))
        g_branches = tuple(g_branches)
    else:
        if scale is not None:
            v = sgather(x)
            g_scale = (g_msg * v).astype(scale.dtype)
            grad_v = g_msg * scale.astype(g_msg.dtype)
        else:
            grad_v = g_msg
        g_branches = branches  # () — empty structure

    grad_x = sender_scatter(grad_v).astype(dt)
    return (
        grad_x,
        jnp.zeros(senders.shape, dtype=f0),
        jnp.zeros(receivers.shape, dtype=f0),
        jnp.zeros(mask.shape, dtype=f0),
        None if win is None else jnp.zeros(win.shape, dtype=f0),
        None if real_edges is None else jnp.zeros(real_edges.shape, dtype=f0),
        g_branches,
        g_scale,
    )


_fused_conv.defvjp(_fused_conv_fwd, _fused_conv_bwd)


# ---------------------------------------------------------------------------
# public dispatcher
# ---------------------------------------------------------------------------


def fused_conv(
    x: jnp.ndarray,
    senders: jnp.ndarray,
    receivers: jnp.ndarray,
    edge_mask: jnp.ndarray,
    num_segments: int,
    branches: Sequence[Tuple] = (),
    acts: Sequence[str] = (),
    scale: Optional[jnp.ndarray] = None,
    win: Optional[jnp.ndarray] = None,
    real_edges: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Fused gather -> edge network -> masked scatter (module docstring).

    ``branches``: up to two ``(W [Hin, Hout], b [Hout]|None,
    rtab [N, Hout]|None, eterm [E, Hout]|None)`` tuples whose activated
    outputs multiply elementwise (one branch = a plain edge MLP, two =
    the CGCNN gate); empty = identity messages (Hout = Hin).
    ``acts``: one activation name per branch (see ``_ACTS``).
    ``scale``: optional [E, Hout] per-edge factor (SchNet filter).
    ``win``: loader-emitted sender block windows ([2, n_blocks] int32)
    — routes the backward's sender scatter through the local-window
    kernel; without it the backward falls back to XLA's scatter-add.
    ``real_edges``: optional scalar int32 occupancy bound
    (GraphBatch.edge_occupancy) — every edge slot at position >=
    real_edges must be MASKED; the kernel then skips fully-padded tail
    chunks entirely (zero DMAs, zero MXU work) with bit-identical
    output. None processes the full pad.

    CONTRACT: ``receivers`` sorted ascending (the loader contract all
    convs rely on — same as ``segment_sum_family``). Returns float32
    [num_segments, Hout]; callers cast. The mask is non-differentiable.
    """
    branches = tuple(tuple(br) for br in branches)
    acts = tuple(acts)
    if len(acts) != len(branches):
        raise ValueError(
            f"fused_conv: {len(branches)} branches but {len(acts)} activations"
        )
    if len(branches) > 2:
        raise ValueError("fused_conv supports at most 2 edge-network branches")
    for name in acts:
        if name not in _ACTS:
            raise ValueError(f"unknown fused_conv activation {name!r}")
    hout = branches[0][0].shape[1] if branches else x.shape[1]
    spec = (len(branches), acts)
    use_kernel = fused_conv_active() and senders.shape[0] > 0
    interpret = _interpret_mode()
    mask = jax.lax.stop_gradient(edge_mask)

    if not use_kernel:
        return _fused_conv(spec, num_segments, False, False, x, senders,
                           receivers, mask, win, real_edges, branches, scale)

    # lane-pad every width to the 128-lane kernel tile; padding lives
    # OUTSIDE the custom-vjp op, so AD slices the cotangents back
    hp = _pad128(x.shape[1])
    hop = _pad128(hout)
    xk = _pad_cols(x, hp)
    brk = tuple(
        (
            _pad_cols(
                jnp.concatenate(
                    [W, jnp.zeros((hp - W.shape[0], W.shape[1]), W.dtype)], axis=0
                )
                if W.shape[0] != hp
                else W,
                hop,
            ),
            _pad_cols(b, hop),
            _pad_cols(r, hop),
            _pad_cols(e_, hop),
        )
        for (W, b, r, e_) in branches
    )
    sck = _pad_cols(scale, hop)
    out = _fused_conv(spec, num_segments, True, interpret, xk, senders,
                      receivers, mask, win, real_edges, brk, sck)
    return out[:, :hout]


# ---------------------------------------------------------------------------
# cross-layer VMEM residency: the fused conv STACK
# ---------------------------------------------------------------------------
#
# A width-preserving stack of L fused conv layers executed as ONE kernel
# with the node features RESIDENT in VMEM between layers:
#
#     h_0     = x
#     out_l   = segment_sum(mask * act_e(h_l[send] @ W_l + b_l))
#     h_{l+1} = act_i(out_l)
#
# returning out_{L-1} (no inter-layer activation on the last layer).
# The single-layer kernel reads the gather table from HBM once per
# sender window per chunk and writes the layer output back to HBM — for
# an L-layer stack that is L full round trips of the node features.
# Here the features live in a ping-pong VMEM scratch pair: layer l
# gathers its windows from slot l%2 with plain VMEM dynamic slices
# (zero HBM gather traffic after the one-time load) and writes its
# activated out blocks into slot (l+1)%2. Per-layer weights arrive as a
# blocked [L, hp, hp] operand whose index map advances with the layer
# grid dim, so Pallas's input pipeline double-buffers layer l+1's
# weight DMA behind layer l's compute. The TPU grid (L, n_blocks)
# executes sequentially in lexicographic order — every block of layer l
# completes before layer l+1 starts, which is what makes the ping-pong
# safe.
#
# Restrictions (enforced by the dispatcher, which falls back to the
# per-layer loop): square weights (width-preserving), f32 activations,
# num_segments == x.shape[0] (outputs feed back as inputs), one edge
# MLP per layer (no rtab/eterm/scale — those are per-layer functions of
# h_l and would have to be recomputed in-kernel), and the VMEM
# footprint estimate under HYDRAGNN_RESIDENCY_VMEM_MB. Intermediate
# layers' out-block flushes do write garbage to the output's HBM
# buffer, but the final layer's flush overwrites every block (last
# writer wins on the sequential grid) — the waste is L-1 node-space
# writes, far smaller than the L-1 edge-space gather round trips
# deleted.


def _make_stack_kernel(act_e, act_i, hp, n_layers):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(ptr_ref, plan_ref, *refs):
        (x_hbm, send_hbm, recv_hbm, mask_hbm, w_ref, b_ref, out_ref,
         xbuf, send_vmem, recv_vmem, mask_vmem, gacc_ref,
         sem_ids, sem_x) = refs

        l = pl.program_id(0)
        i = pl.program_id(1)
        # same occupancy clamp as the single-layer kernel: the edge set
        # is identical for every layer, so skipped tail chunks are
        # skipped L times over
        occ = plan_ref[3, 0]
        lo = jnp.minimum(ptr_ref[i], occ)
        hi = jnp.minimum(ptr_ref[i + 1], occ)
        n_clamp = plan_ref[2, 0]
        out_ref[:] = jnp.zeros_like(out_ref)
        k0 = lo // CE
        k1 = (hi + CE - 1) // CE
        sslot = l % 2  # layer l reads slot l%2, writes slot (l+1)%2

        # one-time residency load at grid step (0, 0): x -> slot 0, and
        # zero slot 1 so rows outside the written blocks ([n_pad_out,
        # n_res), never stored to) read as exact zeros in every layer
        @pl.when((l == 0) & (i == 0))
        def _load_resident():
            cp = pltpu.make_async_copy(x_hbm, xbuf.at[0], sem_x.at[0])
            cp.start()
            cp.wait()
            xbuf[1] = jnp.zeros(xbuf.shape[1:], xbuf.dtype)

        def id_dmas(slot, k):
            start = pl.multiple_of(k * CE, CE)
            return [
                pltpu.make_async_copy(
                    send_hbm.at[:, pl.ds(start, CE)], send_vmem.at[slot],
                    sem_ids.at[slot, 0],
                ),
                pltpu.make_async_copy(
                    recv_hbm.at[:, pl.ds(start, CE)], recv_vmem.at[slot],
                    sem_ids.at[slot, 1],
                ),
                pltpu.make_async_copy(
                    mask_hbm.at[:, pl.ds(start, CE)], mask_vmem.at[slot],
                    sem_ids.at[slot, 2],
                ),
            ]

        @pl.when(k0 < k1)
        def _warmup():
            for cp in id_dmas(k0 % 2, k0):
                cp.start()

        def chunk_body(k, _):
            slot = k % 2

            @pl.when(k + 1 < k1)
            def _prefetch_ids():
                for cp in id_dmas((k + 1) % 2, k + 1):
                    cp.start()

            for cp in id_dmas(slot, k):
                cp.wait()
            send = send_vmem[slot][0, :]  # [CE]
            astart = plan_ref[0, k]
            wcnt = plan_ref[1, k]
            gacc_ref[:] = jnp.zeros_like(gacc_ref)

            # windowed sender gather — same one-hot math as the single
            # kernel, but the window is a VMEM slice of the resident
            # buffer instead of an HBM DMA (the traffic this mode
            # deletes). The source slot alternates per layer; the two
            # pl.when branches keep the slot index static for the load.
            def window_body(w, _):
                wstart = astart + w * BW
                cstart = pl.multiple_of(
                    jnp.minimum(wstart, n_clamp), ALIGN
                )
                local = send - cstart
                in_range = (send >= wstart) & (send < wstart + BW)
                local = jnp.where(in_range, local, -1)
                onehot = (
                    local[:, None]
                    == jax.lax.broadcasted_iota(jnp.int32, (CE, BW), 1)
                ).astype(jnp.float32)

                def accumulate(win):
                    gacc_ref[:] += jax.lax.dot_general(
                        onehot, win, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST,
                    )

                @pl.when(sslot == 0)
                def _from_slot0():
                    accumulate(xbuf[0, pl.ds(cstart, BW), :])

                @pl.when(sslot == 1)
                def _from_slot1():
                    accumulate(xbuf[1, pl.ds(cstart, BW), :])

                return 0

            jax.lax.fori_loop(0, wcnt, window_body, 0)

            v = gacc_ref[:]  # [CE, hp] f32, exact copies of h_l rows
            rows = jax.lax.broadcasted_iota(jnp.int32, (BN, CE), 0) + i * BN
            onehot_r = recv_vmem[slot] == rows  # [BN, CE]
            mf = mask_vmem[slot][0, :].astype(jnp.float32)[:, None]

            # this layer's edge MLP (w_ref block = [1, hp, hp] at layer l)
            pre = jax.lax.dot_general(
                v, w_ref[0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
            pre = pre + b_ref[0]  # [1, hp] broadcasts
            msg = _ACTS[act_e][0](pre) * mf

            # masked one-hot scatter, 3-term bf16 split (exact f32)
            onehot_t = onehot_r.astype(jnp.bfloat16)
            hi_t = msg.astype(jnp.bfloat16)
            r1 = msg - hi_t.astype(jnp.float32)
            mid_t = r1.astype(jnp.bfloat16)
            lo_t = (r1 - mid_t.astype(jnp.float32)).astype(jnp.bfloat16)
            for term in (hi_t, mid_t, lo_t):
                out_ref[:] += jax.lax.dot_general(
                    onehot_t, term, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            return 0

        jax.lax.fori_loop(k0, k1, chunk_body, 0)

        # hand the activated block to the next layer: store into the
        # TARGET slot (static index under pl.when, dynamic row offset).
        # Rows [num_segments, n_pad_out) get act_i(0) here where the
        # per-layer loop re-pads zeros — but no sender ever points at
        # them (senders < num_segments), so they are only ever read with
        # zero one-hot coefficients: exact +0 either way.
        @pl.when(l + 1 < n_layers)
        def _store_next():
            y = _ACTS[act_i][0](out_ref[:])
            row0 = pl.multiple_of(i * BN, BN)

            @pl.when(sslot == 0)
            def _to_slot1():
                xbuf[1, pl.ds(row0, BN), :] = y

            @pl.when(sslot == 1)
            def _to_slot0():
                xbuf[0, pl.ds(row0, BN), :] = y

    return kernel


def _stack_kernel_call(x, senders, receivers, mask, w_stack, b_stack,
                       real_edges, num_segments, spec, interpret):
    """Resident-stack kernel invocation. ``x`` pre-padded to 128 lanes,
    ``w_stack`` [L, hp, hp] f32, ``b_stack`` [L, 1, hp] f32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    act_e, act_i, n_layers = spec
    e = senders.shape[0]
    n, hp = x.shape

    n_pad_out = ((num_segments + BN - 1) // BN) * BN
    # the resident buffer doubles as gather table AND inter-layer output
    # target: rows must cover both the window headroom and every written
    # out block (n_pad_out can exceed the single kernel's gather pad)
    n_res = max(((n + ALIGN - 1) // ALIGN) * ALIGN, BW, n_pad_out)
    if n_res != n:
        x = jnp.concatenate(
            [x, jnp.zeros((n_res - n, hp), x.dtype)], axis=0
        )
    e_pad = ((e + CE - 1) // CE) * CE
    send = jnp.concatenate(
        [senders.astype(jnp.int32), jnp.full((e_pad - e,), n_res, jnp.int32)]
    )
    recv = jnp.concatenate(
        [receivers.astype(jnp.int32), jnp.full((e_pad - e,), n_pad_out, jnp.int32)]
    )
    mask_i = jnp.concatenate(
        [mask.astype(jnp.int32), jnp.zeros((e_pad - e,), jnp.int32)]
    )
    n_blocks = n_pad_out // BN
    boundaries = jnp.arange(n_blocks + 1, dtype=jnp.int32) * BN
    block_ptr = jnp.searchsorted(recv[:e], boundaries, side="left").astype(jnp.int32)
    n_chunks = e_pad // CE
    plan = _window_plan_local(send, n_res, n_chunks, ce=CE)
    occ = (
        jnp.full((1,), e, jnp.int32)
        if real_edges is None
        else jnp.minimum(real_edges.reshape(1).astype(jnp.int32), e)
    )
    plan = jnp.concatenate([plan, jnp.broadcast_to(occ, (1, n_chunks))], axis=0)

    operands = [
        x, send[None, :], recv[None, :], mask_i[None, :],
        w_stack.astype(jnp.float32), b_stack.astype(jnp.float32),
    ]
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),  # x (one-time residency DMA)
        pl.BlockSpec(memory_space=pl.ANY),  # send
        pl.BlockSpec(memory_space=pl.ANY),  # recv
        pl.BlockSpec(memory_space=pl.ANY),  # mask
        # per-layer params: block index follows the layer grid dim, so
        # the pipeline prefetches layer l+1's weights during layer l
        pl.BlockSpec((1, hp, hp), lambda l, i, p, q: (l, 0, 0)),
        pl.BlockSpec((1, 1, hp), lambda l, i, p, q: (l, 0, 0)),
    ]
    kernel = _make_stack_kernel(act_e, act_i, hp, n_layers)
    scratch = [
        pltpu.VMEM((2, n_res, hp), jnp.float32),  # resident ping-pong pair
        pltpu.VMEM((2, 1, CE), jnp.int32),
        pltpu.VMEM((2, 1, CE), jnp.int32),
        pltpu.VMEM((2, 1, CE), jnp.int32),
        pltpu.VMEM((CE, hp), jnp.float32),
        pltpu.SemaphoreType.DMA((2, 3)),
        pltpu.SemaphoreType.DMA((1,)),
    ]
    vma = _vma_of(*operands)
    operands = [_match_vma(o, vma) for o in operands]
    block_ptr = _match_vma(block_ptr, vma)
    plan = _match_vma(plan, vma)
    out_sds = jax.ShapeDtypeStruct((n_pad_out, hp), jnp.float32, vma=vma)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_layers, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((BN, hp), lambda l, i, p, q: (i, 0)),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        out_shape=out_sds,
        grid_spec=grid_spec,
        interpret=interpret,
        name="fused_conv_stack",
    )(block_ptr, plan, *operands)
    return out[:num_segments]


def _stack_ref_loop(spec, num_segments, use_kernel, interpret, x, senders,
                    receivers, mask, win, real_edges, w_stack, b_stack):
    """Per-layer composition of ``_fused_conv`` — three jobs at once:
    the numerical contract the resident kernel is tested against
    (bit-exact in f32), the VMEM-budget fallback path (still per-layer
    fused kernels when available), and the backward's recompute target.
    Intermediate activations are cast back to the input dtype so bf16
    stacks stay bf16 layer to layer."""
    act_e, act_i, n_layers = spec
    h = x
    out = None
    for l in range(n_layers):
        branches = ((w_stack[l], b_stack[l].reshape(-1), None, None),)
        out = _fused_conv((1, (act_e,)), num_segments, use_kernel, interpret,
                          h, senders, receivers, mask, win, real_edges,
                          branches, None)
        if l + 1 < n_layers:
            h = _ACTS[act_i][0](out).astype(x.dtype)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _fused_stack(spec, num_segments, use_kernel, interpret, x, senders,
                 receivers, mask, win, real_edges, w_stack, b_stack):
    if use_kernel:
        return _stack_kernel_call(x, senders, receivers, mask, w_stack,
                                  b_stack, real_edges, num_segments, spec,
                                  interpret)
    return _stack_ref_loop(spec, num_segments, False, interpret, x, senders,
                           receivers, mask, win, real_edges, w_stack, b_stack)


def _fused_stack_fwd(spec, num_segments, use_kernel, interpret, x, senders,
                     receivers, mask, win, real_edges, w_stack, b_stack):
    out = _fused_stack(spec, num_segments, use_kernel, interpret, x, senders,
                       receivers, mask, win, real_edges, w_stack, b_stack)
    return out, (x, senders, receivers, mask, win, real_edges, w_stack, b_stack)


def _fused_stack_bwd(spec, num_segments, use_kernel, interpret, res, g):
    """Recompute-based backward: differentiate the per-layer composition
    (which runs the fast single-layer VJPs — local-window scatters, MXU
    contractions). The resident forward is bit-identical to that
    composition, so gradients are consistent by construction."""
    x, senders, receivers, mask, win, real_edges, w_stack, b_stack = res
    f0 = jax.dtypes.float0

    def f(x_, w_, b_):
        return _stack_ref_loop(spec, num_segments, use_kernel, interpret, x_,
                               senders, receivers, mask, win, real_edges,
                               w_, b_)

    _, vjp = jax.vjp(f, x, w_stack, b_stack)
    gx, gw, gb = vjp(g)
    return (
        gx,
        jnp.zeros(senders.shape, dtype=f0),
        jnp.zeros(receivers.shape, dtype=f0),
        jnp.zeros(mask.shape, dtype=f0),
        None if win is None else jnp.zeros(win.shape, dtype=f0),
        None if real_edges is None else jnp.zeros(real_edges.shape, dtype=f0),
        gw,
        gb,
    )


_fused_stack.defvjp(_fused_stack_fwd, _fused_stack_bwd)


def residency_vmem_budget_bytes() -> int:
    """VMEM the resident stack kernel may claim, from
    ``HYDRAGNN_RESIDENCY_VMEM_MB`` (default 12 — a TPU core has ~16MB
    and the compiler needs headroom for the pipeline's own buffers)."""
    return int(knobs.get_float("HYDRAGNN_RESIDENCY_VMEM_MB", 12.0) * (1 << 20))


def residency_vmem_bytes(num_nodes: int, width: int) -> int:
    """Estimated VMEM footprint of the resident stack kernel for a
    given gather-table size — the decision rule documented in
    docs/PERF.md r08. Dominated by the ping-pong feature pair.

    graftcheck contract CC006 (docs/LINT.md) re-derives this estimate
    from the entry point's shapes and fails CI when it exceeds the
    ``HYDRAGNN_RESIDENCY_VMEM_MB`` budget — or when the budget itself
    over-promises physical VMEM — so keep this arithmetic and
    ``hydragnn_tpu/lint/ir.py::check_vmem_budget`` telling one story."""
    hp = _pad128(width)
    n_pad_out = ((num_nodes + BN - 1) // BN) * BN
    n_res = max(((num_nodes + ALIGN - 1) // ALIGN) * ALIGN, BW, n_pad_out)
    return (
        2 * n_res * hp * 4        # resident ping-pong feature pair
        + 2 * (hp * hp + hp) * 4  # double-buffered layer params
        + 3 * 2 * CE * 4          # id chunk buffers
        + CE * hp * 4             # gather accumulator
        + 2 * BN * hp * 4         # out block double buffer
    )


def fused_conv_stack(
    x: jnp.ndarray,
    senders: jnp.ndarray,
    receivers: jnp.ndarray,
    edge_mask: jnp.ndarray,
    num_segments: int,
    weights: jnp.ndarray,
    biases: Optional[jnp.ndarray] = None,
    edge_act: str = "none",
    inter_act: str = "relu",
    win: Optional[jnp.ndarray] = None,
    real_edges: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """L fused conv layers with cross-layer VMEM residency (see the
    section comment above). Computes, for l in [0, L):

        h_0 = x;  out_l = segment_sum(mask * edge_act(h_l[send] @ W_l + b_l))
        h_{l+1} = inter_act(out_l)

    and returns out_{L-1} as float32 [num_segments, H] (no inter_act on
    the last layer; callers apply their own epilogue and cast).

    ``weights``: [L, H, H] (or a sequence of [H, H]) — width-preserving
    by construction. ``biases``: [L, H] or None. ``num_segments`` must
    equal ``x.shape[0]`` (outputs feed back as inputs). ``win`` /
    ``real_edges``: as in :func:`fused_conv`; the occupancy bound
    applies to every layer. Falls back to a per-layer loop of
    :func:`fused_conv` (same numerics) when the Pallas kernel is off,
    activations are not f32, or the estimated VMEM footprint exceeds
    :func:`residency_vmem_budget_bytes`."""
    if not isinstance(weights, jnp.ndarray):
        weights = jnp.stack([jnp.asarray(w) for w in weights], axis=0)
    if weights.ndim != 3 or weights.shape[1] != weights.shape[2]:
        raise ValueError(
            f"fused_conv_stack needs square [L, H, H] weights, got {weights.shape}"
        )
    n, h = x.shape
    n_layers = int(weights.shape[0])
    if weights.shape[1] != h:
        raise ValueError(
            f"weights width {weights.shape[1]} != feature width {h}"
        )
    if num_segments != n:
        raise ValueError(
            "fused_conv_stack feeds layer outputs back as inputs; "
            f"num_segments ({num_segments}) must equal x.shape[0] ({n})"
        )
    for name in (edge_act, inter_act):
        if name not in _ACTS:
            raise ValueError(f"unknown fused_conv_stack activation {name!r}")
    if biases is not None and not isinstance(biases, jnp.ndarray):
        biases = jnp.stack([jnp.asarray(b) for b in biases], axis=0)

    spec = (edge_act, inter_act, n_layers)
    mask = jax.lax.stop_gradient(edge_mask)
    use_kernel = fused_conv_active() and senders.shape[0] > 0
    interpret = _interpret_mode()

    hp = _pad128(h)
    xk = _pad_cols(x, hp)
    wk = weights
    if hp != h:
        wk = jnp.concatenate(
            [wk, jnp.zeros((n_layers, hp - h, h), wk.dtype)], axis=1
        )
        wk = _pad_cols(wk, hp)
    bk = (
        jnp.zeros((n_layers, 1, hp), wk.dtype)
        if biases is None
        else _pad_cols(biases, hp).reshape(n_layers, 1, hp)
    )
    re_ = (
        None
        if real_edges is None
        else jax.lax.stop_gradient(real_edges).reshape(1).astype(jnp.int32)
    )

    resident = (
        use_kernel
        and xk.dtype == jnp.float32
        and wk.dtype == jnp.float32
        and residency_vmem_bytes(n, h) <= residency_vmem_budget_bytes()
    )
    if resident:
        out = _fused_stack(spec, num_segments, True, interpret, xk, senders,
                           receivers, mask, win, re_, wk, bk)
    else:
        # per-layer dispatch: still the fused single-layer kernel when
        # available (each call carries its own VJP), plain XLA otherwise
        out = _stack_ref_loop(spec, num_segments, use_kernel, interpret, xk,
                              senders, receivers, mask, win, re_, wk, bk)
    return out[:, :h]
