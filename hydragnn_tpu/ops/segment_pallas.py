"""Fused one-pass "sum-family" segment aggregation (sum, sum-of-squares,
count) — the PNA hot path.

PNA needs mean/std per receiver (reference: hydragnn/models/PNAStack.py:27
via PyG aggregators), which decomposes into three sum-reductions over the
edge messages. Done naively that is 3+ scatter passes, each re-reading
the [E, H] message array from HBM. Two fused implementations:

  - ``segment_sum_family_xla``: one concatenated segment_sum — XLA
    reads the messages once and scatters [E, 2H+1] rows (measured
    1.1-2.0 ms at E=120k, H=128 on v5e — ~7x off the HBM roofline).
  - ``segment_sum_family_pallas``: a Pallas TPU kernel — grid over
    output node blocks with scalar-prefetched CSR row pointers,
    DOUBLE-BUFFERED HBM->VMEM DMA of edge chunks, and one-hot MXU
    matmul accumulation in VMEM (precision=HIGHEST: the MXU's default
    path rounds f32 inputs to bf16). One read of the messages, no
    scatter: measured 0.36 ms at the same shape — 5.5x over XLA
    (docs/PERF.md). The TPU DEFAULT via ``HYDRAGNN_PALLAS=auto``
    when receivers are sorted (batch_graphs canonicalizes
    receiver-major order) and H % 128 == 0.

SPMD composition: the kernel calls are wrapped in
``jax.experimental.custom_partitioning`` with an edge-axis rule — when
GSPMD shards the operands on their leading (edge) axis (the giant-graph
path, ``parallel/edge_sharded.py:place_giant_batch``), each device runs
the CSR kernel on its LOCAL edge slice (a contiguous receiver-sorted
range, so the CSR contract holds per shard) and one ``psum`` over the
sharded axis combines the per-node partials. No escape hatch needed:
the fast kernel and the giant-graph sharding path compose. Inside
``shard_map`` (the DP train step) the operands are already local and
the wrapper lowers to the plain kernel. The one context that cannot
partition the op is ``vmap`` (custom_partitioning has no batching
rule) — ``make_dp_edge_train_step`` traces its model vmap under
:func:`xla_segment_ops`, which forces the XLA path programmatically.

Training goes through a hand-written gather VJP (``_family``): the
kernel has no native autodiff, and the closed-form backward
(m*g_sum[ids] + 2*m^2*data*g_sumsq[ids]) is cheaper than XLA's
packed-scatter VJP anyway. The mask is non-differentiable by contract
(stop_gradient applied on entry): it is an edge-validity weighting,
not a learnable quantity.

The Pallas kernel requires ``segment_ids`` sorted ascending (it builds
CSR block pointers by binary search); the XLA pass accepts any order.
Both need a static ``num_segments``.

``HYDRAGNN_PALLAS`` knob contract:
  - ``auto`` (default): Pallas on TPU for sorted, 2-D, 128-lane data;
  - ``1``: force the kernel when the backend is TPU (sorting on the
    fly if needed); falls back to XLA elsewhere rather than crashing
    at Mosaic lowering on CPU/GPU;
  - ``interpret``: force the kernel in interpret mode on ANY backend
    (CPU-mesh tests of the sharded kernel path);
  - ``0``: force XLA.

The FULLY FUSED conv-layer kernel (gather -> edge MLP -> scatter in
one Pallas call, r07) builds on this file's machinery — window plans,
vma matching, partitioning compat, the fast gather/sum dispatchers —
and lives in :mod:`hydragnn_tpu.ops.fused_conv`; it shares the knob
contract above.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.custom_partitioning import custom_partitioning
from jax.sharding import NamedSharding, PartitionSpec as P

from hydragnn_tpu.utils import knobs

# Grid tile sizes, env-overridable for on-chip tuning (tools/tune_tiles.py):
# larger tiles amortize per-grid-step overhead (the r04 flagship trace
# shows ~1 ms kernel calls moving only ~0.2 GB — overhead-bound), at the
# cost of VMEM and wasted work on boundary blocks.


_TUNE_TILES_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "TUNE_TILES.json",
)


def _tile_defaults(path: str = _TUNE_TILES_PATH) -> dict:
    """Block/chunk defaults from the committed sweep table
    (``TUNE_TILES.json`` at the repo root, written by
    ``tools/tune_tiles.py --save``): ``{shape_tag: {device_kind:
    {"BN", "CE", "BCAST_CE"}}}``. Selection keys come from env —
    ``HYDRAGNN_TILE_SHAPE`` then ``HYDRAGNN_DEVICE_KIND``, each falling
    back to the table's ``"default"`` row — NOT from ``jax.devices()``:
    importing this module must never trigger backend init. The explicit
    ``HYDRAGNN_BN`` / ``HYDRAGNN_CE`` / ``HYDRAGNN_BCAST_CE`` env knobs
    always win over the table. A tree without the table (an installed
    package) runs on the baked defaults and says so in ``"source"``; a
    table that is there but cannot be read is an error, not a silent
    change of tiles."""
    import json

    out = {"BN": 128, "CE": 512, "BCAST_CE": 1024, "source": "baked"}
    try:
        with open(path) as f:
            table = json.load(f)
    except FileNotFoundError:
        return out
    except ValueError as exc:
        raise ValueError(f"{path} is not valid JSON: {exc}") from exc
    shape = knobs.get_str("HYDRAGNN_TILE_SHAPE", "default")
    kind = knobs.get_str("HYDRAGNN_DEVICE_KIND", "default")
    by_shape = table.get(shape) or table.get("default") or {}
    entry = by_shape.get(kind) or by_shape.get("default") or {}
    for k in ("BN", "CE", "BCAST_CE"):
        if k in entry:
            out[k] = int(entry[k])
    out["source"] = "TUNE_TILES.json"
    return out


_TILE_DEFAULTS = _tile_defaults()
BN = knobs.get_int("HYDRAGNN_BN", _TILE_DEFAULTS["BN"])  # output rows (nodes) per grid step
CE = knobs.get_int("HYDRAGNN_CE", _TILE_DEFAULTS["CE"])  # edges DMA'd per inner chunk
# Gather-kernel chunk: the bcast kernel has no cross-chunk accumulator,
# so it tolerates bigger chunks than the family/sum kernels' CE —
# measured on v5e (r05 flagship trace, with the table window then tied
# to CE at 528 rows): 512 -> 77.8 ms/step, 1024 -> 75.9, 2048 -> 79.7.
# Default 1024. Neither chunk size moves the window (BW, below).
_BCAST_CE = knobs.get_int("HYDRAGNN_BCAST_CE", _TILE_DEFAULTS["BCAST_CE"])
if BN % 16 or CE % 16 or BN <= 0 or CE <= 0 or _BCAST_CE % 16 or _BCAST_CE <= 0:
    raise ValueError(
        f"HYDRAGNN_BN={BN} / HYDRAGNN_CE={CE} / HYDRAGNN_BCAST_CE={_BCAST_CE} "
        "must be positive multiples of 16 (Mosaic tiling: HBM slice starts "
        "and output blocks must stay tile-aligned — a misaligned value "
        "fails deep in kernel lowering)"
    )

_FORCE_XLA = contextvars.ContextVar("hydragnn_force_xla_segment_ops", default=False)


@contextlib.contextmanager
def xla_segment_ops():
    """Force the XLA segment path for every op traced inside this
    context. Needed where the partitioned kernel op cannot appear:
    under ``vmap`` (custom_partitioning has no batching rule —
    ``parallel/edge_sharded.py:make_dp_edge_train_step`` vmaps the
    model over the data axis). Trace-time scoped: wrap the code that
    BUILDS/TRACES the jitted function, not the execution."""
    tok = _FORCE_XLA.set(True)
    try:
        yield
    finally:
        _FORCE_XLA.reset(tok)


def _vma_of(*arrays) -> frozenset:
    """Union of the manual-mesh axes the given arrays vary over (empty
    outside shard_map)."""
    out: frozenset = frozenset()
    for a in arrays:
        out = out | jax.typeof(a).vma
    return out


def _match_vma(x, vma: frozenset):
    """Promote ``x`` to vary over ``vma`` — constructed operands (zero
    padding, window plans) otherwise arrive non-varying inside shard_map
    with check_vma=True and fail the interpreter's per-operand vma
    match."""
    missing = tuple(vma - jax.typeof(x).vma)
    return jax.lax.pcast(x, missing, to="varying") if missing else x


def pallas_available() -> bool:
    try:
        from jax.experimental import pallas as pl  # noqa: F401
        from jax.experimental.pallas import tpu as pltpu  # noqa: F401
    except ImportError:  # pragma: no cover
        return False
    return True


def segment_sum_family_xla(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    mask: Optional[jnp.ndarray] = None,
    indices_are_sorted: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(sum, sumsq, count) in ONE segment_sum over [E, 2H+1]."""
    # accumulate in f32 even under bf16 mixed precision: sum/sumsq feed a
    # variance cancellation (mean(x^2) - mean(x)^2) that bf16 cannot carry
    data = data.astype(jnp.float32)
    ones = jnp.ones((data.shape[0], 1), dtype=jnp.float32)
    if mask is not None:
        m = mask[:, None].astype(jnp.float32)
        data = data * m
        ones = ones * m
    packed = jnp.concatenate([data, data * data, ones], axis=-1)
    out = jax.ops.segment_sum(
        packed, segment_ids, num_segments, indices_are_sorted=indices_are_sorted
    )
    h = data.shape[1]
    return out[:, :h], out[:, h : 2 * h], out[:, 2 * h]


def _family_kernel(block_ptr_ref, msg_hbm, recv_hbm,
                   sum_ref, sumsq_ref,
                   msg_vmem, recv_vmem, sems):
    """One grid step aggregates every edge of node block i
    (rows [i*BN, (i+1)*BN)). Edges arrive receiver-sorted, so the block's
    edges live in [block_ptr[i], block_ptr[i+1]); DMA windows are CE-
    aligned (Mosaic tiling) and stray edges from neighbouring blocks are
    excluded by the one-hot receiver match itself. Chunks are
    DOUBLE-BUFFERED (see :func:`_csr_chunk_loop`)."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    _csr_chunk_loop(block_ptr_ref[i], block_ptr_ref[i + 1], msg_hbm, recv_hbm,
                    msg_vmem, recv_vmem, sems, sum_ref, sumsq_ref)


def _sum_kernel(block_ptr_ref, msg_hbm, recv_hbm, sum_ref,
                msg_vmem, recv_vmem, sems):
    """Sum-only sibling of :func:`_family_kernel` (one matmul per chunk)
    — serves the VJP hot paths (gather backwards, extremum tie counts)
    where only a plain segment sum is needed. Shares the DMA/one-hot
    structure via :func:`_csr_chunk_loop`."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    _csr_chunk_loop(block_ptr_ref[i], block_ptr_ref[i + 1], msg_hbm, recv_hbm,
                    msg_vmem, recv_vmem, sems, sum_ref, None)


def _sum_local_kernel(win_ref, msg_hbm, recv_hbm, sum_ref,
                      msg_vmem, recv_vmem, sems):
    """Segment sum for UNSORTED-BUT-LOCAL ids: block i's edges are not
    contiguous, but the caller guarantees every edge whose id falls in
    rows [i*B, (i+1)*B) — B = the out-ref block size, derived from the
    window shape by :func:`local_block_rows` — lies inside the
    edge-position window [win[0, i], win[1, i]) (host-precomputed —
    ``graph/batch.py`` emits it from the batch's block structure). The
    window may contain stray edges of neighbouring blocks; the one-hot
    id match excludes them, exactly like the CE-aligned DMA overhang
    in the sorted kernel."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    _csr_chunk_loop(win_ref[0, i], win_ref[1, i], msg_hbm, recv_hbm,
                    msg_vmem, recv_vmem, sems, sum_ref, None)


def _csr_chunk_loop(lo, hi, msg_hbm, recv_hbm,
                    msg_vmem, recv_vmem, sems, sum_ref, sumsq_ref):
    """Shared double-buffered CSR chunk loop: accumulate the one-hot
    matmul over edge positions [lo, hi) into ``sum_ref`` (and
    ``sumsq_ref`` when not None)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    sum_ref[:] = jnp.zeros_like(sum_ref)
    if sumsq_ref is not None:
        sumsq_ref[:] = jnp.zeros_like(sumsq_ref)
    k0 = lo // CE
    k1 = (hi + CE - 1) // CE

    def dmas(slot, k):
        start = pl.multiple_of(k * CE, CE)
        return (
            pltpu.make_async_copy(
                msg_hbm.at[pl.ds(start, CE), :], msg_vmem.at[slot], sems.at[slot, 0]
            ),
            pltpu.make_async_copy(
                recv_hbm.at[:, pl.ds(start, CE)], recv_vmem.at[slot], sems.at[slot, 1]
            ),
        )

    @pl.when(k0 < k1)
    def _warmup():
        for cp in dmas(k0 % 2, k0):
            cp.start()

    def chunk_body(k, _):
        slot = k % 2

        @pl.when(k + 1 < k1)
        def _prefetch():
            for cp in dmas((k + 1) % 2, k + 1):
                cp.start()

        for cp in dmas(slot, k):
            cp.wait()
        raw = msg_vmem[slot]
        # block size from the output ref itself: BN for the sorted
        # kernels, the window plan's derived size for the local kernel
        bn = sum_ref.shape[0]
        rows = jax.lax.broadcasted_iota(jnp.int32, (bn, CE), 0) + i * bn
        onehot = recv_vmem[slot] == rows
        if raw.dtype == jnp.bfloat16:
            # native-MXU bf16 path: onehot x value products are exact
            # (0/1 times an already-bf16 value) and accumulation is f32
            # — no need for the 6x-cost HIGHEST f32 emulation. The
            # squares are NOT bf16-exact, so sumsq splits the exact f32
            # square into hi + lo bf16 terms (two native matmuls):
            # products then roundtrip within ~2^-16 relative of f32,
            # matching the XLA reference's upcast-then-square.
            onehot_t = onehot.astype(jnp.bfloat16)
            sum_ref[:] += jax.lax.dot_general(
                onehot_t, raw, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if sumsq_ref is not None:
                sq = raw.astype(jnp.float32)
                sq = sq * sq
                hi = sq.astype(jnp.bfloat16)
                lo = (sq - hi.astype(jnp.float32)).astype(jnp.bfloat16)
                sumsq_ref[:] += jax.lax.dot_general(
                    onehot_t, hi, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) + jax.lax.dot_general(
                    onehot_t, lo, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
        else:
            # f32 values: 3-term bf16 split -> 3 native MXU matmuls per
            # sum instead of the 6-pass HIGHEST f32 emulation (2x
            # faster). The one-hot side is exact 0/1, and hi+mid+lo
            # carries 24 mantissa bits — the full f32 significand — so
            # each product reconstructs the f32 value exactly and the
            # only deviation from HIGHEST is f32 accumulation order
            # (well inside the segment-sum contract; a 2-term split was
            # tried and fails the 1e-5 interpret-vs-XLA gate under
            # cancellation). Bit-exactness contracts live in the GATHER
            # kernel (_window_gather_acc), which keeps HIGHEST.
            msg = raw.astype(jnp.float32)
            onehot_t = onehot.astype(jnp.bfloat16)

            def split_dot(x):
                hi = x.astype(jnp.bfloat16)
                r1 = x - hi.astype(jnp.float32)
                mid = r1.astype(jnp.bfloat16)
                lo = (r1 - mid.astype(jnp.float32)).astype(jnp.bfloat16)
                out = None
                for term in (hi, mid, lo):
                    d = jax.lax.dot_general(
                        onehot_t, term, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                    out = d if out is None else out + d
                return out

            sum_ref[:] += split_dot(msg)
            if sumsq_ref is not None:
                sumsq_ref[:] += split_dot(msg * msg)
        return 0

    jax.lax.fori_loop(k0, k1, chunk_body, 0)


def _csr_prep(data, segment_ids, mask, num_segments):
    """Shard-local prep for the CSR kernels (``segment_ids`` must be
    sorted ascending — sorting, if any, happens before the partitioned
    op so each shard's slice stays contiguous): dtype normalization
    (bf16 stays bf16 for half-width DMA unless a float weight mask
    forces f32; everything else goes f32), mask premultiply, CE tail
    padding with sentinel receivers, CSR block pointers."""
    e, h = data.shape
    n_pad = ((num_segments + BN - 1) // BN) * BN
    # bf16 stays bf16: the kernel DMAs half the bytes and upcasts in
    # registers before the f32-accumulating matmuls (under mixed
    # precision the model already rounded the messages to bf16, so no
    # information is lost); every other dtype goes f32
    float_mask = mask is not None and jnp.issubdtype(mask.dtype, jnp.floating)
    if data.dtype != jnp.bfloat16 or float_mask:
        # bf16 stays bf16 EXCEPT under a float weight mask: the weighted
        # products are not bf16-representable, and rounding them before
        # accumulation measurably diverges from the f32 XLA path at
        # realistic degrees (caught by the on-chip selfcheck at E=120k,
        # ~23 edges/node — boolean masks are exact in any dtype)
        data = data.astype(jnp.float32)
    if mask is not None:
        data = data * mask[:, None].astype(data.dtype)
    e_pad = ((e + CE - 1) // CE) * CE
    data = jnp.concatenate([data, jnp.zeros((e_pad - e, h), data.dtype)], axis=0)
    recv = jnp.concatenate(
        [segment_ids.astype(jnp.int32), jnp.full((e_pad - e,), n_pad, jnp.int32)]
    )
    n_blocks = n_pad // BN
    boundaries = jnp.arange(n_blocks + 1, dtype=jnp.int32) * BN
    block_ptr = jnp.searchsorted(recv[:e], boundaries, side="left").astype(jnp.int32)
    return data, recv, block_ptr, n_pad, n_blocks, h


def _csr_kernel_call(data, segment_ids, mask, num_segments, interpret, family):
    """Shard-local CSR kernel invocation (sorted contract). Returns
    (sum, sumsq, cnt) when ``family`` else the plain sum."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    data, recv, block_ptr, n_pad, n_blocks, h = _csr_prep(
        data, segment_ids, mask, num_segments
    )
    n_out = 2 if family else 1
    # under shard_map with check_vma=True the out_shape must declare which
    # manual mesh axes the result varies over, and every operand
    # (including constructed padding/pointer arrays) must carry them
    vma = _vma_of(data, recv)
    data = _match_vma(data, vma)
    recv = _match_vma(recv, vma)
    block_ptr = _match_vma(block_ptr, vma)
    out_sds = jax.ShapeDtypeStruct((n_pad, h), jnp.float32, vma=vma)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[pl.BlockSpec((BN, h), lambda i, ptr: (i, 0))] * n_out,
        scratch_shapes=[
            pltpu.VMEM((2, CE, h), data.dtype),
            pltpu.VMEM((2, 1, CE), jnp.int32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    outs = pl.pallas_call(
        _family_kernel if family else _sum_kernel,
        out_shape=[out_sds] * n_out,
        grid_spec=grid_spec,
        interpret=interpret,
        name="csr_family" if family else "csr_sum",
    )(block_ptr, data, recv[None, :])
    if not family:
        return outs[0][:num_segments]
    # the count is an [E, 1] reduction — bandwidth-trivial next to the
    # [E, H] passes, so XLA keeps it while Pallas does the heavy lifting
    ones = jnp.ones((segment_ids.shape[0],), jnp.float32)
    if mask is not None:
        ones = ones * mask.astype(jnp.float32)
    cnt = jax.ops.segment_sum(
        ones, segment_ids, num_segments, indices_are_sorted=True
    )
    return outs[0][:num_segments], outs[1][:num_segments], cnt


def _make_partitioned_op(family: bool, has_mask: bool):
    """Build a custom_partitioning wrapper around the CSR kernel.

    Partitioning rule: when GSPMD shards the operands on the edge axis
    (leading dim of ``data``/``ids``/``mask`` — the giant-graph path),
    each device runs the kernel on its local, contiguous,
    receiver-sorted edge slice against the full segment range, and one
    ``psum`` over the sharded mesh axis combines the per-node partials.
    Any other operand sharding is canonicalized to replicated. Outputs
    are replicated (they are [num_segments, ...] node-space arrays)."""
    n_args = 3 if has_mask else 2

    def base(*args):
        data, ids = args[0], args[1]
        mask = args[2] if has_mask else None
        num_segments, interpret = args[n_args], args[n_args + 1]
        return _csr_kernel_call(data, ids, mask, num_segments, interpret, family)

    op = custom_partitioning(base, static_argnums=(n_args, n_args + 1))

    def _out_shardings(mesh):
        rep = NamedSharding(mesh, P())
        return (rep, rep, rep) if family else rep

    def infer(num_segments, interpret, mesh, arg_shapes, result_shape):
        return _out_shardings(mesh)

    def partition(num_segments, interpret, mesh, arg_shapes, result_shape):
        spec = arg_shapes[0].sharding.spec
        edge_axis = spec[0] if len(spec) >= 1 else None

        def lower_fn(*arrs):
            data, ids = arrs[0], arrs[1]
            mask = arrs[2] if has_mask else None
            out = _csr_kernel_call(
                data, ids, mask, num_segments, interpret, family
            )
            if edge_axis is not None:
                out = jax.lax.psum(out, edge_axis)
            return out

        arg_sh = [
            NamedSharding(mesh, P(edge_axis, None)),
            NamedSharding(mesh, P(edge_axis)),
        ]
        if has_mask:
            arg_sh.append(NamedSharding(mesh, P(edge_axis)))
        return mesh, lower_fn, _out_shardings(mesh), tuple(arg_sh)

    ins = "e h, e" + (", e" if has_mask else "")
    outs = "n h, n h, n" if family else "n h"
    op.def_partition(
        partition=partition,
        infer_sharding_from_operands=infer,
        sharding_rule=f"{ins} -> {outs}",
    )
    return op


_FAMILY_OP = _make_partitioned_op(family=True, has_mask=False)
_FAMILY_OP_MASKED = _make_partitioned_op(family=True, has_mask=True)
_SUM_OP = _make_partitioned_op(family=False, has_mask=False)
_SUM_OP_MASKED = _make_partitioned_op(family=False, has_mask=True)


def _sort_for_csr(data, segment_ids, mask, indices_are_sorted):
    """Global pre-sort for the forced-kernel path. Happens OUTSIDE the
    partitioned op so the sorted contract holds per shard."""
    if indices_are_sorted:
        return data, segment_ids, mask
    order = jnp.argsort(segment_ids)
    return (
        data[order],
        segment_ids[order],
        None if mask is None else mask[order],
    )


@functools.partial(
    jax.jit, static_argnames=("num_segments", "interpret", "indices_are_sorted")
)
def segment_sum_family_pallas(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    mask: Optional[jnp.ndarray] = None,
    interpret: bool = False,
    indices_are_sorted: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    data, segment_ids, mask = _sort_for_csr(
        data, segment_ids, mask, indices_are_sorted
    )
    if mask is not None:
        return _FAMILY_OP_MASKED(data, segment_ids, mask, num_segments, interpret)
    return _FAMILY_OP(data, segment_ids, num_segments, interpret)


@functools.partial(
    jax.jit, static_argnames=("num_segments", "interpret", "indices_are_sorted")
)
def segment_sum_pallas(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    mask: Optional[jnp.ndarray] = None,
    interpret: bool = False,
    indices_are_sorted: bool = False,
) -> jnp.ndarray:
    """Plain segment sum through the double-buffered CSR kernel."""
    data, segment_ids, mask = _sort_for_csr(
        data, segment_ids, mask, indices_are_sorted
    )
    if mask is not None:
        return _SUM_OP_MASKED(data, segment_ids, mask, num_segments, interpret)
    return _SUM_OP(data, segment_ids, num_segments, interpret)


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def segment_sum_local_pallas(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    win: jnp.ndarray,
    num_segments: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Segment sum for UNSORTED ids with host-provided per-node-block
    edge windows — the scatter-add of a batched-graph sender axis
    without the [E, H] permute a sorted reduction needs (the permute
    row-gather is serial on TPU: ~7.4 ms at E=699k, r03 trace).

    ``win`` is int32 [2, n_blocks]: every edge e with
    ``segment_ids[e] // B == i`` must satisfy
    ``win[0, i] <= e < win[1, i]``, where the block size B =
    :func:`local_block_rows`(num_segments, n_blocks) — derived
    identically by the window EMITTER (``graph/batch.py:
    _block_windows``) and this kernel, so B rides the win SHAPE and
    needs no extra plumbing. Blocks sized to the batch's typical graph
    keep large graphs from re-scanning their edge window once per
    128-row block (docs/PERF.md r04). Windows of different blocks may
    overlap (stray ids are excluded by the kernel's one-hot match);
    empty blocks use lo == hi. Locality is guaranteed for batched
    graphs because each graph's nodes and edges are contiguous."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    e, h = data.shape
    n_blocks = int(win.shape[1])
    BNL = local_block_rows(num_segments, n_blocks)
    n_pad = n_blocks * BNL
    # a window plan emitted for a DIFFERENT num_segments derives a
    # different block size here and would silently drop edges whose
    # id // BNL disagrees with the emitter's id // B; the minimality
    # check catches that mismatch class (the emitter always produces
    # the minimal block count for its derived size)
    if n_blocks > 1 and (n_blocks - 1) * BNL >= num_segments:
        raise ValueError(
            f"win has {n_blocks} blocks but num_segments={num_segments} "
            f"needs at most {(num_segments + BNL - 1) // BNL} at the "
            f"derived block size {BNL} — the plan was emitted for a "
            "different num_segments (graph/batch.py:_block_windows)"
        )
    if data.dtype != jnp.bfloat16:
        data = data.astype(jnp.float32)
    e_pad = ((e + CE - 1) // CE) * CE
    data = jnp.concatenate([data, jnp.zeros((e_pad - e, h), data.dtype)], axis=0)
    ids = jnp.concatenate(
        [segment_ids.astype(jnp.int32), jnp.full((e_pad - e,), n_pad, jnp.int32)]
    )
    vma = _vma_of(data, ids)
    data = _match_vma(data, vma)
    ids = _match_vma(ids, vma)
    win = _match_vma(win.astype(jnp.int32), vma)
    out_sds = jax.ShapeDtypeStruct((n_pad, h), jnp.float32, vma=vma)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[pl.BlockSpec((BNL, h), lambda i, ptr: (i, 0))],
        scratch_shapes=[
            pltpu.VMEM((2, CE, h), data.dtype),
            pltpu.VMEM((2, 1, CE), jnp.int32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    (out,) = pl.pallas_call(
        _sum_local_kernel,
        out_shape=[out_sds],
        grid_spec=grid_spec,
        interpret=interpret,
        name="segment_sum_local",
    )(win, data, ids[None, :])
    return out[:num_segments]


def local_block_rows(num_segments: int, n_blocks: int) -> int:
    """The local-window kernels' block size, derived from the window
    plan's SHAPE: the B (multiple of 16 — the same bf16 sublane-tiling
    envelope the HYDRAGNN_BN guard enforces) with n_blocks * B >=
    num_segments that both the emitter and the kernel compute from
    (num_segments, n_blocks) — the contract that lets the host pick
    graph-sized blocks without extra static plumbing."""
    b = (num_segments + n_blocks - 1) // n_blocks
    return ((b + 15) // 16) * 16


def segment_sum_local_fast(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    win: Optional[jnp.ndarray],
    num_segments: int,
) -> jnp.ndarray:
    """Dispatcher for the local-window segment sum: Pallas kernel when
    the window plan is present and the knob/backend allow it (window
    locality substitutes for the sorted contract), XLA's unsorted
    scatter-add otherwise. Accumulates f32; returns f32 like
    :func:`segment_sum_fast`."""
    if win is not None and data.ndim == 2:
        h = _narrow_kernel_width(data, indices_are_sorted=True)
        if h is not None:
            return segment_sum_local_pallas(
                _lane_pad(data), segment_ids, win, num_segments,
                interpret=_interpret_mode(),
            )[:, :h]
        if _use_pallas(data, indices_are_sorted=True):
            return segment_sum_local_pallas(
                data, segment_ids, win, num_segments,
                interpret=_interpret_mode(),
            )
    return jax.ops.segment_sum(
        data.astype(jnp.float32), segment_ids, num_segments
    )


# ---------------------------------------------------------------------------
# CSR broadcast (sorted-ids row gather): out[e] = table[ids[e]]
# ---------------------------------------------------------------------------
#
# XLA lowers a [N, H] -> [E, H] row gather on TPU to a serial per-row
# loop — measured 6-9 ms at E=699k, H=128 on v5e (~19 GB/s effective),
# and the PNA backward pays ~36 of them per step (g_sum[recv],
# g_sumsq[recv], extremum out[recv]/share[recv] per layer): 280 of the
# 471 ms step (r03 trace, docs/PERF.md). For SORTED ids the gather is a
# CSR broadcast with perfect locality: an edge chunk of C ids
# (C = _BCAST_CE for the gather kernel, CE for the fused backward)
# reads only the table rows its ids span, so a one-hot MXU matmul
# (out_chunk = onehot[C, BW] @ window[BW, H]) streams the output at
# bandwidth instead of looping rows; a chunk whose ids span more than
# one BW-row window loops over as many windows as it needs. Exactness: each output row is
# 1.0 * table_row summed once — exact for bf16 inputs with f32
# accumulation; f32 inputs use HIGHEST (the f32-as-3xbf16 split times
# exact 1.0 reconstructs exactly) — for |x| >= ~1e-30. Below that the
# split's residual terms progressively fall under bf16's NORMAL floor
# and flush (measured v5e decay: ~2^-16 rel by 1e-33, ~2^-8 rel by
# 3e-36); below bf16's min normal (1.18e-38) the hi term itself
# flushes and the value reads back exactly 0 (gated by
# tools/tpu_selfcheck.py:bcast_tiny_magnitude_f32). Consequence for
# the extremum backward's tie detection (data == gather(out)):
# segments whose extremum magnitude is below ~1e-30 can drop their
# extremum gradient — numerically-negligible in any real training.

ALIGN = 16  # window starts/sizes are 16-row aligned: Mosaic must prove
# HBM slice starts divisible by the tiling — 8 rows for f32, 16 for
# packed bf16 (8-sublane tile x 2-row packing)
BW = 128  # table-window rows per DMA, one MXU pass wide. The one-hot
# costs BW columns a window, selecting or not, so the window follows the
# rows a chunk's ids span, not how many ids it holds: batched graphs'
# senders span 84 rows (p50) and at most 139 a 1,024-id chunk, sorted
# receivers at most 72 (the cells' loader batches, ISSUE 32), about one
# window a chunk; a wider span loops over ceil(span / BW) windows.
# Measured against 256 and 528 on v5e: PERF.md section 6, PR 32.


def _window_gather_acc(scal_ref, table_hbm, recv_ref, win_vmem, acc_ref, sems):
    """Shared windowed-gather loop: accumulate ``table[recv]`` for the
    current grid step's edge chunk into ``acc_ref`` (f32).

    A chunk's ids may SPAN any row range (ids can skip nodes), so the
    chunk loops over as many BW-wide windows as its span needs —
    ``scal_ref[1, k]`` (prefetched) holds the count, 1 in the batched-
    graph common case.
    Window DMA starts are clamped to stay in bounds; a logical range
    check keeps overlapping clamped windows from double-selecting.
    Exactness: each output row accumulates exactly one 1.0 x value
    product in f32 — native bf16 matmul for bf16 tables, HIGHEST for
    f32 (the f32-as-3xbf16 split times exact 1.0 reconstructs
    exactly). This is the subtlest logic in the file; it is shared by
    the bcast gather and the fused PNA backward's K2 so the two cannot
    diverge."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k = pl.program_id(0)
    astart = scal_ref[0, k]
    wcnt = scal_ref[1, k]
    n_clamp = scal_ref[2, 0]  # n_pad - BW: max legal DMA start
    recv = recv_ref[0, :]
    acc_ref[:] = jnp.zeros_like(acc_ref)

    def dma(slot, wstart):
        return pltpu.make_async_copy(
            table_hbm.at[
                pl.ds(pl.multiple_of(jnp.minimum(wstart, n_clamp), ALIGN), BW), :
            ],
            win_vmem.at[slot],
            sems.at[slot],
        )

    dma(0, astart).start()

    def window_body(w, _):
        slot = w % 2
        wstart = astart + w * BW

        @pl.when(w + 1 < wcnt)
        def _prefetch():
            dma((w + 1) % 2, wstart + BW).start()

        dma(slot, wstart).wait()
        cstart = jnp.minimum(wstart, n_clamp)
        local = recv - cstart  # [CE]
        # fold the logical-range check into the index vector (Mosaic
        # cannot broadcast a 1-bit vector into a minor dim): ids outside
        # [wstart, wstart + BW) get a poison index no iota lane matches
        in_range = (recv >= wstart) & (recv < wstart + BW)
        local = jnp.where(in_range, local, -1)
        onehot = (
            local[:, None]
            == jax.lax.broadcasted_iota(jnp.int32, (recv.shape[0], BW), 1)
        )
        win = win_vmem[slot]
        if win.dtype == jnp.float32:
            acc_ref[:] += jax.lax.dot_general(
                onehot.astype(jnp.float32), win, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
        else:
            acc_ref[:] += jax.lax.dot_general(
                onehot.astype(win.dtype), win, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        return 0

    jax.lax.fori_loop(0, wcnt, window_body, 0)


def _window_plan(recv, e, n_pad_t, n_chunks, ce=None):
    """Per-chunk window plan (scalar-prefetch operand for
    :func:`_window_gather_acc`): [astart; wcnt; n_clamp] as int32
    [3, n_chunks]. ``recv`` is the chunk-padded sorted id vector whose
    sentinels are >= ``n_pad_t`` (outside every logical window)."""
    ce = CE if ce is None else ce
    first = recv[::ce][:n_chunks]
    astart = first & ~jnp.int32(ALIGN - 1)
    last_real = jnp.minimum(recv[ce - 1 :: ce][:n_chunks], recv[e - 1])
    wcnt = jnp.maximum(1, (last_real + 1 - astart + BW - 1) // BW)
    return jnp.stack(
        [astart, wcnt, jnp.full((n_chunks,), n_pad_t - BW, jnp.int32)]
    ).astype(jnp.int32)


def _window_plan_local(recv, n_pad_t, n_chunks, ce=None):
    """Window plan for UNSORTED ids: per-chunk min/max via a fused
    [n_chunks, CE] reshape reduction (the sorted plan's strided-slice
    shortcut assumes monotonicity). Correct for arbitrary ids; FAST
    only when each chunk's ids span a narrow row range — true for
    batched graphs, whose senders are confined to their graph's
    contiguous node block. Sentinel ids (>= n_pad_t) never match a
    window row (windows are clamped to n_pad_t - BW), so only the min
    needs guarding against them."""
    ce = CE if ce is None else ce
    chunks = recv[: n_chunks * ce].reshape(n_chunks, ce)
    lo = jnp.min(chunks, axis=1)
    hi = jnp.minimum(jnp.max(chunks, axis=1), n_pad_t - 1)
    astart = lo & ~jnp.int32(ALIGN - 1)
    wcnt = jnp.maximum(1, (hi + 1 - astart + BW - 1) // BW)
    return jnp.stack(
        [astart, wcnt, jnp.full((n_chunks,), n_pad_t - BW, jnp.int32)]
    ).astype(jnp.int32)


def window_counts(ids, n_rows, ce=None):
    """Host mirror of :func:`_window_plan_local`'s count: the BW-row
    windows each ``ce``-id chunk (default ``_BCAST_CE``) of ``ids``
    needs over a table of ``n_rows`` rows. ``ids`` is [..., E]; each
    row of E ids is padded and chunked as :func:`_bcast_kernel_call`
    does. Set-up arithmetic for the manifest's ``gather_windows``
    (train/run.py), never on a step's path."""
    import numpy as np

    ce = _BCAST_CE if ce is None else ce
    ids = np.asarray(ids, np.int64)
    ids = ids.reshape(-1, ids.shape[-1])
    n_pad_t = max(((n_rows + ALIGN - 1) // ALIGN) * ALIGN, BW)
    e_pad = -(-ids.shape[1] // ce) * ce
    ids = np.pad(ids, ((0, 0), (0, e_pad - ids.shape[1])), constant_values=n_pad_t)
    chunks = ids.reshape(ids.shape[0], -1, ce)
    lo = chunks.min(axis=-1)
    hi = np.minimum(chunks.max(axis=-1), n_pad_t - 1)
    astart = lo & ~(ALIGN - 1)
    return np.maximum(1, (hi + 1 - astart + BW - 1) // BW).reshape(-1)


def _bcast_kernel(scal_ref, table_hbm, recv_ref, out_ref,
                  win_vmem, acc_ref, sems):
    """Grid step k: out rows [k*C, (k+1)*C) = table[recv rows], C =
    the call's chunk size (_BCAST_CE; a chunk whose ids span more than
    BW rows loops over several table windows).
    recv chunk and out chunk are Pallas-pipelined BlockSpec windows; the
    data-dependent table windows are manual DMAs (BlockSpec index maps
    cannot express data-dependent starts) — see
    :func:`_window_gather_acc`."""
    _window_gather_acc(scal_ref, table_hbm, recv_ref, win_vmem, acc_ref, sems)
    out_ref[:] = acc_ref[:].astype(out_ref.dtype)


def _bcast_kernel_call(table, ids, interpret, sorted_ids=True):
    """Shard-local windowed-row-gather kernel invocation. ``sorted_ids``
    picks the window-plan flavour: strided-slice shortcut for sorted
    ids, chunk min/max (:func:`_window_plan_local`) for unsorted-but-
    local ids — the kernel itself is id-order agnostic."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    e = ids.shape[0]
    n, h = table.shape
    if e == 0:
        return table[:0]
    # The gather kernel has no cross-chunk accumulator, so its chunk
    # size can exceed the family/sum kernels' CE without VMEM pressure;
    # HYDRAGNN_BCAST_CE overrides (per-call measurement knob).
    bce = _BCAST_CE
    n_pad = max(((n + ALIGN - 1) // ALIGN) * ALIGN, BW)
    if n_pad != n:
        table = jnp.concatenate(
            [table, jnp.zeros((n_pad - n, h), table.dtype)], axis=0
        )
    e_pad = ((e + bce - 1) // bce) * bce
    # sentinel rows land outside every logical window -> zero rows
    recv = jnp.concatenate(
        [ids.astype(jnp.int32), jnp.full((e_pad - e,), n_pad, jnp.int32)]
    )
    n_chunks = e_pad // bce
    if sorted_ids:
        scal = _window_plan(recv, e, n_pad, n_chunks, ce=bce)
    else:
        scal = _window_plan_local(recv, n_pad, n_chunks, ce=bce)
    vma = _vma_of(recv, table)
    table = _match_vma(table, vma)
    recv = _match_vma(recv, vma)
    scal = _match_vma(scal, vma)
    out_sds = jax.ShapeDtypeStruct((e_pad, h), table.dtype, vma=vma)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, bce), lambda k, ptr: (0, k)),
        ],
        out_specs=pl.BlockSpec((bce, h), lambda k, ptr: (k, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, BW, h), table.dtype),
            pltpu.VMEM((bce, h), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        _bcast_kernel,
        out_shape=out_sds,
        grid_spec=grid_spec,
        interpret=interpret,
        name="bcast_gather",
    )(scal, table, recv[None, :])
    return out[:e]


# ---------------------------------------------------------------------------
# Fused gather + K-group pre-reduction (r05): the PNA aligned path's four
# statistics without materializing v = table[senders] in HBM
# ---------------------------------------------------------------------------
#
# The run-aligned PNA branch (models/convs.py) computed v via the bcast
# gather ([E, H] HBM write), then read it back 4-6x in separate fused
# passes (sum8, sumsq8, vmax8, vneg8 — the r05 trace's "fwd reduce_sum
# n=4" block at ~3.5 ms/layer). This kernel keeps the gathered chunk in
# VMEM and emits the K-group statistics directly:
#
#   stats [E/K, 2H] f32   = [group-sum(masked v) | group-sum(masked v^2)]
#   both  [E/K, 2H] dtype = [group-max(masked v) | group-max(masked -v)]
#
# exactly the layouts the downstream E/K segment ops consume. The
# backward (jax.custom_vjp in :func:`gather_presum_stats`) REGATHERS v
# once and differentiates the identical jnp composition, so gradient
# semantics (incl. reshape-max tie handling) match the unfused path by
# construction; grad_table is the windowed local scatter.


def _gather_stats_kernel(scal_ref, table_hbm, recv_ref, mask_ref,
                         stats_ref, both_ref, win_vmem, acc_ref, sems):
    """Grid step k: gather chunk k's rows into VMEM (shared windowed
    loop), then reduce the K-groups in registers. K is static:
    chunk_rows // stats_rows."""
    _window_gather_acc(scal_ref, table_hbm, recv_ref, win_vmem, acc_ref, sems)
    acc = acc_ref[:]  # [bce, h] f32 (exact for bf16 tables)
    bce, h = acc.shape
    k_stat = bce // stats_ref.shape[0]
    # arithmetic masking: Mosaic cannot broadcast a 1-bit vector into a
    # minor dim (same constraint as _window_gather_acc's range check),
    # so the mask rides as f32 0/1 — exact, and select-free
    mf = mask_ref[0, :].astype(jnp.float32)[:, None]
    vf = acc * mf
    stats_ref[:, :h] = vf.reshape(-1, k_stat, h).sum(axis=1)
    stats_ref[:, h:] = (vf * vf).reshape(-1, k_stat, h).sum(axis=1)
    # fill with the OUTPUT dtype's min so all-masked groups read back
    # exactly like the unfused where(m, v, finfo(dtype).min) path
    neg = jnp.float32(jnp.finfo(both_ref.dtype).min)
    fill = (1.0 - mf) * neg
    vx = (acc * mf + fill).reshape(-1, k_stat, h).max(axis=1)
    vn = (-acc * mf + fill).reshape(-1, k_stat, h).max(axis=1)
    both_ref[:, :h] = vx.astype(both_ref.dtype)
    both_ref[:, h:] = vn.astype(both_ref.dtype)


def _gather_stats_call(table, ids, mask, k_group, interpret):
    """Invoke the fused gather+stats kernel. ``ids`` are unsorted-but-
    local (batched-graph senders); requires k_group | len(ids) and the
    chunk size divisible by k_group (loader-aligned batches guarantee
    both). Returns (stats [E/k, 2H] f32, both [E/k, 2H] table.dtype)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    e = ids.shape[0]
    n, h = table.shape
    bce = _BCAST_CE
    # explicit raise, not assert: a direct (non-gated) caller under
    # ``python -O`` must still get the invariant message rather than an
    # opaque Pallas BlockSpec/shape error downstream
    if e % bce != 0 or bce % k_group != 0:
        raise ValueError(
            "gather_presum_stats divisibility contract violated: needs "
            f"len(ids) % _BCAST_CE == 0 and _BCAST_CE % k_group == 0, got "
            f"len(ids)={e}, _BCAST_CE={bce}, k_group={k_group} — gate calls "
            "with gather_presum_eligible()"
        )
    n_pad = max(((n + ALIGN - 1) // ALIGN) * ALIGN, BW)
    if n_pad != n:
        table = jnp.concatenate(
            [table, jnp.zeros((n_pad - n, h), table.dtype)], axis=0
        )
    recv = ids.astype(jnp.int32)
    n_chunks = e // bce
    scal = _window_plan_local(recv, n_pad, n_chunks, ce=bce)
    mask_i = mask.astype(jnp.int32)
    vma = _vma_of(recv, table, mask_i)
    table = _match_vma(table, vma)
    recv = _match_vma(recv, vma)
    mask_i = _match_vma(mask_i, vma)
    scal = _match_vma(scal, vma)
    rows = e // k_group
    stats_sds = jax.ShapeDtypeStruct((rows, 2 * h), jnp.float32, vma=vma)
    both_sds = jax.ShapeDtypeStruct((rows, 2 * h), table.dtype, vma=vma)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, bce), lambda k, ptr: (0, k)),
            pl.BlockSpec((1, bce), lambda k, ptr: (0, k)),
        ],
        out_specs=[
            pl.BlockSpec((bce // k_group, 2 * h), lambda k, ptr: (k, 0)),
            pl.BlockSpec((bce // k_group, 2 * h), lambda k, ptr: (k, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, BW, h), table.dtype),
            pltpu.VMEM((bce, h), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    stats, both = pl.pallas_call(
        _gather_stats_kernel,
        out_shape=[stats_sds, both_sds],
        grid_spec=grid_spec,
        interpret=interpret,
        name="gather_stats",
    )(scal, table, recv[None, :], mask_i[None, :])
    return stats, both


def _presum_stats_ref(v, mask, k_group):
    """The unfused composition the kernel replaces — also the VJP's
    recompute target, so gradient semantics (reshape-sum broadcast,
    reshape-max even tie split) match the pre-r05 path exactly."""
    m = mask[:, None]
    h = v.shape[1]
    vf = jnp.where(m, v, 0).astype(jnp.float32)
    stats = jnp.concatenate(
        [
            vf.reshape(-1, k_group, h).sum(axis=1),
            (vf * vf).reshape(-1, k_group, h).sum(axis=1),
        ],
        axis=-1,
    )
    neg = jnp.finfo(v.dtype).min
    both = jnp.concatenate(
        [
            jnp.where(m, v, neg).reshape(-1, k_group, h).max(axis=1),
            jnp.where(m, -v, neg).reshape(-1, k_group, h).max(axis=1),
        ],
        axis=-1,
    )
    return stats, both


def local_min_rows() -> int:
    """Shared row threshold for the local-window kernel family: the
    fixed per-call cost (window plan + grid setup) only pays off on
    large operands (qm9's 61k-row config measured 7.5 vs 6.3 ms device
    on the local pair — docs/PERF.md r04)."""
    return knobs.get_int("HYDRAGNN_LOCAL_MIN_ROWS", 200_000)


def gather_presum_eligible(table, ids, win, k_group) -> bool:
    """Kernel-path gate for :func:`gather_presum_stats`: TPU with the
    local kernels active, host-emitted scatter windows present, lane-
    aligned width, and chunk divisibility at BOTH granularities (the
    call hard-asserts them; an ineligible shape must fall back, not
    crash — e.g. run_align=3 with an accidentally 1024-divisible
    E_pad, or a hand-tuned HYDRAGNN_BCAST_CE K doesn't divide)."""
    return (
        win is not None
        and table.ndim == 2
        and table.shape[1] % 128 == 0
        and ids.shape[0] % _BCAST_CE == 0
        and _BCAST_CE % k_group == 0
        and ids.shape[0] >= local_min_rows()
        and local_kernel_active()
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def gather_presum_stats(table, ids, mask, win, num_rows, k_group):
    """Fused ``v = table[ids]`` + masked K-group (sum, sumsq, max, -min)
    — the PNA aligned pre-reduction without materializing v in HBM.
    Callers must pass :func:`gather_presum_eligible` first; the fallback
    composition lives in the caller (models/convs.py), not here."""
    stats, both = _gather_stats_call(
        table, ids, mask, k_group, interpret=_interpret_mode()
    )
    return stats, both


def _gather_presum_fwd(table, ids, mask, win, num_rows, k_group):
    stats, both = gather_presum_stats(table, ids, mask, win, num_rows, k_group)
    return (stats, both), (table, ids, mask, win, both)


def _gather_presum_bwd(num_rows, k_group, res, cots):
    """Analytic backward: regather v once and assemble grad_v in closed
    form from the SAVED forward outputs — an earlier jax.vjp-based
    variant re-ran the whole forward composition inside the pullback
    (the primal is evaluated by jax.vjp), costing ~2.3 ms/layer of
    redundant E-level passes on the flagship trace.

    Semantics match plain AD of :func:`_presum_stats_ref`: the sum
    terms are linear (+ 2 v g for the square), the max terms follow
    jax's reduce-max convention — even split among tied group slots,
    tie counts taken on the FILLED values (masked slots tie only in
    all-masked groups, where the mask factor zeroes them anyway).
    Share math runs f32 (the extremum-VJP contract, segment.py)."""
    table, ids, mask, win, both_fwd = res
    g_stats, g_both = cots
    h = table.shape[1]
    m = mask[:, None]
    v = gather_rows_local_fast(table, ids)

    def rep(a):
        return jnp.broadcast_to(
            a[:, None, :], (a.shape[0], k_group, a.shape[1])
        ).reshape(a.shape[0] * k_group, a.shape[1])

    # tie masks stay in the COMPUTE dtype (0/1 exact in bf16; group
    # counts <= k_group are exact too) — an f32 formulation materialized
    # ~2 GB/layer of converts on the flagship trace. Shares divide in
    # f32 at the E/K level (bandwidth-trivial), then broadcast.
    neg = jnp.finfo(v.dtype).min
    tie_x = (jnp.where(m, v, neg) == rep(both_fwd[:, :h])).astype(v.dtype)
    tie_n = (jnp.where(m, -v, neg) == rep(both_fwd[:, h:])).astype(v.dtype)
    cnt_x = tie_x.reshape(-1, k_group, h).sum(axis=1).astype(jnp.float32)
    cnt_n = tie_n.reshape(-1, k_group, h).sum(axis=1).astype(jnp.float32)
    share_x = (
        g_both[:, :h].astype(jnp.float32) / jnp.maximum(cnt_x, 1.0)
    ).astype(v.dtype)
    share_n = (
        g_both[:, h:].astype(jnp.float32) / jnp.maximum(cnt_n, 1.0)
    ).astype(v.dtype)
    vf = jnp.where(m, v, 0).astype(jnp.float32)
    grad = (
        rep(g_stats[:, :h])
        + 2.0 * vf * rep(g_stats[:, h:])
        + (tie_x * rep(share_x)).astype(jnp.float32)
        - (tie_n * rep(share_n)).astype(jnp.float32)
    )
    grad_v = jnp.where(m, grad, 0.0).astype(table.dtype)
    grad_table = segment_sum_local_fast(
        grad_v, ids, win, num_rows
    ).astype(table.dtype)
    f0 = jax.dtypes.float0
    return (
        grad_table,
        jnp.zeros(ids.shape, dtype=f0),
        jnp.zeros(mask.shape, dtype=f0),
        jnp.zeros(win.shape, dtype=f0),
    )


gather_presum_stats.defvjp(_gather_presum_fwd, _gather_presum_bwd)


def _make_partitioned_bcast():
    """custom_partitioning wrapper: ids may be GSPMD-sharded on the edge
    axis (each shard's slice is contiguous and sorted — the giant-graph
    path); the table is replicated and each device gathers its local
    rows. Output follows the ids' edge sharding; no collective."""

    def base(table, ids, interpret, sorted_ids=True):
        return _bcast_kernel_call(table, ids, interpret, sorted_ids)

    op = custom_partitioning(base, static_argnums=(2, 3))

    def infer(interpret, sorted_ids, mesh, arg_shapes, result_shape):
        ids_spec = arg_shapes[1].sharding.spec
        edge_axis = ids_spec[0] if len(ids_spec) >= 1 else None
        return NamedSharding(mesh, P(edge_axis, None))

    def partition(interpret, sorted_ids, mesh, arg_shapes, result_shape):
        ids_spec = arg_shapes[1].sharding.spec
        edge_axis = ids_spec[0] if len(ids_spec) >= 1 else None

        def lower_fn(table, ids):
            return _bcast_kernel_call(table, ids, interpret, sorted_ids)

        arg_sh = (
            NamedSharding(mesh, P(None, None)),
            NamedSharding(mesh, P(edge_axis)),
        )
        return mesh, lower_fn, NamedSharding(mesh, P(edge_axis, None)), arg_sh

    op.def_partition(
        partition=partition,
        infer_sharding_from_operands=infer,
        sharding_rule="n h, e -> e h",
    )
    return op


_BCAST_OP = _make_partitioned_bcast()


def gather_rows_sorted_fast(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """``table[ids]`` for SORTED ids: the CSR-broadcast Pallas kernel on
    TPU (one-hot MXU matmul per edge chunk — streams at bandwidth where
    XLA's row gather loops serially), plain indexing otherwise. NOT
    differentiated — callers are custom backward functions (the gather's
    own VJP would be a sorted segment sum). Same knob contract as
    :func:`segment_sum_family`; requires 2-D [N, H] table with
    H % 128 == 0 for the kernel path (narrower tables are lane-padded
    in and sliced back — :func:`_lane_pad`)."""
    if ids.shape[0] == 0 or table.ndim != 2:
        return table[ids]
    h = _narrow_kernel_width(table, indices_are_sorted=True)
    if h is not None:
        return _BCAST_OP(_lane_pad(table), ids, _interpret_mode(), True)[:, :h]
    if _use_pallas(table, indices_are_sorted=True):
        return _BCAST_OP(table, ids, _interpret_mode(), True)
    return table[ids]


def gather_rows_local_fast(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """``table[ids]`` for UNSORTED-BUT-LOCAL ids (each id chunk
    spans a narrow row range — batched-graph senders): the windowed
    bcast kernel with the chunk-min/max plan. Plain indexing off-TPU.
    NOT differentiated, like :func:`gather_rows_sorted_fast` — callers
    pair it with the local-window segment sum backward."""
    if ids.shape[0] == 0 or table.ndim != 2:
        return table[ids]
    h = _narrow_kernel_width(table, indices_are_sorted=True)
    if h is not None:
        return _BCAST_OP(_lane_pad(table), ids, _interpret_mode(), False)[:, :h]
    if _use_pallas(table, indices_are_sorted=True):
        return _BCAST_OP(table, ids, _interpret_mode(), False)
    return table[ids]


def _kernel_eligible(indices_are_sorted: bool) -> bool:
    """Knob/backend part of the dispatch decision (no shape check)."""
    if _FORCE_XLA.get():
        return False
    knob = knobs.get_str("HYDRAGNN_PALLAS", "auto")
    if knob == "0":
        return False
    if not pallas_available():
        return False
    if knob == "interpret":
        return True
    if knob == "1":
        return jax.default_backend() == "tpu"
    return indices_are_sorted and jax.default_backend() == "tpu"


def local_kernel_active() -> bool:
    """Trace-time: would the local-window kernel pair actually lower to
    Pallas here? Callers holding BOTH a window plan and a sorted perm
    (the model chassis) use this to pick the local path only when it
    wins — on forced-XLA paths (vmap'd dp_edge step, non-TPU backends)
    the sorted-permute fallback beats the unsorted scatter-add the
    local fallback would pay."""
    return _kernel_eligible(indices_are_sorted=True)


def _use_pallas(data: jnp.ndarray, indices_are_sorted: bool) -> bool:
    """Shared HYDRAGNN_PALLAS knob contract (module docstring): "1"
    forces the kernel on TPU, "interpret" forces it in interpret mode
    on any backend, "0" forces XLA, default auto = Pallas on TPU for
    sorted, 2-D, 128-lane-multiple data. :func:`xla_segment_ops`
    overrides everything (vmap has no custom_partitioning rule)."""
    tiles = data.ndim == 2 and data.shape[1] % 128 == 0
    return tiles and _kernel_eligible(indices_are_sorted)


def _narrow_kernel_width(data: jnp.ndarray, indices_are_sorted: bool):
    """The shared narrow-data dispatch test: returns the original width
    ``h`` when ``data`` is 2-D, NOT 128-lane aligned, and the knob /
    backend allow the kernel — i.e. the caller should ``_lane_pad`` the
    data in and slice ``[:, :h]`` back out. None otherwise. One
    definition so the eligibility contract cannot diverge between the
    gather / sum / family dispatchers."""
    if data.ndim != 2:
        return None
    h = data.shape[1]
    if h % 128 != 0 and _kernel_eligible(indices_are_sorted):
        return h
    return None


def _lane_pad(data: jnp.ndarray) -> jnp.ndarray:
    """Zero-pad the feature axis up to the next 128-lane multiple.

    XLA's scatter/gather segment lowerings loop PER ROW on TPU, so a
    narrow op (e.g. the first conv layer, whose width is the raw
    feature count) costs the same 5-9 ms as a 128-wide one while the
    Pallas kernels stream rows in bulk — padding lanes to reach the
    kernel is a large net win (r03 trace: conv_0's XLA-fallback ops
    were ~40 ms of the step). Callers slice the result back; under AD
    the pad's transpose slices cotangents automatically."""
    h = data.shape[1]
    hp = ((h + 127) // 128) * 128
    return jnp.concatenate(
        [data, jnp.zeros((data.shape[0], hp - h), data.dtype)], axis=1
    )


def _interpret_mode() -> bool:
    return knobs.get_str("HYDRAGNN_PALLAS", "auto") == "interpret"


def segment_sum_fast(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    mask: Optional[jnp.ndarray] = None,
    indices_are_sorted: bool = False,
) -> jnp.ndarray:
    """Segment sum for VJP hot paths: the Pallas CSR kernel on TPU when
    receivers are sorted and the width tiles (same knob contract as
    :func:`segment_sum_family`), XLA otherwise. Not differentiated
    itself — callers are custom backward functions.

    ACCUMULATION CONTRACT: sums always accumulate in >= f32 regardless
    of input dtype — the kernel accumulates f32 natively (bf16 inputs
    DMA half the bytes, exact for 0/1-valued data like tie masks), and
    the XLA fallback upcasts sub-f32 inputs first. Callers may
    therefore pass bf16 cotangents/masks purely for bandwidth.

    f32 inputs ride a 3-term bf16 split (3 native MXU matmuls); the
    reconstruction is bit-exact only while all three split terms stay
    bf16-normal — |x| >= ~1e-30. Below that the lo/mid terms flush
    (bf16 subnormals) and accuracy decays to the hi term's 8 bits; the
    on-chip selfcheck gates the measured decay bands for BOTH the
    gather (``bcast_tiny_magnitude_f32``) and this sum path
    (``sum_tiny_magnitude_f32``). Training impact: segments whose
    values sit below ~1e-30 are numerically zero anyway.

    Narrow data is lane-padded into the kernel (see :func:`_lane_pad`)."""
    h = _narrow_kernel_width(data, indices_are_sorted)
    if h is not None:
        out = segment_sum_pallas(
            _lane_pad(data), segment_ids, num_segments, mask,
            interpret=_interpret_mode(),
            indices_are_sorted=indices_are_sorted,
        )
        return out[:, :h]
    if _use_pallas(data, indices_are_sorted):
        return segment_sum_pallas(
            data, segment_ids, num_segments, mask,
            interpret=_interpret_mode(),
            indices_are_sorted=indices_are_sorted,
        )
    if data.dtype in (jnp.bfloat16, jnp.float16):
        data = data.astype(jnp.float32)
    if mask is not None:
        data = data * mask[:, None].astype(data.dtype)
    return jax.ops.segment_sum(
        data, segment_ids, num_segments, indices_are_sorted=indices_are_sorted
    )


def _family_impl(data, segment_ids, num_segments, mask, indices_are_sorted, use_pallas):
    if use_pallas:
        return segment_sum_family_pallas(
            data, segment_ids, num_segments, mask,
            interpret=_interpret_mode(),
            indices_are_sorted=indices_are_sorted,
        )
    return segment_sum_family_xla(
        data, segment_ids, num_segments, mask,
        indices_are_sorted=indices_are_sorted,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 4, 5))
def _family(data, segment_ids, num_segments, mask, indices_are_sorted, use_pallas):
    """Family with a hand-written gather backward: makes the Pallas
    kernel trainable (pallas_call has no native VJP) and replaces XLA's
    packed-scatter VJP with the closed form
    d/d(data) = m * g_sum[ids] + 2 * m^2 * data * g_sumsq[ids]
    (m = mask weights; for a boolean mask m^2 = m and this reduces to
    the gated form)."""
    return _family_impl(data, segment_ids, num_segments, mask,
                        indices_are_sorted, use_pallas)


def _family_fwd(data, segment_ids, num_segments, mask, indices_are_sorted, use_pallas):
    out = _family_impl(data, segment_ids, num_segments, mask,
                       indices_are_sorted, use_pallas)
    return out, (data, segment_ids, mask)


def _family_bwd(num_segments, indices_are_sorted, use_pallas, res, g):
    data, segment_ids, mask = res
    g_sum, g_sumsq, _ = g  # count is data-independent
    # cast the [N, H] cotangents to the data dtype BEFORE the
    # [E, H]-widening gathers: under bf16 mixed precision this halves
    # the two gather writes (the backward's dominant HBM traffic), and
    # the final cotangent is data.dtype regardless
    g_sum = g_sum.astype(data.dtype)
    g_sumsq = g_sumsq.astype(data.dtype)
    if indices_are_sorted:
        # ONE stacked CSR-broadcast instead of two serial XLA row
        # gathers (the r03 trace's dominant backward cost: 6-9 ms each
        # at E=699k vs ~0.5 ms through the kernel)
        both = gather_rows_sorted_fast(
            jnp.concatenate([g_sum, g_sumsq], axis=-1), segment_ids
        )
        h = data.shape[1]
        g_sum_e, g_sumsq_e = both[:, :h], both[:, h:]
    else:
        g_sum_e, g_sumsq_e = g_sum[segment_ids], g_sumsq[segment_ids]
    sumsq_term = 2.0 * data * g_sumsq_e
    if mask is None:
        grad = g_sum_e + sumsq_term
        mask_zero = None
    else:
        # weighted closed form: out_sum = sum(m*d), out_sumsq = sum(m^2*d^2)
        # => d/dd = m*g_sum[ids] + 2*m^2*d*g_sumsq[ids]
        m = mask.astype(g_sum.dtype)[:, None]
        grad = m * (g_sum_e + m * sumsq_term)
        # the mask is non-differentiable by contract (stop_gradient on
        # entry in segment_sum_family): bool/int masks take a float0
        # cotangent, float weight masks a true-zero one
        if jnp.issubdtype(mask.dtype, jnp.floating):
            mask_zero = jnp.zeros(mask.shape, dtype=mask.dtype)
        else:
            mask_zero = jnp.zeros(mask.shape, dtype=jax.dtypes.float0)
    ids_zero = jnp.zeros(segment_ids.shape, dtype=jax.dtypes.float0)
    return grad.astype(data.dtype), ids_zero, mask_zero


_family.defvjp(_family_fwd, _family_bwd)


# ---------------------------------------------------------------------------
# Fused PNA aggregation: (sum, sumsq, [max(v), max(-v)]) with a two-kernel
# backward
# ---------------------------------------------------------------------------
#
# The r03 retrace showed the PNA backward still paying ~128 ms/step in
# edge-space fragments: per layer, two widening gathers for the family
# cotangents, tie-mask construction + a count kernel + a share gather
# per extremum, then three [E, H] cotangent branches concatenated and
# added. Fusing the WHOLE aggregation backward into two CSR kernels
# collapses all of it to three [E, *] passes per layer:
#
#   K1 (node-block grid): one pass over v computing the min/max tie
#      counts [N, 2H] — the per-edge extremum values arrive via a
#      one-hot MXU matmul against the node-blocked `both` array, so the
#      tie masks never touch HBM.
#   K2 (edge-chunk grid): one pass over v emitting the COMPLETE grad_v
#      — all six node-level tables (g_sum, g_sumsq, both, shares) are
#      stacked into one [N, 6H] table and gathered per chunk with a
#      single windowed one-hot matmul (the bcast kernel's window plan),
#      then combined in VMEM:
#        grad = m * (g_sum_e + 2 v g_sumsq_e
#                    + (v == max_e) shmax_e - ((-v) == negmin_e) shmin_e)
#
# Exactness of the tie compares: one-hot x bf16 products are exact and
# each output row accumulates exactly one nonzero product in f32, so the
# gathered extremum is a bit-exact row copy and `v == max_e` matches the
# unfused semantics. Masked edges carry vv = -inf in K1 (never tie in a
# real segment) and are zeroed by the final m factor in K2; `both` is
# empty-cleaned to 0 before the backward, so empty segments tie nothing.
#
# The float-weight-mask case (m^2 factor on the sumsq term) and
# non-kernel contexts fall back to an unfused composition of the same
# formulas.


def _pna_bwd_count_kernel(block_ptr_ref, v_hbm, recv_hbm, mask_hbm, both_ref,
                          cnt_ref, v_vmem, recv_vmem, mask_vmem, sems):
    """K1: per node block, count min/max ties over the block's edges."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    lo = block_ptr_ref[i]
    hi = block_ptr_ref[i + 1]
    cnt_ref[:] = jnp.zeros_like(cnt_ref)
    k0 = lo // CE
    k1 = (hi + CE - 1) // CE
    has_mask = mask_hbm is not None

    def dmas(slot, k):
        start = pl.multiple_of(k * CE, CE)
        cps = [
            pltpu.make_async_copy(
                v_hbm.at[pl.ds(start, CE), :], v_vmem.at[slot], sems.at[slot, 0]
            ),
            pltpu.make_async_copy(
                recv_hbm.at[:, pl.ds(start, CE)], recv_vmem.at[slot], sems.at[slot, 1]
            ),
        ]
        if has_mask:
            cps.append(
                pltpu.make_async_copy(
                    mask_hbm.at[:, pl.ds(start, CE)], mask_vmem.at[slot],
                    sems.at[slot, 2],
                )
            )
        return cps

    @pl.when(k0 < k1)
    def _warmup():
        for cp in dmas(k0 % 2, k0):
            cp.start()

    def chunk_body(k, _):
        slot = k % 2

        @pl.when(k + 1 < k1)
        def _prefetch():
            for cp in dmas((k + 1) % 2, k + 1):
                cp.start()

        for cp in dmas(slot, k):
            cp.wait()
        v = v_vmem[slot]
        # tie detection runs in f32 regardless of data dtype (the v5e
        # VPU has no bf16 compare): bf16 -> f32 is exact, and the
        # gathered extremum rows are f32 accumulations of exact values
        neg = float(jnp.finfo(v.dtype).min)
        vv = jnp.concatenate([v, -v], axis=-1).astype(jnp.float32)  # [CE, 2H]
        if has_mask:
            # arithmetic masking (avoids broadcasting a 1-bit vector):
            # unmasked rows keep their value, masked rows become the
            # forward's where(mask, vv, finfo.min) sentinel
            m = (mask_vmem[slot][0, :][:, None] > 0).astype(jnp.float32)
            vv = jnp.maximum(vv * m + (1.0 - m) * neg, neg)
        rows = jax.lax.broadcasted_iota(jnp.int32, (BN, CE), 0) + i * BN
        onehot = recv_vmem[slot] == rows  # [BN, CE]
        # per-edge extremum rows via one-hot matmul against the node
        # block: exact row copies — native bf16 for bf16 data (0/1
        # products exact, single nonzero per row, f32 accumulation),
        # HIGHEST for f32 (the 3x-bf16 split times exact 1.0
        # reconstructs exactly)
        if v.dtype == jnp.bfloat16:
            oh = onehot.astype(jnp.bfloat16)
            both_e = jax.lax.dot_general(
                oh, both_ref[:], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            sel = (vv == both_e).astype(jnp.bfloat16)
            cnt_ref[:] += jax.lax.dot_general(
                oh, sel, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        else:
            oh = onehot.astype(jnp.float32)
            both_e = jax.lax.dot_general(
                oh, both_ref[:].astype(jnp.float32), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
            sel = (vv == both_e).astype(jnp.float32)
            cnt_ref[:] += jax.lax.dot_general(
                oh, sel, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
        return 0

    jax.lax.fori_loop(k0, k1, chunk_body, 0)


def _pna_bwd_grad_kernel(scal_ref, table_hbm, recv_ref, v_ref, mask_ref,
                         grad_ref, win_vmem, acc_ref, sems):
    """K2: per edge chunk, gather the stacked [N, 6H] cotangent table
    (shared window plan/loop — :func:`_window_gather_acc`) and emit the
    complete grad_v chunk."""
    _window_gather_acc(scal_ref, table_hbm, recv_ref, win_vmem, acc_ref, sems)

    v = v_ref[:]
    h = v.shape[1]
    # combine in f32: the acc rows are exact copies of the (possibly
    # bf16) table values, v upcasts exactly, and the v5e VPU has no
    # bf16 compare anyway — only the final grad casts back
    vf = v.astype(jnp.float32)
    g = acc_ref[:]  # [CE, 6H] f32
    gs, gss = g[:, :h], g[:, h : 2 * h]
    mx, mnn = g[:, 2 * h : 3 * h], g[:, 3 * h : 4 * h]
    shx, shn = g[:, 4 * h : 5 * h], g[:, 5 * h :]
    grad = gs + 2.0 * vf * gss
    grad = grad + jnp.where(vf == mx, shx, 0.0)
    grad = grad - jnp.where(-vf == mnn, shn, 0.0)
    if mask_ref is not None:
        m = (mask_ref[0, :] > 0).astype(jnp.float32)
        # bool-mask semantics: m == m^2, one factor gates everything
        grad = grad * m[:, None]
    grad_ref[:] = grad.astype(grad_ref.dtype)


def _pna_bwd_kernels(v, receivers, mask, both, g_sum, g_sumsq, g_both,
                     num_segments, interpret):
    """Shard-local fused backward: K1 tie counts, node-level shares,
    K2 full grad. Requires sorted receivers, H % 128 == 0, bool mask."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    e, h = v.shape
    vd = v.dtype
    n_pad_out = ((num_segments + BN - 1) // BN) * BN
    e_pad = ((e + CE - 1) // CE) * CE
    recv = jnp.concatenate(
        [receivers.astype(jnp.int32), jnp.full((e_pad - e,), n_pad_out, jnp.int32)]
    )
    v_p = jnp.concatenate([v, jnp.zeros((e_pad - e, h), vd)], axis=0)
    if mask is not None:
        mask_i = jnp.concatenate(
            [mask.astype(jnp.int32), jnp.zeros((e_pad - e,), jnp.int32)]
        )
    else:
        mask_i = None

    # ---- K1: tie counts [n_pad_out, 2H] ----
    both_p = jnp.concatenate(
        [both, jnp.zeros((n_pad_out - num_segments, 2 * h), both.dtype)], axis=0
    )
    n_blocks = n_pad_out // BN
    boundaries = jnp.arange(n_blocks + 1, dtype=jnp.int32) * BN
    block_ptr = jnp.searchsorted(recv[:e], boundaries, side="left").astype(jnp.int32)
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),  # v
        pl.BlockSpec(memory_space=pl.ANY),  # recv
    ]
    operands = [v_p, recv[None, :]]
    if mask_i is not None:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands.append(mask_i[None, :])
    in_specs.append(pl.BlockSpec((BN, 2 * h), lambda i, ptr: (i, 0)))  # both
    operands.append(both_p)

    def k1_kernel(*args):
        if mask_i is not None:
            ptr, vh, rh, mh, bh, cnt, vv, rv, mv, sems = args
            _pna_bwd_count_kernel(ptr, vh, rh, mh, bh, cnt, vv, rv, mv, sems)
        else:
            ptr, vh, rh, bh, cnt, vv, rv, sems = args
            _pna_bwd_count_kernel(ptr, vh, rh, None, bh, cnt, vv, rv, None, sems)

    scratch = [
        pltpu.VMEM((2, CE, h), vd),
        pltpu.VMEM((2, 1, CE), jnp.int32),
    ]
    if mask_i is not None:
        scratch.append(pltpu.VMEM((2, 1, CE), jnp.int32))
    scratch.append(pltpu.SemaphoreType.DMA((2, 3)))
    # under shard_map with check_vma=True the out_shape must declare
    # which manual mesh axes the result varies over, and every operand
    # must carry them (same as the family/bcast kernels)
    vma = _vma_of(v_p, recv, both_p)
    operands = [_match_vma(o, vma) for o in operands]
    block_ptr = _match_vma(block_ptr, vma)
    cnt_both = pl.pallas_call(
        k1_kernel,
        out_shape=jax.ShapeDtypeStruct((n_pad_out, 2 * h), jnp.float32, vma=vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_blocks,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((BN, 2 * h), lambda i, ptr: (i, 0)),
            scratch_shapes=scratch,
        ),
        interpret=interpret,
        name="pna_bwd_tie_count",
    )(block_ptr, *operands)[:num_segments]

    # ---- node-level shares, stacked table ----
    share = (g_both.astype(jnp.float32) / jnp.maximum(cnt_both, 1.0)).astype(vd)
    table = jnp.concatenate(
        [g_sum.astype(vd), g_sumsq.astype(vd), both.astype(vd), share], axis=-1
    )  # [num_segments, 6H]

    # ---- K2: full grad via the bcast window plan over the 6H table ----
    n = table.shape[0]
    n_pad_t = max(((n + ALIGN - 1) // ALIGN) * ALIGN, BW)
    table_p = jnp.concatenate(
        [table, jnp.zeros((n_pad_t - n, 6 * h), vd)], axis=0
    )
    recv_t = jnp.where(recv >= n, n_pad_t, recv)  # sentinels beyond windows
    n_chunks = e_pad // CE
    scal = _window_plan(recv_t, e, n_pad_t, n_chunks)

    in_specs2 = [
        pl.BlockSpec(memory_space=pl.ANY),  # table
        pl.BlockSpec((1, CE), lambda k, ptr: (0, k)),  # recv
        pl.BlockSpec((CE, h), lambda k, ptr: (k, 0)),  # v
    ]
    operands2 = [table_p, recv_t[None, :], v_p]
    if mask_i is not None:
        in_specs2.append(pl.BlockSpec((1, CE), lambda k, ptr: (0, k)))
        operands2.append(mask_i[None, :])

    def k2_kernel(*args):
        if mask_i is not None:
            scal_r, th, rr, vr, mr, gr, wv, ac, sems = args
            _pna_bwd_grad_kernel(scal_r, th, rr, vr, mr, gr, wv, ac, sems)
        else:
            scal_r, th, rr, vr, gr, wv, ac, sems = args
            _pna_bwd_grad_kernel(scal_r, th, rr, vr, None, gr, wv, ac, sems)

    vma2 = vma | _vma_of(table_p)
    operands2 = [_match_vma(o, vma2) for o in operands2]
    scal = _match_vma(scal, vma2)
    grad = pl.pallas_call(
        k2_kernel,
        out_shape=jax.ShapeDtypeStruct((e_pad, h), vd, vma=vma2),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_chunks,),
            in_specs=in_specs2,
            out_specs=pl.BlockSpec((CE, h), lambda k, ptr: (k, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, BW, 6 * h), vd),
                pltpu.VMEM((CE, 6 * h), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        interpret=interpret,
        name="pna_bwd_grad",
    )(scal, *operands2)
    return grad[:e]


def _pna_bwd_unfused(v, receivers, mask, both, g_sum, g_sumsq, g_both,
                     num_segments, indices_are_sorted):
    """Reference composition of the same backward (CPU / vmap / float
    masks): identical math, built from the dispatching building blocks."""
    vd = v.dtype
    h = v.shape[1]
    neg = jnp.finfo(vd).min
    vv = jnp.concatenate([v, -v], axis=-1)
    if mask is not None:
        vv = jnp.where(mask[:, None], vv, neg)
    from hydragnn_tpu.graph.segment import _gather_fwd_impl

    both_e = _gather_fwd_impl(both.astype(vd), receivers, indices_are_sorted)
    sel = vv == both_e
    cnt_both = segment_sum_fast(
        sel.astype(vd), receivers, num_segments,
        indices_are_sorted=indices_are_sorted,
    ).astype(jnp.float32)
    share = (g_both.astype(jnp.float32) / jnp.maximum(cnt_both, 1.0)).astype(vd)
    gpack = _gather_fwd_impl(
        jnp.concatenate([g_sum.astype(vd), g_sumsq.astype(vd), share], axis=-1),
        receivers, indices_are_sorted,
    )
    gs, gss, sh = gpack[:, :h], gpack[:, h : 2 * h], gpack[:, 2 * h :]
    ties = jnp.where(sel, sh, vd.type(0))
    tie_term = ties[:, :h] - ties[:, h:]
    if mask is None:
        grad = gs + 2.0 * v * gss + tie_term
    elif jnp.issubdtype(mask.dtype, jnp.floating):
        # float masks WEIGHT the sums (m on sum, m^2 on sumsq — the
        # family's weighted closed form) but only GATE the extremum
        # (the forward's where(mask, vv, -inf) is a boolean gate)
        m = mask.astype(vd)[:, None]
        mb = (mask != 0).astype(vd)[:, None]
        grad = m * gs + m * m * 2.0 * v * gss + mb * tie_term
    else:
        m = mask.astype(vd)[:, None]
        grad = m * (gs + 2.0 * v * gss + tie_term)
    return grad.astype(vd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 4))
def _pna_aggregate(v, receivers, num_segments, mask, indices_are_sorted):
    s, sq, cnt = _family_impl(
        v, receivers, num_segments, mask, indices_are_sorted,
        _use_pallas(v, indices_are_sorted),
    )
    vd = v.dtype
    neg = jnp.finfo(vd).min
    vv = jnp.concatenate([v, -v], axis=-1)
    if mask is not None:
        vv = jnp.where(mask[:, None], vv, neg)
    raw = jax.ops.segment_max(
        vv, receivers, num_segments, indices_are_sorted=indices_are_sorted
    )
    both = jnp.where(raw <= neg, vd.type(0), raw)  # empty-cleaned
    return s, sq, cnt, both


def _pna_aggregate_fwd(v, receivers, num_segments, mask, indices_are_sorted):
    out = _pna_aggregate(v, receivers, num_segments, mask, indices_are_sorted)
    return out, (v, receivers, mask, out[3])


def _pna_aggregate_bwd(num_segments, indices_are_sorted, res, g):
    v, receivers, mask, both = res
    g_sum, g_sumsq, _, g_both = g  # count is data-independent
    float_mask = mask is not None and jnp.issubdtype(mask.dtype, jnp.floating)
    if (
        indices_are_sorted
        and v.ndim == 2
        and v.shape[1] % 128 == 0
        and not float_mask
        and _kernel_eligible(indices_are_sorted)
    ):
        grad = _pna_bwd_kernels(
            v, receivers, mask, both.astype(v.dtype), g_sum, g_sumsq, g_both,
            num_segments, _interpret_mode(),
        )
    else:
        grad = _pna_bwd_unfused(
            v, receivers, mask, both.astype(v.dtype), g_sum, g_sumsq, g_both,
            num_segments, indices_are_sorted,
        )
    ids_zero = jnp.zeros(receivers.shape, dtype=jax.dtypes.float0)
    if mask is None:
        mask_zero = None
    elif jnp.issubdtype(mask.dtype, jnp.floating):
        mask_zero = jnp.zeros(mask.shape, dtype=mask.dtype)
    else:
        mask_zero = jnp.zeros(mask.shape, dtype=jax.dtypes.float0)
    return grad, ids_zero, mask_zero


_pna_aggregate.defvjp(_pna_aggregate_fwd, _pna_aggregate_bwd)


def pna_aggregate(
    v: jnp.ndarray,
    receivers: jnp.ndarray,
    num_segments: int,
    mask: Optional[jnp.ndarray] = None,
    indices_are_sorted: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused PNA aggregation statistics of ``v`` grouped by receiver.

    Returns ``(vsum f32, vsumsq f32, cnt f32, both)`` where
    ``both[:, :H] = segment_max(v)`` and ``both[:, H:] =
    segment_max(-v)`` (= -min), masked, with EMPTY segments already
    cleaned to 0; ``cnt`` is the mask-aware per-segment edge count the
    family pass computes anyway (data-independent cotangent — callers
    with a precomputed degree can ignore it and XLA dead-code
    eliminates it). The backward is the two-kernel fused pass
    documented above (falls back to an unfused composition off-TPU /
    under vmap / for float masks). The mask is non-differentiable by
    contract. Narrow data is lane-padded into the kernels
    (:func:`_lane_pad`) and the outputs sliced back."""
    if mask is not None:
        mask = jax.lax.stop_gradient(mask)
    h = _narrow_kernel_width(v, indices_are_sorted)
    if h is not None:
        s, sq, cnt, both = _pna_aggregate(
            _lane_pad(v), receivers, num_segments, mask, indices_are_sorted
        )
        hp = (h + 127) // 128 * 128
        both = jnp.concatenate([both[:, :h], both[:, hp : hp + h]], axis=-1)
        return s[:, :h], sq[:, :h], cnt, both
    return _pna_aggregate(v, receivers, num_segments, mask, indices_are_sorted)


def segment_sum_family(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    mask: Optional[jnp.ndarray] = None,
    indices_are_sorted: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Dispatch. Default ("auto"): the double-buffered Pallas kernel on
    TPU when the caller guarantees sorted receivers and the feature
    width is a 128-lane multiple (measured 5.5x faster than the XLA
    scatter at E=120k, H=128 on v5e — docs/PERF.md); the fused XLA pass
    otherwise. The kernel op carries a custom_partitioning rule, so it
    composes with GSPMD edge sharding (module docstring); only vmap
    contexts need :func:`xla_segment_ops`. The mask (edge validity or
    float weights) is non-differentiable by contract. Narrow data is
    lane-padded into the kernel (:func:`_lane_pad`; the pad's AD
    transpose slices the cotangent back automatically)."""
    if mask is not None:
        mask = jax.lax.stop_gradient(mask)
    h = _narrow_kernel_width(data, indices_are_sorted)
    if h is not None:
        s, sq, cnt = _family(
            _lane_pad(data), segment_ids, num_segments, mask,
            indices_are_sorted, True,
        )
        return s[:, :h], sq[:, :h], cnt
    use_pallas = _use_pallas(data, indices_are_sorted)
    return _family(data, segment_ids, num_segments, mask,
                   indices_are_sorted, use_pallas)
