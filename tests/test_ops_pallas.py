"""Fused sum-family aggregation: XLA fused pass and Pallas kernel
(interpret mode on CPU) must match the plain per-op reference."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from jax import shard_map
from hydragnn_tpu.ops import (
    segment_sum_family_pallas,
    segment_sum_family_xla,
)


@pytest.fixture
def case():
    rng = np.random.default_rng(5)
    e, h, n = 700, 16, 100
    recv = np.sort(rng.integers(0, n, e)).astype(np.int32)
    data = rng.normal(size=(e, h)).astype(np.float32)
    mask = rng.random(e) > 0.2
    return jnp.asarray(data), jnp.asarray(recv), n, jnp.asarray(mask)


def _reference(data, recv, n, mask):
    m = np.asarray(mask)[:, None]
    d = np.asarray(data) * m
    s = np.zeros((n, d.shape[1]), np.float64)
    sq = np.zeros((n, d.shape[1]), np.float64)
    c = np.zeros(n, np.float64)
    np.add.at(s, np.asarray(recv), d)
    np.add.at(sq, np.asarray(recv), d * d)
    np.add.at(c, np.asarray(recv), m[:, 0].astype(np.float64))
    return s, sq, c


def pytest_xla_family_matches_reference(case):
    data, recv, n, mask = case
    s, sq, c = segment_sum_family_xla(data, recv, n, mask)
    rs, rsq, rc = _reference(data, recv, n, mask)
    np.testing.assert_allclose(s, rs, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(sq, rsq, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(c, rc, rtol=1e-6)


def pytest_pallas_family_matches_reference(case):
    data, recv, n, mask = case
    s, sq, c = segment_sum_family_pallas(data, recv, n, mask, interpret=True)
    rs, rsq, rc = _reference(data, recv, n, mask)
    np.testing.assert_allclose(s, rs, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(sq, rsq, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(c, rc, rtol=1e-6)


def pytest_pallas_family_no_mask_multi_chunk():
    """More edges than one CE chunk per block, empty segments included."""
    rng = np.random.default_rng(7)
    e, h, n = 3000, 8, 40  # ~75 edges/node; block 0 covers all 40 nodes
    recv = np.sort(rng.integers(0, n // 2, e)).astype(np.int32)  # half empty
    data = rng.normal(size=(e, h)).astype(np.float32)
    s, sq, c = segment_sum_family_pallas(
        jnp.asarray(data), jnp.asarray(recv), n, None, interpret=True
    )
    rs, rsq, rc = _reference(data, recv, n, np.ones(e, bool))
    np.testing.assert_allclose(s, rs, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(sq, rsq, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(c, rc, rtol=1e-6)


def pytest_xla_family_unsorted_ids():
    """The default path must be correct for sender-major (unsorted
    receiver) edge orderings, e.g. SMILES-featurized graphs."""
    rng = np.random.default_rng(9)
    e, h, n = 500, 8, 60
    recv = rng.integers(0, n, e).astype(np.int32)  # deliberately unsorted
    data = rng.normal(size=(e, h)).astype(np.float32)
    s, sq, c = segment_sum_family_xla(jnp.asarray(data), jnp.asarray(recv), n)
    rs, rsq, rc = _reference(data, recv, n, np.ones(e, bool))
    np.testing.assert_allclose(s, rs, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(c, rc, rtol=1e-6)


def pytest_pallas_family_unsorted_ids_sorts():
    """Default indices_are_sorted=False must be correct for sender-major
    orderings (the kernel sorts internally)."""
    rng = np.random.default_rng(13)
    e, h, n = 600, 8, 70
    recv = rng.integers(0, n, e).astype(np.int32)  # deliberately unsorted
    data = rng.normal(size=(e, h)).astype(np.float32)
    s, sq, c = segment_sum_family_pallas(
        jnp.asarray(data), jnp.asarray(recv), n, None, interpret=True
    )
    rs, rsq, rc = _reference(data, recv, n, np.ones(e, bool))
    np.testing.assert_allclose(s, rs, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(c, rc, rtol=1e-6)


def pytest_family_accumulates_f32_under_bf16():
    """bf16 inputs: mean/var cancellation must not collapse (f32 accum)."""
    rng = np.random.default_rng(21)
    e, n = 512, 4
    recv = np.sort(rng.integers(0, n, e)).astype(np.int32)
    # mean 8, spread 0.2: representable in bf16 (ulp ~0.03) but a ~128-term
    # bf16 running sum (~1000, ulp ~4) would drown the contributions;
    # f32 accumulation must preserve the variance's order of magnitude
    data = (8.0 + 0.2 * rng.normal(size=(e, 8))).astype(np.float32)
    s, sq, c = segment_sum_family_xla(
        jnp.asarray(data, dtype=jnp.bfloat16), jnp.asarray(recv), n
    )
    mean = np.asarray(s) / np.asarray(c)[:, None]
    var = np.asarray(sq) / np.asarray(c)[:, None] - mean**2
    assert np.all(var > 5e-3), var.min()
    assert np.all(var < 1e-1), var.max()


def pytest_family_custom_vjp_matches_autodiff():
    """segment_sum_family routes ALL training gradients through the
    hand-written gather VJP; it must equal autodiff of the mathematical
    definition (masked sum / sum-of-squares), including masked rows."""
    rng = np.random.default_rng(3)
    e, h, n = 300, 8, 40
    data = jnp.asarray(rng.normal(size=(e, h)).astype(np.float32))
    seg = jnp.asarray(np.sort(rng.integers(0, n, e)).astype(np.int32))
    mask = jnp.asarray(rng.random(e) > 0.2)

    from hydragnn_tpu.ops import segment_sum_family

    def via_custom(d):
        s, sq, c = segment_sum_family(d, seg, n, mask=mask, indices_are_sorted=True)
        return (s * 1.3).sum() + (sq * 0.7).sum() + c.sum()

    def via_autodiff(d):
        m = mask[:, None].astype(jnp.float32)
        dm = d * m
        s = jax.ops.segment_sum(dm, seg, n)
        sq = jax.ops.segment_sum(dm * dm, seg, n)
        c = jax.ops.segment_sum(m[:, 0], seg, n)
        return (s * 1.3).sum() + (sq * 0.7).sum() + c.sum()

    np.testing.assert_allclose(
        float(via_custom(data)), float(via_autodiff(data)), rtol=1e-5
    )
    g_custom = jax.grad(via_custom)(data)
    g_auto = jax.grad(via_autodiff)(data)
    np.testing.assert_allclose(
        np.asarray(g_custom), np.asarray(g_auto), rtol=1e-5, atol=1e-6
    )
    # masked rows receive exactly zero gradient
    assert not np.asarray(g_custom)[~np.asarray(mask)].any()

    # no-mask path
    g2 = jax.grad(lambda d: segment_sum_family(d, seg, n)[1].sum())(data)
    g2_ref = jax.grad(lambda d: jax.ops.segment_sum(d * d, seg, n).sum())(data)
    np.testing.assert_allclose(np.asarray(g2), np.asarray(g2_ref), rtol=1e-5, atol=1e-6)


def pytest_sum_kernel_interpret_matches_xla():
    """The sum-only CSR kernel (VJP hot path) against jax.ops.segment_sum,
    interpret mode, masked + unsorted-input coverage."""
    from hydragnn_tpu.ops.segment_pallas import segment_sum_pallas

    rng = np.random.default_rng(5)
    e, h, n = 700, 128, 150
    data = jnp.asarray(rng.normal(size=(e, h)).astype(np.float32))
    seg_sorted = jnp.asarray(np.sort(rng.integers(0, n, e)).astype(np.int32))
    mask = jnp.asarray(rng.random(e) > 0.3)

    ref = jax.ops.segment_sum(data * mask[:, None], seg_sorted, n)
    out = segment_sum_pallas(
        data, seg_sorted, n, mask=mask, interpret=True, indices_are_sorted=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)

    seg_rand = jnp.asarray(rng.integers(0, n, e).astype(np.int32))
    ref2 = jax.ops.segment_sum(data, seg_rand, n)
    out2 = segment_sum_pallas(data, seg_rand, n, interpret=True)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2), rtol=1e-5, atol=1e-5)


def pytest_gather_rows_grad_matches_plain_gather():
    """gather_rows must be value- and gradient-identical to x[ids]."""
    from hydragnn_tpu.graph.segment import gather_rows

    rng = np.random.default_rng(7)
    n, h, e = 60, 16, 400
    x = jnp.asarray(rng.normal(size=(n, h)).astype(np.float32))
    ids = jnp.asarray(np.sort(rng.integers(0, n, e)).astype(np.int32))
    w = jnp.asarray(rng.normal(size=(e, h)).astype(np.float32))

    np.testing.assert_array_equal(
        np.asarray(gather_rows(x, ids, n, True)), np.asarray(x[ids])
    )
    g_custom = jax.grad(lambda xx: (gather_rows(xx, ids, n, True) * w).sum())(x)
    g_plain = jax.grad(lambda xx: (xx[ids] * w).sum())(x)
    np.testing.assert_allclose(
        np.asarray(g_custom), np.asarray(g_plain), rtol=1e-5, atol=1e-6
    )


def pytest_gather_rows_permuted_grad_matches_plain():
    """gather_rows_permuted (unsorted ids + precomputed argsort) must be
    value- and gradient-identical to x[ids]."""
    from hydragnn_tpu.graph.segment import gather_rows_permuted

    rng = np.random.default_rng(9)
    n, h, e = 60, 16, 400
    x = jnp.asarray(rng.normal(size=(n, h)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, n, e).astype(np.int32))  # unsorted
    perm = jnp.argsort(ids)
    w = jnp.asarray(rng.normal(size=(e, h)).astype(np.float32))

    np.testing.assert_array_equal(
        np.asarray(gather_rows_permuted(x, ids, perm, n)), np.asarray(x[ids])
    )
    g_custom = jax.grad(
        lambda xx: (gather_rows_permuted(xx, ids, perm, n) * w).sum()
    )(x)
    g_plain = jax.grad(lambda xx: (xx[ids] * w).sum())(x)
    np.testing.assert_allclose(
        np.asarray(g_custom), np.asarray(g_plain), rtol=1e-5, atol=1e-6
    )


def pytest_family_pallas_bf16_path():
    """The kernel's bf16 DMA path: bf16 inputs, f32 accumulation — must
    match the XLA family on the same bf16 data (interpret mode), and a
    non-boolean weight mask must not be double-rounded."""
    from hydragnn_tpu.ops.segment_pallas import (
        segment_sum_family_pallas,
        segment_sum_family_xla,
        segment_sum_pallas,
    )

    rng = np.random.default_rng(11)
    e, h, n = 700, 128, 150
    data = jnp.asarray(rng.normal(size=(e, h)).astype(np.float32)).astype(jnp.bfloat16)
    seg = jnp.asarray(np.sort(rng.integers(0, n, e)).astype(np.int32))
    mask = jnp.asarray(rng.random(e) > 0.3)

    s_ref, sq_ref, c_ref = segment_sum_family_xla(data, seg, n, mask=mask)
    s_out, sq_out, c_out = segment_sum_family_pallas(
        data, seg, n, mask=mask, interpret=True, indices_are_sorted=True
    )
    np.testing.assert_allclose(np.asarray(s_out), np.asarray(s_ref), rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(sq_out), np.asarray(sq_ref), rtol=1e-4, atol=1e-3)
    np.testing.assert_array_equal(np.asarray(c_out), np.asarray(c_ref))
    # outputs accumulate f32 even from bf16 inputs
    assert s_out.dtype == jnp.float32 and sq_out.dtype == jnp.float32

    # float weight mask with bf16 data: the kernel promotes to f32 (the
    # weighted products are not bf16-representable; on-chip selfcheck
    # divergence at realistic degrees) — reference is the pure-f32 product
    wmask = jnp.asarray(rng.random(e).astype(np.float32))
    ref = jax.ops.segment_sum(
        data.astype(jnp.float32) * wmask[:, None],
        seg, n,
    )
    out = segment_sum_pallas(
        data, seg, n, mask=wmask, interpret=True, indices_are_sorted=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-3)


def pytest_partitioned_family_edge_sharded_mesh(monkeypatch):
    """The custom_partitioning rule (VERDICT r02 item 2): the family
    kernel over operands GSPMD-sharded on the edge axis must run
    per-shard (local CSR + psum) and match the unsharded reference —
    interpret mode forced via HYDRAGNN_PALLAS=interpret on the 8-device
    CPU mesh."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from hydragnn_tpu.ops import segment_sum_family

    rng = np.random.default_rng(17)
    e, h, n = 1024, 128, 96  # e divisible by 8
    data = jnp.asarray(rng.normal(size=(e, h)).astype(np.float32))
    seg = jnp.asarray(np.sort(rng.integers(0, n, e)).astype(np.int32))
    mask = jnp.asarray(rng.random(e) > 0.25)

    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    sh = NamedSharding(mesh, P("data"))
    data_s = jax.device_put(data, NamedSharding(mesh, P("data", None)))
    seg_s = jax.device_put(seg, sh)
    mask_s = jax.device_put(mask, sh)

    s_ref, sq_ref, c_ref = segment_sum_family_xla(data, seg, n, mask=mask)

    monkeypatch.setenv("HYDRAGNN_PALLAS", "interpret")
    fn = jax.jit(
        lambda d, i, m: segment_sum_family(d, i, n, mask=m, indices_are_sorted=True)
    )
    s, sq, c = fn(data_s, seg_s, mask_s)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(sq), np.asarray(sq_ref), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(c), np.asarray(c_ref), rtol=1e-6)
    # gradients flow through the partitioned op's custom VJP too
    g = jax.grad(
        lambda d: sum(
            x.sum()
            for x in jax.jit(
                lambda dd: segment_sum_family(dd, seg_s, n, mask=mask_s, indices_are_sorted=True)
            )(d)[:2]
        )
    )(data_s)
    assert np.isfinite(np.asarray(g)).all()


def pytest_partitioned_family_inside_shard_map(monkeypatch):
    """Inside shard_map (the DP train step) operands are already local;
    the partitioned op must lower to the plain kernel per device."""
    from jax.sharding import Mesh, PartitionSpec as P

    from hydragnn_tpu.ops import segment_sum_family

    rng = np.random.default_rng(19)
    d_dev, e, h, n = 8, 256, 128, 40
    data = rng.normal(size=(d_dev, e, h)).astype(np.float32)
    seg = np.sort(rng.integers(0, n, (d_dev, e)), axis=1).astype(np.int32)

    mesh = Mesh(np.array(jax.devices()[:d_dev]), ("data",))

    monkeypatch.setenv("HYDRAGNN_PALLAS", "interpret")

    def local(d, i):
        s, sq, c = segment_sum_family(d[0], i[0], n, indices_are_sorted=True)
        return s[None]

    # check_vma=False matches every in-tree shard_map (sharded.py,
    # edge_sharded.py); interpret-mode pallas does not propagate vma
    fn = jax.jit(
        shard_map(
            local, mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=P("data"), check_vma=False,
        )
    )
    out = fn(jnp.asarray(data), jnp.asarray(seg))
    for i in range(d_dev):
        ref = jax.ops.segment_sum(jnp.asarray(data[i]), jnp.asarray(seg[i]), n)
        np.testing.assert_allclose(
            np.asarray(out[i]), np.asarray(ref), rtol=1e-4, atol=1e-4
        )


def pytest_xla_segment_ops_context_forces_fallback(monkeypatch):
    """xla_segment_ops() must force the XLA path at trace time — the
    programmatic gate for vmap contexts where custom_partitioning has no
    batching rule (ADVICE r02 medium)."""
    from hydragnn_tpu.ops import segment_sum_family
    from hydragnn_tpu.ops.segment_pallas import _use_pallas, xla_segment_ops

    rng = np.random.default_rng(23)
    b, e, h, n = 3, 200, 128, 30
    data = jnp.asarray(rng.normal(size=(b, e, h)).astype(np.float32))
    seg = jnp.asarray(np.sort(rng.integers(0, n, (b, e)), axis=1).astype(np.int32))

    monkeypatch.setenv("HYDRAGNN_PALLAS", "interpret")  # would pick the kernel...
    assert _use_pallas(data[0], True)
    with xla_segment_ops():
        assert not _use_pallas(data[0], True)  # ...but the context wins
        # vmap over the family op traces cleanly on the XLA path
        out = jax.vmap(
            lambda d, i: segment_sum_family(d, i, n, indices_are_sorted=True)[0]
        )(data, seg)
    for i in range(b):
        ref = jax.ops.segment_sum(data[i], seg[i], n)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref), rtol=1e-5, atol=1e-5)


def pytest_family_float_weight_mask_gradient():
    """ADVICE r02: differentiating segment_sum_family with a FLOAT weight
    mask must (a) not raise, and (b) apply the weighted closed form
    (m*g_sum + 2*m^2*d*g_sumsq) — checked against autodiff of the
    mathematical definition. The mask itself is non-differentiable
    (stop_gradient contract)."""
    from hydragnn_tpu.ops import segment_sum_family

    rng = np.random.default_rng(29)
    e, h, n = 300, 8, 40
    data = jnp.asarray(rng.normal(size=(e, h)).astype(np.float32))
    seg = jnp.asarray(np.sort(rng.integers(0, n, e)).astype(np.int32))
    wmask = jnp.asarray(rng.random(e).astype(np.float32))

    def via_custom(d):
        s, sq, c = segment_sum_family(d, seg, n, mask=wmask, indices_are_sorted=True)
        return (s * 1.3).sum() + (sq * 0.7).sum()

    def via_autodiff(d):
        m = wmask[:, None]
        dm = d * m
        s = jax.ops.segment_sum(dm, seg, n)
        sq = jax.ops.segment_sum(dm * dm, seg, n)
        return (s * 1.3).sum() + (sq * 0.7).sum()

    np.testing.assert_allclose(float(via_custom(data)), float(via_autodiff(data)), rtol=1e-5)
    g_custom = jax.grad(via_custom)(data)
    g_auto = jax.grad(via_autodiff)(data)
    np.testing.assert_allclose(np.asarray(g_custom), np.asarray(g_auto), rtol=1e-4, atol=1e-5)

    # mask arg gets a zero cotangent, not an error
    g_mask = jax.grad(
        lambda m: segment_sum_family(data, seg, n, mask=m, indices_are_sorted=True)[0].sum()
    )(wmask)
    assert not np.asarray(g_mask).any()


def pytest_pallas_knob_1_requires_tpu_backend(monkeypatch):
    """ADVICE r02: HYDRAGNN_PALLAS=1 on a non-TPU backend must fall back
    to XLA instead of crashing at Mosaic lowering."""
    from hydragnn_tpu.ops.segment_pallas import _use_pallas

    data = jnp.zeros((16, 128), jnp.float32)
    monkeypatch.setenv("HYDRAGNN_PALLAS", "1")
    assert jax.default_backend() == "cpu"
    assert not _use_pallas(data, True)  # CPU: knob 1 falls back


def pytest_bcast_gather_matches_indexing():
    """CSR-broadcast row gather (sorted ids): kernel output must be
    bit-exact against plain indexing across chunk boundaries, window
    clamping near the table end, low- and high-degree id patterns, f32
    and bf16 tables."""
    from hydragnn_tpu.ops.segment_pallas import _bcast_kernel_call

    rng = np.random.default_rng(23)
    cases = [
        (700, 100, 128, "f32"),      # single-chunk tail
        (3000, 40, 128, "f32"),      # high degree, few rows (clamped windows)
        (2048, 2000, 128, "f32"),    # low degree ~1: chunk spans ~CE rows
        (1537, 77, 256, "bf16"),     # multi-chunk + ragged tail + wide H
    ]
    for e, n, h, dt in cases:
        ids = jnp.asarray(np.sort(rng.integers(0, n, e)).astype(np.int32))
        table = jnp.asarray(rng.normal(size=(n, h)).astype(np.float32))
        if dt == "bf16":
            table = table.astype(jnp.bfloat16)
        out = _bcast_kernel_call(table, ids, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(table[ids]))


def pytest_bcast_gather_in_vjps_interpret(monkeypatch):
    """The family and extremum backward passes route their widening
    gathers through the CSR-broadcast kernel when ids are sorted: grads
    under HYDRAGNN_PALLAS=interpret must match HYDRAGNN_PALLAS=0."""
    from hydragnn_tpu.graph import segment as S
    from hydragnn_tpu.ops import segment_sum_family

    rng = np.random.default_rng(29)
    e, h, n = 900, 128, 120
    data = jnp.asarray(rng.normal(size=(e, h)).astype(np.float32))
    seg = jnp.asarray(np.sort(rng.integers(0, n, e)).astype(np.int32))
    mask = jnp.asarray(rng.random(e) > 0.2)

    def loss(d):
        s, sq, c = segment_sum_family(d, seg, n, mask=mask, indices_are_sorted=True)
        mx = S.segment_max(d, seg, n, mask=mask, indices_are_sorted=True)
        mn = S.segment_min(d, seg, n, mask=mask, indices_are_sorted=True)
        xr = S.gather_rows(jnp.tanh(s), seg, n, True)
        return (s * s).sum() + sq.sum() + (mx * mn).sum() + xr.sum()

    monkeypatch.setenv("HYDRAGNN_PALLAS", "0")
    g_xla = jax.jit(jax.grad(loss))(data)
    monkeypatch.setenv("HYDRAGNN_PALLAS", "interpret")
    g_k = jax.jit(jax.grad(loss))(data)
    np.testing.assert_allclose(
        np.asarray(g_k), np.asarray(g_xla), rtol=1e-5, atol=1e-5
    )


def pytest_bcast_gather_edge_sharded_mesh(monkeypatch):
    """The CSR-broadcast op's custom_partitioning rule: edge-sharded ids
    on the 8-device CPU mesh gather per-shard from a replicated table
    and match plain indexing."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from hydragnn_tpu.ops.segment_pallas import gather_rows_sorted_fast

    rng = np.random.default_rng(31)
    e, h, n = 1024, 128, 96
    ids = jnp.asarray(np.sort(rng.integers(0, n, e)).astype(np.int32))
    table = jnp.asarray(rng.normal(size=(n, h)).astype(np.float32))

    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    ids_s = jax.device_put(ids, NamedSharding(mesh, P("data")))
    table_s = jax.device_put(table, NamedSharding(mesh, P()))

    monkeypatch.setenv("HYDRAGNN_PALLAS", "interpret")
    out = jax.jit(gather_rows_sorted_fast)(table_s, ids_s)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(table[ids]))


def _pna_reference(v, recv, n, mask):
    """Composed reference for pna_aggregate from the plain building
    blocks (the pre-fusion formulation)."""
    from hydragnn_tpu.graph import segment as S
    from hydragnn_tpu.ops import segment_sum_family

    s, sq, cnt = segment_sum_family(v, recv, n, mask=mask, indices_are_sorted=True)
    mx = S.segment_max(v, recv, n, mask=mask, indices_are_sorted=True)
    mn = S.segment_min(v, recv, n, mask=mask, indices_are_sorted=True)
    return s, sq, cnt, mx, mn


def pytest_pna_aggregate_matches_composed(monkeypatch):
    """pna_aggregate forward AND gradient must match the composed
    segment ops — f32/bf16, with/without mask, deliberate ties, both
    the unfused (HYDRAGNN_PALLAS=0) and kernel (interpret) backwards."""
    rng = np.random.default_rng(37)
    e, h, n = 1200, 128, 90
    recv = jnp.asarray(np.sort(rng.integers(0, n, e)).astype(np.int32))
    base = rng.normal(size=(e, h)).astype(np.float32)
    # deliberate ties: quantize so segments share extrema
    base = np.round(base * 4) / 4
    mask_b = jnp.asarray(rng.random(e) > 0.2)

    from hydragnn_tpu.ops import pna_aggregate

    for dtype in (jnp.float32, jnp.bfloat16):
        v0 = jnp.asarray(base).astype(dtype)
        for mask in (None, mask_b):
            def loss_f(v, agg):
                s, sq, cnt, both = agg(v)
                mx, mn = both[:, :h], -both[:, h:]
                return (
                    (s * s).sum() + sq.sum()
                    + (mx.astype(jnp.float32) * 2.0).sum()
                    + (mn.astype(jnp.float32) * 3.0).sum()
                )

            def agg_fused(v, _mask=mask):
                return pna_aggregate(v, recv, n, mask=_mask, indices_are_sorted=True)

            def agg_ref(v, _mask=mask):
                s, sq, cnt, mx, mn = _pna_reference(v, recv, n, _mask)
                return s, sq, cnt, jnp.concatenate([mx, -mn], axis=-1)

            for knob in ("0", "interpret"):
                monkeypatch.setenv("HYDRAGNN_PALLAS", knob)
                out_f = jax.jit(lambda v: agg_fused(v))(v0)
                monkeypatch.setenv("HYDRAGNN_PALLAS", "0")
                out_r = jax.jit(lambda v: agg_ref(v))(v0)
                np.testing.assert_allclose(
                    np.asarray(out_f[2]), np.asarray(out_r[2]), rtol=1e-6,
                    err_msg=f"cnt {dtype} mask={mask is not None} {knob}",
                )
                for a, b, name in zip(out_f[:2], out_r[:2], ("sum", "sumsq")):
                    np.testing.assert_allclose(
                        np.asarray(a), np.asarray(b), rtol=2e-2, atol=2e-2,
                        err_msg=f"{name} {dtype} mask={mask is not None} {knob}",
                    )
                np.testing.assert_array_equal(
                    np.asarray(out_f[3]), np.asarray(out_r[3]),
                    err_msg=f"both {dtype} mask={mask is not None} {knob}",
                )

                monkeypatch.setenv("HYDRAGNN_PALLAS", knob)
                g_f = jax.jit(jax.grad(lambda v: loss_f(v, agg_fused)))(v0)
                monkeypatch.setenv("HYDRAGNN_PALLAS", "0")
                g_r = jax.jit(jax.grad(lambda v: loss_f(v, agg_ref)))(v0)
                np.testing.assert_allclose(
                    np.asarray(g_f, np.float32), np.asarray(g_r, np.float32),
                    rtol=2e-2, atol=2e-2,
                    err_msg=f"grad {dtype} mask={mask is not None} {knob}",
                )


def pytest_pna_aggregate_narrow_width_lane_pads(monkeypatch):
    """conv_0-shaped narrow widths must lane-pad through the fused op
    (kernel backward in interpret mode) and match the unfused path."""
    rng = np.random.default_rng(41)
    e, h, n = 900, 24, 70
    recv = jnp.asarray(np.sort(rng.integers(0, n, e)).astype(np.int32))
    v0 = jnp.asarray(np.round(rng.normal(size=(e, h)) * 4) / 4, dtype=jnp.float32)
    mask = jnp.asarray(rng.random(e) > 0.25)

    from hydragnn_tpu.ops import pna_aggregate

    def loss(v):
        s, sq, cnt, both = pna_aggregate(v, recv, n, mask=mask, indices_are_sorted=True)
        return (s * s).sum() + sq.sum() + both.sum() * 2.0 + cnt.sum()

    monkeypatch.setenv("HYDRAGNN_PALLAS", "0")
    ref_out = jax.jit(lambda v: pna_aggregate(v, recv, n, mask=mask, indices_are_sorted=True))(v0)
    ref_g = jax.jit(jax.grad(loss))(v0)
    monkeypatch.setenv("HYDRAGNN_PALLAS", "interpret")
    k_out = jax.jit(lambda v: pna_aggregate(v, recv, n, mask=mask, indices_are_sorted=True))(v0)
    k_g = jax.jit(jax.grad(loss))(v0)
    for a, b in zip(k_out, ref_out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(k_g), np.asarray(ref_g), rtol=1e-5, atol=1e-5)


def pytest_pna_aggregate_grad_inside_shard_map(monkeypatch):
    """pna_aggregate's fused backward must trace and match the XLA path
    under jax.shard_map (the DP train-step context). check_vma=False
    like every in-tree shard_map: interpret-mode pallas' internal grid
    indexing is not vma-aware (hlo_interpreter dynamic_slice), so
    check_vma=True only works with the compiled Mosaic kernels on a
    real TPU — where the K1/K2 out_shapes now declare their vma and
    operands are pvary-promoted like the sibling kernels."""
    from jax.sharding import Mesh, PartitionSpec as P

    from hydragnn_tpu.ops import pna_aggregate

    rng = np.random.default_rng(43)
    d_dev, e, h, n = 8, 512, 128, 40
    data = np.round(rng.normal(size=(d_dev, e, h)) * 4).astype(np.float32) / 4
    seg = np.sort(rng.integers(0, n, (d_dev, e)), axis=1).astype(np.int32)

    mesh = Mesh(np.array(jax.devices()[:d_dev]), ("data",))
    monkeypatch.setenv("HYDRAGNN_PALLAS", "interpret")

    def local_loss(d, i):
        s, sq, cnt, both = pna_aggregate(d[0], i[0], n, indices_are_sorted=True)
        return ((s * s).sum() + sq.sum() + both.sum())[None]

    def loss(d, i):
        per = shard_map(
            local_loss, mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=P("data"), check_vma=False,
        )(d, i)
        return per.sum()

    g = jax.jit(jax.grad(loss))(jnp.asarray(data), jnp.asarray(seg))

    monkeypatch.setenv("HYDRAGNN_PALLAS", "0")
    g_ref = jax.jit(jax.grad(loss))(jnp.asarray(data), jnp.asarray(seg))
    np.testing.assert_allclose(
        np.asarray(g), np.asarray(g_ref), rtol=1e-5, atol=1e-5
    )


def pytest_gather_presum_stats_matches_reference(monkeypatch):
    """Fused gather + K-group pre-reduction (r05): forward equals the
    unfused composition over a materialized gather, and the custom VJP
    (regather + differentiate the composition) matches plain AD of that
    composition — values AND grads, with deliberate mask structure."""
    from hydragnn_tpu.graph.batch import _block_windows
    from hydragnn_tpu.ops.segment_pallas import (
        _presum_stats_ref,
        gather_presum_eligible,
        gather_presum_stats,
    )

    monkeypatch.setenv("HYDRAGNN_PALLAS", "interpret")
    monkeypatch.setenv("HYDRAGNN_LOCAL_MIN_ROWS", "0")

    rng = np.random.default_rng(17)
    e, n_rows, h, K = 2048, 512, 128, 8
    # unsorted-but-local senders: confined to 64-node blocks like
    # batched-graph senders; round values so f32/bf16 compares tie
    table = np.round(rng.normal(size=(n_rows, h)) * 4).astype(np.float32) / 4
    grp = np.sort(rng.integers(0, 32, e))
    send = (grp * 16 + rng.integers(0, 16, e)).astype(np.int32)
    mask = rng.random(e) > 0.25
    # whole K-groups masked too (empty-group fill path)
    mask[64:72] = False
    perm = np.argsort(send, kind="stable").astype(np.int32)
    win = jnp.asarray(_block_windows(send, perm, n_rows))

    assert gather_presum_eligible(jnp.asarray(table), jnp.asarray(send), win, K)
    # indivisible chunk/K combos must FALL BACK, not crash at trace time
    assert not gather_presum_eligible(jnp.asarray(table), jnp.asarray(send), win, 3)

    def fused_loss(t):
        stats, both = gather_presum_stats(
            t, jnp.asarray(send), jnp.asarray(mask), win, n_rows, K
        )
        return (stats * stats).sum() + both.astype(jnp.float32).sum()

    def ref_loss(t):
        v = t[jnp.asarray(send)]
        stats, both = _presum_stats_ref(v, jnp.asarray(mask), K)
        return (stats * stats).sum() + both.astype(jnp.float32).sum()

    t = jnp.asarray(table)
    np.testing.assert_allclose(
        float(fused_loss(t)), float(ref_loss(t)), rtol=1e-5
    )
    g_fused = jax.jit(jax.grad(fused_loss))(t)
    g_ref = jax.jit(jax.grad(ref_loss))(t)
    np.testing.assert_allclose(
        np.asarray(g_fused), np.asarray(g_ref), rtol=1e-5, atol=1e-5
    )

    # bf16 table: forward values must agree with the bf16 composition
    tb = t.astype(jnp.bfloat16)
    s_f, b_f = gather_presum_stats(
        tb, jnp.asarray(send), jnp.asarray(mask), win, n_rows, K
    )
    s_r, b_r = _presum_stats_ref(tb[jnp.asarray(send)], jnp.asarray(mask), K)
    np.testing.assert_allclose(
        np.asarray(s_f), np.asarray(s_r), rtol=1e-6, atol=1e-6
    )
    np.testing.assert_array_equal(
        np.asarray(b_f.astype(jnp.float32)), np.asarray(b_r.astype(jnp.float32))
    )
