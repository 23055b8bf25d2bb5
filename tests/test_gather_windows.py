"""The windowed one-hot gathers at the table-window width BW
(ops/segment_pallas.py), in Pallas interpret mode: bit for bit against
plain indexing or the unfused composition, and against the same kernel
at the old width CE + ALIGN = 528, over chunks whose ids span one, two
and six windows (ISSUE 32). And the manifest's host count of windows a
chunk (``window_counts``) against the device plan on a loader batch."""

import importlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

sp = importlib.import_module("hydragnn_tpu.ops.segment_pallas")
fc = importlib.import_module("hydragnn_tpu.ops.fused_conv")

# rows a chunk's ids are drawn from -> the windows that chunk needs at BW 128
SPANS = [pytest.param(100, 1, id="1-window"), pytest.param(200, 2, id="2-windows"),
         pytest.param(700, 6, id="6-windows")]
DTYPES = [pytest.param(jnp.float32, id="f32"), pytest.param(jnp.bfloat16, id="bf16")]
OLD_BW = 528


@pytest.fixture
def width(monkeypatch):
    """Call ``width(w)`` to run what follows at window width ``w``; the
    module's own width comes back after the test."""

    def set_width(w):
        monkeypatch.setattr(sp, "BW", w)
        monkeypatch.setattr(fc, "BW", w)
        jax.clear_caches()  # nothing traced at another width is reused

    yield set_width
    jax.clear_caches()


def _ids(span, chunk, n_chunks, sort, seed=0):
    """Chunk c's ids uniform over rows [c * span, (c + 1) * span): every
    chunk spans ``span`` rows, unsorted-but-local like batched senders."""
    rng = np.random.default_rng(seed)
    ids = np.concatenate([rng.integers(c * span, (c + 1) * span, chunk) for c in range(n_chunks)])
    return np.sort(ids).astype(np.int32) if sort else ids.astype(np.int32)


def _table(n, dt, seed=1):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(n, 128)).astype(np.float32)).astype(dt)


@pytest.mark.parametrize("dt", DTYPES)
@pytest.mark.parametrize("span,windows", SPANS)
@pytest.mark.parametrize("sorted_ids", [True, False], ids=["sorted", "local"])
def test_bcast_gather_exact_at_any_span(dt, span, windows, sorted_ids):
    ce = sp._BCAST_CE
    ids = _ids(span, ce, 2, sorted_ids)
    n = 2 * span + 37
    assert sp.BW == 128 and sp.window_counts(ids, n).max() == windows
    ids = ids[: 2 * ce - 300]  # a ragged tail chunk too
    table = _table(n, dt)
    out = sp._bcast_kernel_call(table, jnp.asarray(ids), interpret=True, sorted_ids=sorted_ids)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(table[ids]))


@pytest.mark.parametrize("dt", DTYPES)
@pytest.mark.parametrize("span,windows", SPANS)
def test_gather_stats_equals_unfused_composition(dt, span, windows):
    ce, k = sp._BCAST_CE, 8
    ids = _ids(span, ce, 2, sort=False)
    n = 2 * span + 37
    assert sp.window_counts(ids, n).max() == windows
    # quarters: every partial sum of a K-group is exact, in any order
    table = jnp.round(_table(n, jnp.float32) * 4).astype(dt) / 4
    mask = np.random.default_rng(2).random(ids.shape[0]) > 0.25
    mask[64:72] = False  # a whole K-group masked: the fill path
    stats, both = sp._gather_stats_call(table, jnp.asarray(ids), jnp.asarray(mask), k, interpret=True)
    ref_stats, ref_both = sp._presum_stats_ref(table[ids], jnp.asarray(mask), k)
    np.testing.assert_array_equal(np.asarray(stats), np.asarray(ref_stats))
    np.testing.assert_array_equal(np.asarray(both.astype(jnp.float32)), np.asarray(ref_both.astype(jnp.float32)))


def _fused_case(span, dt):
    """Senders whose CE-edge chunks each span ``span`` rows; receivers
    sorted over the same nodes; a per-edge SchNet-like scale."""
    rng = np.random.default_rng(3)
    e = 3 * sp.CE
    send = _ids(span, sp.CE, 3, sort=False)
    n = 3 * span + 29
    recv = np.sort(rng.integers(0, n - 10, e)).astype(np.int32)
    mask = rng.random(e) > 0.2
    scale = jnp.asarray(rng.normal(size=(e, 128)).astype(np.float32)).astype(dt)
    return _table(n, dt), jnp.asarray(send), jnp.asarray(recv), jnp.asarray(mask), scale, n


@pytest.mark.parametrize("dt", DTYPES)
@pytest.mark.parametrize("span,windows", SPANS)
def test_fused_conv_scale_forward_and_vjp_do_not_depend_on_width(monkeypatch, width, dt, span, windows):
    monkeypatch.setenv("HYDRAGNN_PALLAS", "interpret")
    x, send, recv, mask, scale, n = _fused_case(span, dt)
    assert sp.window_counts(send, n, ce=sp.CE).max() == windows

    def run():
        out, pull = jax.vjp(lambda x, s: fc.fused_conv(x, send, recv, mask, n, scale=s), x, scale)
        return [np.asarray(a) for a in (out, *pull(jnp.ones_like(out)))]

    new = run()
    width(OLD_BW)
    old = run()
    for a, b in zip(new, old):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("span,windows", SPANS)
def test_resident_stack_does_not_depend_on_width(width, span, windows):
    x, send, recv, mask, _, n = _fused_case(span, jnp.float32)
    assert sp.window_counts(send, n, ce=sp.CE).max() == windows
    rng = np.random.default_rng(4)
    w = jnp.asarray(rng.normal(size=(2, 128, 128)).astype(np.float32) / 11)
    b = jnp.asarray(rng.normal(size=(2, 1, 128)).astype(np.float32))

    def run():
        return np.asarray(fc._stack_kernel_call(x, send, recv, mask, w, b, None, n, ("none", "relu", 2), True))

    new = run()
    width(OLD_BW)
    np.testing.assert_array_equal(new, run())


@pytest.fixture(scope="module")
def loader():
    from hydragnn_tpu.data.ingest import prepare_dataset
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.data.synthetic import deterministic_graph_data
    from hydragnn_tpu.flagship import flagship_config

    cfg = flagship_config(hidden_dim=8, num_conv_layers=2, batch_size=12)
    samples = deterministic_graph_data(number_configurations=36, unit_cell_x_range=(2, 3),
                                       unit_cell_y_range=(2, 3), unit_cell_z_range=(2, 3), seed=0)
    train, _, _, _, _ = prepare_dataset(samples, cfg)
    return GraphLoader(train, 12, shuffle=False, run_align=8, dense_slots=False)


@pytest.mark.parametrize("ce", [1024, 512, 128])
def test_host_window_counts_equal_the_device_plan(loader, ce):
    loader.stacked_device_batches(0)
    senders = loader.built_senders()
    host = sp.window_counts(senders, loader.pad_nodes, ce=ce)
    n_pad = max(-(-loader.pad_nodes // sp.ALIGN) * sp.ALIGN, sp.BW)
    device = []
    for ids in senders.reshape(-1, senders.shape[-1]):
        e_pad = -(-ids.shape[0] // ce) * ce
        recv = jnp.concatenate([jnp.asarray(ids), jnp.full((e_pad - ids.shape[0],), n_pad, jnp.int32)])
        device.append(np.asarray(sp._window_plan_local(recv, n_pad, e_pad // ce, ce=ce)[1]))
    np.testing.assert_array_equal(host, np.concatenate(device))
    assert host.max() > 1  # small graphs, few nodes a batch: some chunks need several windows


def test_manifest_gather_windows_reads_the_built_batches(loader):
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.train.run import _loader_plan

    loader.stacked_device_batches(0)
    plan = _loader_plan(loader)["gather_windows"]
    counts = sp.window_counts(loader.built_senders(), loader.pad_nodes)
    assert plan == {"width": sp.BW, "chunk": sp._BCAST_CE, "mean": float(counts.mean()), "max": int(counts.max())}
    streaming = GraphLoader(loader.samples, 12, shuffle=False, run_align=8, dense_slots=False)
    assert _loader_plan(streaming)["gather_windows"] is None  # it keeps no batches
