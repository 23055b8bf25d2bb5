"""Fused conv kernel (ops/fused_conv.py): forward + VJP must match the
XLA reference composition in Pallas interpret mode on CPU — the tier-1
pin for the TPU kernel path — across masked/padded segments, both
edge-feature modes (receiver-table only vs receiver-table + per-edge
edge term), bf16/f32, and the model-level wiring."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from hydragnn_tpu.ops.fused_conv import _fused_ref, fused_conv


@pytest.fixture
def case():
    rng = np.random.default_rng(11)
    e, n, h = 900, 120, 128
    # sorted receivers with empty segments at the tail (padding nodes)
    recv = np.sort(rng.integers(0, n - 15, e)).astype(np.int32)
    send = rng.integers(0, n, e).astype(np.int32)
    mask = rng.random(e) > 0.2
    x = rng.normal(size=(n, h)).astype(np.float32)
    return (
        jnp.asarray(x),
        jnp.asarray(send),
        jnp.asarray(recv),
        jnp.asarray(mask),
        n,
    )


def _np_identity_reference(x, send, recv, mask, n):
    out = np.zeros((n, x.shape[1]), np.float64)
    xs = np.asarray(x, np.float64)
    for e in range(len(send)):
        if mask[e]:
            out[recv[e]] += xs[send[e]]
    return out


def pytest_identity_matches_numpy(case, monkeypatch):
    monkeypatch.setenv("HYDRAGNN_PALLAS", "interpret")
    x, send, recv, mask, n = case
    out = fused_conv(x, send, recv, mask, n)
    ref = _np_identity_reference(
        np.asarray(x), np.asarray(send), np.asarray(recv), np.asarray(mask), n
    )
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def pytest_identity_and_scale_match_ref(case, monkeypatch, dtype):
    monkeypatch.setenv("HYDRAGNN_PALLAS", "interpret")
    x, send, recv, mask, n = case
    x = x.astype(dtype)
    rng = np.random.default_rng(1)
    scale = jnp.asarray(
        rng.normal(size=(send.shape[0], x.shape[1])).astype(np.float32)
    ).astype(dtype)
    out = fused_conv(x, send, recv, mask, n, scale=scale)
    ref = _fused_ref((0, ()), n, x, send, recv, mask, (), scale)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    scale_ref = float(jnp.abs(ref).max()) or 1.0
    assert float(jnp.abs(out - ref).max()) / scale_ref < tol


@pytest.mark.parametrize("with_eterm", [False, True])
def pytest_glu_both_edge_feature_modes(case, monkeypatch, with_eterm):
    """The CGCNN gate shape: two branches, sigmoid*softplus, receiver
    tables, with and without the additive per-edge term (edge features)."""
    monkeypatch.setenv("HYDRAGNN_PALLAS", "interpret")
    x, send, recv, mask, n = case
    h = x.shape[1]
    rng = np.random.default_rng(2)

    def arr(*shape, s=0.1):
        return jnp.asarray((rng.normal(size=shape) * s).astype(np.float32))

    e = send.shape[0]
    et1 = arr(e, h) if with_eterm else None
    et2 = arr(e, h) if with_eterm else None
    branches = (
        (arr(h, h), None, arr(n, h), et1),
        (arr(h, h), None, arr(n, h), et2),
    )
    acts = ("sigmoid", "softplus")
    out = fused_conv(x, send, recv, mask, n, branches=branches, acts=acts)
    ref = _fused_ref((2, acts), n, x, send, recv, mask, branches, None)
    scale_ref = float(jnp.abs(ref).max()) or 1.0
    assert float(jnp.abs(out - ref).max()) / scale_ref < 1e-4


def pytest_mlp_vjp_matches_reference_ad(case, monkeypatch):
    """grads wrt x, W, b, rtab, scale: the hand-written backward vs
    plain AD of the reference composition."""
    monkeypatch.setenv("HYDRAGNN_PALLAS", "interpret")
    x, send, recv, mask, n = case
    h = x.shape[1]
    rng = np.random.default_rng(3)
    W = jnp.asarray((rng.normal(size=(h, h)) * 0.1).astype(np.float32))
    b = jnp.asarray((rng.normal(size=(h,)) * 0.1).astype(np.float32))
    rt = jnp.asarray((rng.normal(size=(n, h)) * 0.1).astype(np.float32))
    sc = jnp.asarray((rng.normal(size=(send.shape[0], h))).astype(np.float32))

    def loss_fused(x, W, b, rt, sc):
        o = fused_conv(
            x, send, recv, mask, n,
            branches=((W, b, rt, None),), acts=("sigmoid",), scale=sc,
        )
        return (o * o).sum()

    def loss_ref(x, W, b, rt, sc):
        o = _fused_ref(
            (1, ("sigmoid",)), n, x, send, recv, mask, ((W, b, rt, None),), sc
        )
        return (o * o).sum()

    g1 = jax.grad(loss_fused, argnums=(0, 1, 2, 3, 4))(x, W, b, rt, sc)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2, 3, 4))(x, W, b, rt, sc)
    for a, bb, name in zip(g1, g2, ("x", "W", "b", "rtab", "scale")):
        denom = float(jnp.abs(bb).max()) or 1.0
        rel = float(jnp.abs(a - bb).max()) / denom
        assert rel < 1e-4, f"grad {name} rel err {rel}"


def pytest_identity_vjp_and_narrow_width(monkeypatch):
    """Narrow (non-128) widths lane-pad into the kernel; identity-mode
    VJP (the GIN/SAGE/MFC aggregation backward) matches AD."""
    monkeypatch.setenv("HYDRAGNN_PALLAS", "interpret")
    rng = np.random.default_rng(4)
    e, n, h = 520, 70, 40
    recv = jnp.asarray(np.sort(rng.integers(0, n, e)).astype(np.int32))
    send = jnp.asarray(rng.integers(0, n, e).astype(np.int32))
    mask = jnp.asarray(rng.random(e) > 0.25)
    x = jnp.asarray(rng.normal(size=(n, h)).astype(np.float32))
    out = fused_conv(x, send, recv, mask, n)
    assert out.shape == (n, h)
    ref = _fused_ref((0, ()), n, x, send, recv, mask, (), None)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)
    g1 = jax.grad(lambda x: (fused_conv(x, send, recv, mask, n) ** 2).sum())(x)
    g2 = jax.grad(
        lambda x: (_fused_ref((0, ()), n, x, send, recv, mask, (), None) ** 2).sum()
    )(x)
    np.testing.assert_allclose(g1, g2, rtol=1e-3, atol=1e-3)


def pytest_all_masked_is_zero(case, monkeypatch):
    """With every edge masked, even a biased+activated edge network must
    contribute exactly nothing (act(b) != 0 — the mask gates it)."""
    monkeypatch.setenv("HYDRAGNN_PALLAS", "interpret")
    x, send, recv, _, n = case
    h = x.shape[1]
    rng = np.random.default_rng(5)
    W = jnp.asarray((rng.normal(size=(h, h)) * 0.1).astype(np.float32))
    b = jnp.asarray(np.ones((h,), np.float32))
    out = fused_conv(
        x, send, recv, jnp.zeros(send.shape[0], bool), n,
        branches=((W, b, None, None),), acts=("softplus",),
    )
    assert float(jnp.abs(out).max()) == 0.0


def pytest_xla_fallback_is_differentiable(case):
    """Knob=0 (no kernel anywhere) must route through the same custom
    VJP and produce matching grads — the CPU production path."""
    import os

    os.environ["HYDRAGNN_PALLAS"] = "0"
    try:
        x, send, recv, mask, n = case
        out = fused_conv(x, send, recv, mask, n)
        ref = _fused_ref((0, ()), n, x, send, recv, mask, (), None)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
        g = jax.grad(lambda x: (fused_conv(x, send, recv, mask, n) ** 2).sum())(x)
        assert np.isfinite(np.asarray(g)).all()
    finally:
        os.environ.pop("HYDRAGNN_PALLAS", None)


def pytest_model_level_fused_matches_unfused(monkeypatch):
    """GIN / CGCNN / SchNet forward + grads: Architecture.fused_conv
    through the real chassis (interpret kernel) vs the composed legacy
    path — same params, same batch."""
    from hydragnn_tpu.data.ingest import prepare_dataset
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.data.synthetic import deterministic_graph_data
    from hydragnn_tpu.flagship import flagship_config
    from hydragnn_tpu.models.base import model_loss
    from hydragnn_tpu.models.create import create_model_config
    from hydragnn_tpu.utils.config import update_config

    for model_type in ("GIN", "CGCNN", "SchNet"):
        cfg = flagship_config(hidden_dim=8, num_conv_layers=2, batch_size=4)
        arch = cfg["NeuralNetwork"]["Architecture"]
        arch["model_type"] = model_type
        if model_type == "SchNet":
            arch["num_gaussians"] = 8
            arch["num_filters"] = 8
        samples = deterministic_graph_data(
            number_configurations=8,
            unit_cell_x_range=(2, 3),
            unit_cell_y_range=(2, 3),
            unit_cell_z_range=(2, 3),
            seed=0,
        )
        train, val, test, _, _ = prepare_dataset(samples, cfg)
        cfg = update_config(cfg, train, val, test)
        loader = GraphLoader(train, 4, shuffle=False)
        batch = next(iter(loader))
        model, variables = create_model_config(cfg["NeuralNetwork"], batch)

        def loss(params):
            outs = model.apply(
                {"params": params, "batch_stats": variables.get("batch_stats", {})},
                batch,
                train=False,
            )
            total, _ = model_loss(model.cfg, outs, batch)
            return total

        monkeypatch.setenv("HYDRAGNN_PALLAS", "0")
        l0, g0 = jax.value_and_grad(loss)(variables["params"])
        monkeypatch.setenv("HYDRAGNN_PALLAS", "interpret")
        l1, g1 = jax.value_and_grad(loss)(variables["params"])
        assert abs(float(l1) - float(l0)) <= 1e-4 * max(abs(float(l0)), 1.0), model_type
        gmax = max(
            float(jnp.abs(a).max()) for a in jax.tree_util.tree_leaves(g0)
        )
        gerr = max(
            float(jnp.abs(a - b).max())
            for a, b in zip(
                jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)
            )
        )
        assert gerr / max(gmax, 1e-9) < 1e-4, model_type


def pytest_partitioned_fused_edge_sharded_mesh(monkeypatch):
    """The custom_partitioning rule: operands GSPMD-sharded on the edge
    axis run the kernel per shard (contiguous receiver-sorted slices) +
    one psum, matching the unsharded reference — interpret mode on the
    virtual 8-device CPU mesh."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    if len(jax.devices()) < 8:
        pytest.skip("needs the virtual 8-device mesh")
    rng = np.random.default_rng(21)
    e, h, n = 1024, 128, 96  # e divisible by 8
    x = jnp.asarray(rng.normal(size=(n, h)).astype(np.float32))
    recv = jnp.asarray(np.sort(rng.integers(0, n, e)).astype(np.int32))
    send = jnp.asarray(rng.integers(0, n, e).astype(np.int32))
    mask = jnp.asarray(rng.random(e) > 0.25)
    ref = _fused_ref((0, ()), n, x, send, recv, mask, (), None)

    mesh = Mesh(np.array(jax.devices()[:8]), ("edge",))
    esh = NamedSharding(mesh, P("edge"))
    x_s = jax.device_put(x, NamedSharding(mesh, P(None, None)))
    send_s = jax.device_put(send, esh)
    recv_s = jax.device_put(recv, esh)
    mask_s = jax.device_put(mask, esh)

    monkeypatch.setenv("HYDRAGNN_PALLAS", "interpret")
    out = jax.jit(lambda x, s, r, m: fused_conv(x, s, r, m, n))(
        x_s, send_s, recv_s, mask_s
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)


def pytest_fused_inside_shard_map(monkeypatch):
    """Inside shard_map (the DP train step) operands are already local;
    the partitioned fused op must lower to the plain kernel per device."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    if len(jax.devices()) < 4:
        pytest.skip("needs the virtual multi-device mesh")
    rng = np.random.default_rng(23)
    d_dev, e, h, n = 4, 256, 128, 40
    x = rng.normal(size=(d_dev, n, h)).astype(np.float32)
    recv = np.sort(rng.integers(0, n, (d_dev, e)), axis=1).astype(np.int32)
    send = rng.integers(0, n, (d_dev, e)).astype(np.int32)

    mesh = Mesh(np.array(jax.devices()[:d_dev]), ("data",))
    monkeypatch.setenv("HYDRAGNN_PALLAS", "interpret")

    def local(x, s, r):
        out = fused_conv(
            x[0], s[0], r[0], jnp.ones((e,), bool), n
        )
        return out[None]

    fn = jax.jit(
        shard_map(
            local, mesh=mesh, in_specs=(P("data"), P("data"), P("data")),
            out_specs=P("data"), check_vma=False,
        )
    )
    out = fn(jnp.asarray(x), jnp.asarray(send), jnp.asarray(recv))
    for i in range(d_dev):
        ref = _fused_ref(
            (0, ()), n, jnp.asarray(x[i]), jnp.asarray(send[i]),
            jnp.asarray(recv[i]), jnp.ones((e,), bool), (), None,
        )
        np.testing.assert_allclose(
            np.asarray(out[i]), np.asarray(ref), rtol=1e-4, atol=1e-4
        )


def pytest_fused_conv_validates_inputs():
    x = jnp.zeros((4, 8))
    ids = jnp.zeros((3,), jnp.int32)
    mask = jnp.ones((3,), bool)
    with pytest.raises(ValueError, match="activations"):
        fused_conv(x, ids, ids, mask, 4, branches=((jnp.zeros((8, 8)), None, None, None),))
    with pytest.raises(ValueError, match="at most 2"):
        fused_conv(
            x, ids, ids, mask, 4,
            branches=tuple((jnp.zeros((8, 8)), None, None, None) for _ in range(3)),
            acts=("relu",) * 3,
        )
    with pytest.raises(ValueError, match="activation"):
        fused_conv(
            x, ids, ids, mask, 4,
            branches=((jnp.zeros((8, 8)), None, None, None),), acts=("nope",),
        )
