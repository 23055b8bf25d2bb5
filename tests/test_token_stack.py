"""The token stack against the plain reference (``benchmark/reference/
sdar_moe.py``, which imports nothing of the program): packed token
documents as graphs without edges, the block-diffusion mask, grouped-query
attention under the XLA path and the interpreted kernels, the held-experts
layer against the loop and the shares against the uncut layer, the whole
model's loss and gradient, and one ``run_training`` through the scanned
epoch, the diagnosed first step and a save-and-resume. CPU, float32, tiny
widths: hidden 64, 2 layers, 8 experts of which 4 held, vocabulary 64,
block 4."""

import copy
import glob
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.append(os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir, "benchmark"))
from reference import sdar_moe as ref  # noqa: E402

from hydragnn_tpu.data.loader import GraphLoader  # noqa: E402
from hydragnn_tpu.data.tokens import block_diffusion_samples, block_rates  # noqa: E402
from hydragnn_tpu.models.base import ModelConfig, model_loss  # noqa: E402
from hydragnn_tpu.models.create import create_model  # noqa: E402
from hydragnn_tpu.models.token_stack import Attention, ExpertLayer, rotary_angles  # noqa: E402
from hydragnn_tpu.ops import block_attention as ba  # noqa: E402

ARCH = {
    "model_type": "BlockDiffusionMoE", "hidden_dim": 64, "num_conv_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "experts_held": 4, "expert_offset": 0, "vocab_size": 64, "block_length": 4,
    "rope_theta": 1e6, "rms_norm_eps": 1e-6, "radius": None,
    "output_heads": {"node": {"type": "vocabulary", "num_headlayers": 0, "dim_headlayers": []}},
    "task_weights": [1.0],
}


def model_cfg(**over) -> ModelConfig:
    a = {**ARCH, **over}
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim", "num_experts", "num_experts_per_tok",
            "moe_intermediate_size", "experts_held", "expert_offset", "vocab_size", "block_length")
    return ModelConfig(
        model_type=a["model_type"], input_dim=3, hidden_dim=a["hidden_dim"], output_dim=(1,), output_type=("node",),
        output_names=("token",), task_weights=(1.0,), num_conv_layers=a["num_conv_layers"],
        loss_function_type="cross_entropy", **{k: a[k] for k in keys},
    )


def run_config(tmp, **training):
    return {
        "Verbosity": {"level": 0},
        "Dataset": {"name": "tiny_tokens", "format": "token_documents",
                    "node_features": {"name": ["token", "index", "copy"], "dim": [1, 1, 1], "column_index": [0, 1, 2]},
                    "graph_features": {"name": [], "dim": [], "column_index": []}},
        "NeuralNetwork": {
            "Architecture": copy.deepcopy(ARCH),
            "Variables_of_interest": {"input_node_features": [0, 1, 2], "output_names": ["token"],
                                      "output_index": [0], "type": ["node"]},
            "Training": {"num_epoch": 2, "batch_size": 4, "perc_train": 0.5, "loss_function_type": "cross_entropy",
                         "Optimizer": {"type": "AdamW", "learning_rate": 1e-3}, **training},
        },
    }


def documents(n_docs=16, seed=0, lengths=(8, 12, 16, 24)):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 63, size=int(n)) for n in rng.choice(lengths, size=n_docs)]


def samples(n_docs=16, seed=0, **kw):
    return block_diffusion_samples(documents(n_docs, seed, **kw), seed=seed + 1, block_length=4, mask_id=63)


@pytest.fixture(scope="module")
def batch():
    return next(iter(GraphLoader(samples(5, seed=3), 5)))


def reference_rows(batch):
    n_node = np.asarray(batch.n_node)[np.asarray(batch.graph_mask)]
    starts = np.concatenate([[0], np.cumsum(n_node)[:-1]])
    rows = {
        "ids": batch.nodes[:, 0], "index": batch.nodes[:, 1], "cpy": batch.nodes[:, 2],
        "target": batch.node_targets["token"][:, 0], "weight": batch.node_targets["token_weight"][:, 0],
        "valid": batch.node_mask,
    }
    return rows, [(int(s), int(n) // 2) for s, n in zip(starts, n_node)]


def ref_cfg(cfg: ModelConfig, **over) -> ref.Cfg:
    base = dict(layers=cfg.num_conv_layers, hidden=cfg.hidden_dim, heads=cfg.num_attention_heads,
                kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim, experts=cfg.num_experts,
                per_tok=cfg.num_experts_per_tok, expert_width=cfg.moe_intermediate_size, held=cfg.experts_held,
                offset=cfg.expert_offset, vocab=cfg.vocab_size, block=cfg.block_length)
    return ref.Cfg(**{**base, **over})


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-12)
    assert np.abs(a - b).max() <= tol * scale, (np.abs(a - b).max(), scale)


def trees_close(a, b, tol=2e-5):
    fa, fb = jax.tree_util.tree_flatten_with_path(a)[0], jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    for (path, x), y in zip(fa, fb):
        try:
            close(x, y, tol)
        except AssertionError as exc:
            raise AssertionError(f"{jax.tree_util.keystr(path)}: {exc}") from None


@pytest.fixture(params=["xla", "interpret"])
def path(request, monkeypatch):
    monkeypatch.setenv("HYDRAGNN_PALLAS", "0" if request.param == "xla" else "interpret")
    return request.param


# -- the data: documents as graphs without edges ----------------------------------


def test_block_rates_are_stratified():
    r = np.sort(block_rates(9, np.random.default_rng(0), 0.1, 1.0))
    edges = 0.1 + 0.9 * np.arange(10) / 9
    assert np.all((r >= edges[:-1]) & (r <= edges[1:]))


def test_noised_document_layout():
    (s,) = block_diffusion_samples([np.arange(1, 13)], seed=5, block_length=4, mask_id=63)
    n = 12
    assert s.x.dtype == np.int32 and s.x.shape == (2 * n, 3) and s.num_edges == 0
    assert np.array_equal(s.x[:n, 1], np.arange(n)) and np.array_equal(s.x[n:, 1], np.arange(n))
    assert np.all(s.x[:n, 2] == 1) and np.all(s.x[n:, 2] == 0)
    assert np.array_equal(s.x[n:, 0], np.arange(1, 13))
    w, masked = s.node_targets["token_weight"][:, 0], s.x[:n, 0] == 63
    assert np.all(w[n:] == 0) and np.all((w[:n] > 0) == masked) and w.max() <= 10.0 + 1e-6
    assert np.array_equal(s.x[:n, 0][~masked], np.arange(1, 13)[~masked])
    for b in range(3):  # one rate a block, the weight is its inverse
        wb = w[4 * b:4 * b + 4]
        assert len(set(np.round(wb[wb > 0], 5))) <= 1
    with pytest.raises(ValueError, match="multiple of the block length"):
        block_diffusion_samples([np.arange(10)], seed=0, block_length=4, mask_id=63)


def test_loader_keeps_integers_and_plans_a_split_without_edges(batch):
    loader = GraphLoader(samples(8, seed=1), 4, shuffle=True, fixed_membership=True)
    assert loader.plan == "fixed_membership" and loader.real_edges_max == 0 and loader.pad_edges == 8
    assert loader.pad_nodes > loader.real_nodes_max
    stacked = loader.stacked_device_batches(0)
    assert stacked.nodes.dtype == jnp.int32 and stacked.node_targets["token"].dtype == jnp.int32
    assert stacked.node_targets["token_weight"].dtype == jnp.float32
    assert not np.asarray(stacked.edge_mask).any()
    assert batch.nodes.dtype == jnp.int32
    batch.check_invariants()


# -- the mask ---------------------------------------------------------------------


def test_mask_matches_brute_force_for_two_packed_documents_with_padding(batch):
    two = next(iter(GraphLoader(samples(2, seed=4, lengths=(8, 12)), 2)))
    doc, idx, cpy = (np.asarray(a) for a in (two.node_graph, two.nodes[:, 1], two.nodes[:, 2]))
    real = np.asarray(two.node_mask)
    assert (~real).sum() >= 1
    n = len(doc)
    want = np.zeros((n, n), bool)
    for i in range(n):
        for j in range(n):
            if doc[i] != doc[j]:
                continue
            bi, bj = idx[i] // 4, idx[j] // 4
            if cpy[i] == 1 and cpy[j] == 1:
                want[i, j] = bi == bj
            elif cpy[i] == 1 and cpy[j] == 0:
                want[i, j] = bj < bi
            elif cpy[i] == 0 and cpy[j] == 0:
                want[i, j] = bj <= bi
    blk = idx // 4
    got = np.asarray(ba.allowed(doc[:, None], blk[:, None], cpy[:, None], doc[None, :], blk[None, :], cpy[None, :]))
    assert np.array_equal(got, want)
    # no real row looks at padding or across documents; every row sees something
    assert not got[real][:, ~real].any() and got.any(axis=1).all()
    # the reference's own mask, a document at a time
    rows, docs = reference_rows(two)
    for start, tokens in docs:
        sl = slice(start, start + 2 * tokens)
        assert np.array_equal(np.asarray(ref.document_mask(rows["index"][sl], rows["cpy"][sl], 4)), want[sl, sl])
    allowed_pairs = sum(t * t + 4 * t for _, t in docs)
    assert want[real][:, real].sum() == allowed_pairs


# -- the kernels ------------------------------------------------------------------


def _qkv(batch, heads=4, kv=2, d=16, seed=0):
    n = batch.nodes.shape[0]
    kq, kk, kv_, kd = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(kq, (n, heads, d)), jax.random.normal(kk, (n, kv, d)),
            jax.random.normal(kv_, (n, kv, d)), jax.random.normal(kd, (n, heads, d)))


@pytest.mark.parametrize("heads,kv_heads,tile,kv_per_step", [
    pytest.param(4, 2, 16, 2, id="16"),
    pytest.param(4, 2, 64, 2, id="64"),
    pytest.param(16, 2, 16, 1, id="group8-one-kv-head-a-step"),
    pytest.param(16, 8, 32, 4, id="group2-two-head-blocks"),
])
def test_block_attention_kernels_match_the_dense_path(batch, heads, kv_heads, tile, kv_per_step, monkeypatch):
    """Grouped-query heads: a grid step works ``kv_per_step`` key-value
    heads and their query heads (one where a group alone is eight query
    heads); the interpreted kernels against the dense path, forward and the
    three cotangents."""
    assert ba.kv_heads_per_step(heads, kv_heads, 16, 16, tile) == kv_per_step
    q, k, v, do = _qkv(batch, heads, kv_heads)
    doc, blk, cpy = batch.node_graph, batch.nodes[:, 1] // 4, batch.nodes[:, 2]
    dense = lambda q, k, v: ba.block_attention_xla(q, k, v, doc, blk, cpy, 0.25)  # noqa: E731
    want, pull = jax.vjp(dense, q, k, v)
    monkeypatch.setenv("HYDRAGNN_PALLAS", "interpret")
    tiled = lambda q, k, v: ba.block_attention(q, k, v, doc, blk, cpy, 0.25, tile=tile)  # noqa: E731
    got, pull_k = jax.vjp(tiled, q, k, v)
    close(got, want)
    for g, w in zip(pull_k(do), pull(do)):
        close(g, w)


def test_empty_tile_pairs_are_not_listed(batch):
    doc, blk, cpy = batch.node_graph, batch.nodes[:, 1] // 4, batch.nodes[:, 2]
    qmeta, (by_q, by_k) = ba.attention_plan(doc, blk, cpy, tile=16)
    nt = qmeta.shape[0] // 16
    count = int(by_q[4][0])
    assert nt <= count < nt * nt and int(by_k[4][0]) == count
    major, minor, first, last = (np.asarray(a)[:count] for a in by_q[:4])
    assert np.all(np.diff(major) >= 0) and first.sum() == nt and last.sum() == nt
    # a listed pair holds an allowed entry or is the diagonal; an unlisted one holds none
    m = np.asarray(ba.allowed(qmeta[:, 0:1], qmeta[:, 1:2], qmeta[:, 2:3], qmeta[:, 0][None], qmeta[:, 1][None],
                              qmeta[:, 2][None])).reshape(nt, 16, nt, 16).any(axis=(1, 3))
    listed = np.zeros((nt, nt), bool)
    listed[major, minor] = True
    assert np.array_equal(listed, m | np.eye(nt, dtype=bool))


# -- the layers against the reference ---------------------------------------------


def _rows_for_layer(batch, cfg):
    cos, sin = rotary_angles(batch.nodes[:, 1], cfg.head_dim, cfg.rope_theta)
    return (batch.node_graph, batch.nodes[:, 1] // cfg.block_length, batch.nodes[:, 2], cos, sin)


def test_attention_layer_matches_reference(batch, path):
    cfg = model_cfg()
    a = jax.random.normal(jax.random.PRNGKey(1), (batch.nodes.shape[0], cfg.hidden_dim))
    layer = Attention(cfg)
    rows = _rows_for_layer(batch, cfg)
    plan = ba.attention_plan(*rows[:3]) if path == "interpret" else None
    params = layer.init(jax.random.PRNGKey(2), a, rows, plan)["params"]
    params = jax.tree_util.tree_map(lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(p.size), p.shape), params)
    rrows, docs = reference_rows(batch)
    real = batch.node_mask[:, None]

    def prog(p, a):
        return jnp.where(real, layer.apply({"params": p}, a, rows, plan), 0.0)

    def plain(p, a):
        return jnp.where(real, ref.attention(p, a, rrows, docs, ref_cfg(cfg)), 0.0)

    got, pull = jax.vjp(prog, params, a)
    want, pull_r = jax.vjp(plain, params, a)
    close(got, want)
    cot = jax.random.normal(jax.random.PRNGKey(3), got.shape)
    (gp, ga), (wp, wa) = pull(cot), pull_r(cot)
    trees_close(gp, wp)
    close(jnp.where(real, ga, 0.0), jnp.where(real, wa, 0.0))


def _expert_layer(batch, cfg, seed=4):
    m = jax.random.normal(jax.random.PRNGKey(seed), (batch.nodes.shape[0], cfg.hidden_dim))
    layer = ExpertLayer(cfg)
    variables = layer.init(jax.random.PRNGKey(seed + 1), m, batch.node_mask)
    return m, layer, variables["params"]


def test_expert_layer_matches_the_loop(batch, path):
    cfg = model_cfg()
    m, layer, params = _expert_layer(batch, cfg)
    real = batch.node_mask[:, None]

    def prog(p, m):
        y, stats = layer.apply({"params": p}, m, batch.node_mask, mutable=["batch_stats"])
        return jnp.where(real, y, 0.0), stats

    def plain(p, m):
        return jnp.where(real, ref.experts(p, m, ref_cfg(cfg)), 0.0)

    got, pull, stats = jax.vjp(prog, params, m, has_aux=True)
    want, pull_r = jax.vjp(plain, params, m)
    close(got, want)
    cot = jax.random.normal(jax.random.PRNGKey(6), got.shape)
    (gp, gm), (wp, wm) = pull(cot), pull_r(cot)
    trees_close(gp, wp)
    close(jnp.where(real, gm, 0.0), jnp.where(real, wm, 0.0))
    # the counters: assignments of real rows to the held experts, none dropped
    weights = np.asarray(ref.routing(params, m, ref_cfg(cfg)))[np.asarray(batch.node_mask)]
    per_expert = (weights[:, :cfg.experts_held] > 0).sum(0)
    flat = {jax.tree_util.keystr(k): float(v) for k, v in jax.tree_util.tree_flatten_with_path(stats)[0]}
    held = [v for k, v in flat.items() if "held_assignments" in k][0]
    load = [v for k, v in flat.items() if "load_max_over_mean" in k][0]
    assert held == per_expert.sum()
    balanced = weights.shape[0] * cfg.num_experts_per_tok / cfg.num_experts
    assert load == pytest.approx(per_expert.max() / balanced, rel=1e-6)


@pytest.mark.parametrize("per_round", [8, 40, 512])
def test_rounds_of_any_size_give_the_same_layer(batch, per_round, monkeypatch):
    """The held assignments worked 8, 40 or 512 a round (an expert's run cut
    anywhere, a round that holds several experts' runs, one round for all):
    the same layer, forward and backward."""
    import hydragnn_tpu.models.token_stack as ts

    monkeypatch.setattr(ts, "round_rows", lambda rows, cfg: per_round)
    cfg = model_cfg()
    m, layer, params = _expert_layer(batch, cfg)
    real = batch.node_mask[:, None]
    got, pull = jax.vjp(lambda p, m: jnp.where(real, layer.apply({"params": p}, m, batch.node_mask), 0.0), params, m)
    want, pull_r = jax.vjp(lambda p, m: jnp.where(real, ref.experts(p, m, ref_cfg(cfg)), 0.0), params, m)
    close(got, want)
    cot = jax.random.normal(jax.random.PRNGKey(7), got.shape)
    (gp, gm), (wp, wm) = pull(cot), pull_r(cot)
    trees_close(gp, wp)
    close(jnp.where(real, gm, 0.0), jnp.where(real, wm, 0.0))


def test_the_shares_add_up_to_the_uncut_layer(batch, path):
    """Two chips of 4 experts each: what they compute, added, is what the
    reference gives for the whole layer of 8 (no shared expert to count once)."""
    whole = model_cfg(experts_held=8)
    m, _, params = _expert_layer(batch, whole)
    want = ref.experts(params, m, ref_cfg(whole))
    total = jnp.zeros_like(m)
    for offset in (0, 4):
        share = model_cfg(experts_held=4, expert_offset=offset)
        cut = {k: (v if k == "router" else v[offset:offset + 4]) for k, v in params.items()}
        y = ExpertLayer(share).apply({"params": cut}, m, batch.node_mask)
        close(jnp.where(batch.node_mask[:, None], y, 0.0),
              jnp.where(batch.node_mask[:, None], ref.experts(cut, m, ref_cfg(share)), 0.0))
        total = total + y
    real = batch.node_mask[:, None]
    close(jnp.where(real, total, 0.0), jnp.where(real, want, 0.0))


def test_a_router_that_sends_everything_here_drops_nothing(path):
    """Every row's two choices go to the two held experts: four times a
    balanced router's share, worked in several rounds, none dropped."""
    batch = next(iter(GraphLoader(samples(8, seed=9, lengths=(24,)), 8)))
    cfg = model_cfg(num_experts=8, experts_held=2, expert_offset=3)
    m, layer, params = _expert_layer(batch, cfg)
    router = np.full(params["router"].shape, 0.0, np.float32)
    router[:, 3:5] = np.asarray(params["router"][:, 3:5]) + 5.0 * np.sign(np.asarray(m).mean(0))[:, None]
    m = m + 2.0 * jnp.sign(m.mean(0))[None, :]  # a common component, so that every row prefers experts 3 and 4
    params = {**params, "router": jnp.asarray(router)}
    real_rows = batch.node_mask[:, None]

    def prog(p, m):
        y, stats = layer.apply({"params": p}, m, batch.node_mask, mutable=["batch_stats"])
        return jnp.where(real_rows, y, 0.0), stats

    y, pull, stats = jax.vjp(prog, params, m, has_aux=True)
    real = int(batch.node_mask.sum())
    flat = {jax.tree_util.keystr(k): float(v) for k, v in jax.tree_util.tree_flatten_with_path(stats)[0]}
    assert [v for k, v in flat.items() if "held_assignments" in k][0] == 2 * real
    import hydragnn_tpu.models.token_stack as ts

    assert 2 * real > ts.round_rows(m.shape[0], cfg)  # more than one round
    assert [v for k, v in flat.items() if "dropped" in k][0] == 0
    want, pull_r = jax.vjp(lambda p, m: jnp.where(real_rows, ref.experts(p, m, ref_cfg(cfg)), 0.0), params, m)
    close(y, want)
    cot = jax.random.normal(jax.random.PRNGKey(8), y.shape)
    (gp, gm), (wp, wm) = pull(cot), pull_r(cot)
    trees_close({k: v for k, v in gp.items() if k != "router"}, {k: v for k, v in wp.items() if k != "router"})
    close(jnp.where(real_rows, gm, 0.0), jnp.where(real_rows, wm, 0.0))


# -- the whole model --------------------------------------------------------------


def test_model_loss_and_gradient_match_reference(batch, path):
    cfg = model_cfg()
    model, variables = create_model(cfg, batch)
    rows, docs = reference_rows(batch)

    def prog(p):
        outputs = model.apply({"params": p, "batch_stats": variables["batch_stats"]}, batch)
        return model_loss(cfg, outputs, batch)[0]

    def plain(p):
        return ref.loss_fn(p, rows, docs, ref_cfg(cfg))

    got, grad = jax.value_and_grad(prog)(variables["params"])
    want, grad_r = jax.value_and_grad(plain)(variables["params"])
    assert float(got) == pytest.approx(float(want), rel=2e-5)
    assert float(want) > 1.0  # about log(64) with a weight of mean 1
    trees_close(grad, grad_r, tol=1e-4)
    # the head's second column is the arg-max of the reference's log-probabilities
    (out,) = model.apply(variables, batch)
    logp = ref.log_probs(variables["params"], rows, docs, ref_cfg(cfg))
    real = np.asarray(batch.node_mask)
    assert np.array_equal(np.asarray(out[:, 1])[real], np.asarray(jnp.argmax(logp, -1))[real])
    assert out.shape == (batch.nodes.shape[0], 2)


@pytest.mark.parametrize("fault", ["causal_mask", "lost_expert", "half_batch"])
def test_planted_faults_move_the_reference(batch, fault):
    cfg = model_cfg()
    _, variables = create_model(cfg, batch)
    rows, docs = reference_rows(batch)
    rows["first_half"] = batch.node_graph < 2
    sound = ref.loss_fn(variables["params"], rows, docs, ref_cfg(cfg))
    broken = ref.loss_fn(variables["params"], rows, docs, ref_cfg(cfg), fault=fault)
    assert abs(float(broken) - float(sound)) > 1e-3 * abs(float(sound))


def test_model_loss_names_the_head_of_an_unknown_kind(batch):
    cfg = model_cfg()
    bad = ModelConfig(model_type="GIN", input_dim=3, hidden_dim=8, output_dim=(1,), output_type=("node",),
                      output_names=("token",), task_weights=(1.0,), loss_function_type="huber")
    with pytest.raises(ValueError, match="head 'token'.*huber"):
        model_loss(bad, [jnp.zeros((batch.nodes.shape[0], 1))], batch)
    with pytest.raises(ValueError, match="cross_entropy"):
        ModelConfig(**{**cfg.__dict__, "loss_function_type": "mse"})


# -- through run_training ---------------------------------------------------------


def _run(tmp_path, monkeypatch, name, **training):
    from hydragnn_tpu.api import run_training
    from hydragnn_tpu.obs import read_flight_record

    monkeypatch.setenv("HYDRAGNN_TELEMETRY", "1")
    monkeypatch.setenv("HYDRAGNN_DIAGNOSTICS", "1")
    config = run_config(str(tmp_path), **training)
    log_dir = str(tmp_path / name)
    _, state, history, full = run_training(config, samples=samples(24, seed=7), log_dir=log_dir)
    events = read_flight_record(glob.glob(log_dir + "/*/flight.jsonl")[0])
    manifest = [e for e in events if e.get("kind") == "run_start"][0]["manifest"]
    return state, history, full, manifest, [e for e in events if e.get("kind") == "epoch"]


def test_run_training_scans_diagnoses_saves_and_resumes(tmp_path, monkeypatch):
    from hydragnn_tpu.utils.config import get_log_name_config

    state, history, full, manifest, epochs = _run(tmp_path, monkeypatch, "logs", num_epoch=3, checkpoint_every=1)
    mode = manifest["dispatch_mode"]
    assert mode["mode"] == "scan_epoch" and mode["diagnostics"]["path"] == "first_step"
    assert mode["test_split"]["path"] == "on_device"
    stack = manifest["model"]["token_stack"]
    plan = manifest["pad_plans"]["train"]
    assert stack == {"stack": "BlockDiffusionMoE", "layers": 2, "experts_held": 4, "experts": 8,
                     "experts_per_token": 2, "vocabulary_held": 64, "block_length": 4,
                     "attention_grid": {"tile": 512, "query_heads_per_step": 4, "kv_heads_per_step": 2,
                                        "grid_steps_per_call": 1}}
    assert plan["pad_nodes"] <= 512  # one tile: one pair slot a head block
    assert plan["plan"] == "fixed_membership" and plan["real_edges_max"] == 0
    assert plan["real_nodes_max"] < plan["pad_nodes"]
    losses = history["train_loss"]
    assert len(losses) == 3 and losses[-1] < losses[0] and np.isfinite(history["test_loss"]).all()
    nb = plan["num_batches"]
    for e in epochs:
        assert e["steps"] == nb and e["diagnosed_steps"] == 1 and e["graphs"] == 12
        assert e["rows"] == 2 * e["tokens"] > 0
        assert e["moe.dropped"] == 0 and e["moe.held_assignments"] > 0 and e["moe.load_max_over_mean"] > 0
        assert "nonfinite" not in e
        assert e["heads"]["accuracy"]["token"] is not None
    assert epochs[1]["compiles"]["count"] == 0 and epochs[2]["compiles"]["count"] == 0
    # resume from the checkpoint of epoch 1 (a copy of the run cut there) and make epoch 2 again
    name = get_log_name_config(full)
    cfg2 = run_config(str(tmp_path), num_epoch=3, checkpoint_every=1)
    cfg2["NeuralNetwork"]["Training"].update({"continue": 1, "startfrom": name})
    from hydragnn_tpu.api import run_training

    _, state2, history2, _ = run_training(cfg2, samples=samples(24, seed=7), log_dir=str(tmp_path / "logs"))
    assert history2["train_loss"] == losses  # a finished run resumes to nothing left to do


def test_save_and_resume_reproduces_the_loss(tmp_path, monkeypatch):
    from hydragnn_tpu.api import run_training
    from hydragnn_tpu.utils.config import get_log_name_config

    monkeypatch.setenv("HYDRAGNN_DIAGNOSTICS", "0")
    whole = run_config(str(tmp_path), num_epoch=3, checkpoint_every=1)
    _, _, hist, _ = run_training(whole, samples=samples(24, seed=7), log_dir=str(tmp_path / "whole"))
    first = run_config(str(tmp_path), num_epoch=2, checkpoint_every=1)
    _, _, hist_a, full = run_training(first, samples=samples(24, seed=7), log_dir=str(tmp_path / "cut"))
    assert hist_a["train_loss"] == hist["train_loss"][:2]
    name = get_log_name_config(full)
    rest = run_config(str(tmp_path), num_epoch=3, checkpoint_every=1)
    rest["NeuralNetwork"]["Training"].update({"continue": 1, "startfrom": name})
    _, _, hist_b, _ = run_training(rest, samples=samples(24, seed=7), log_dir=str(tmp_path / "cut"))
    assert len(hist_b["train_loss"]) == 3
    assert hist_b["train_loss"][2] == pytest.approx(hist["train_loss"][2], rel=1e-6)
    assert hist_b["val_loss"][2] == pytest.approx(hist["val_loss"][2], rel=1e-6)
