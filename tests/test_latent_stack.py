"""The latent-attention stack (``LatentAttentionMoE``) against the plain
reference (``benchmark/reference/joyai_llm_flash.py``, which imports
nothing of the program): next-token documents as graphs without edges,
latent attention with decoupled rotary dimensions under the XLA path and
the interpreted kernels (whose queries and keys are wider than their
values), the sigmoid router with its balancing bias, the shared expert and
the shares against the uncut layer, the whole model's two heads, and one
``run_training`` through the scanned epoch, the diagnosed first step and a
save-and-resume. CPU, float32, tiny widths: hidden 64, 3 layers (the first
dense), 4 heads scoring at 12 + 8 and carrying 8, 16 experts of which 8
held and 1 shared, vocabulary 64, one prediction depth.

Tolerances: both sides compute in float32 the same sums in other orders
(the kernels' online softmax, the grouped products' rounds, the chunked
head), so they agree to a few units in the last place of the largest
entry: 2e-5 of the largest entry for outputs, 1e-4 for whole-model
gradients, which sum over more terms. The fp8 control reads far above
them (``test_fp8_operands_fail_the_tolerances``)."""

import copy
import glob
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.append(os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir, "benchmark"))
from reference import joyai_llm_flash as ref  # noqa: E402

from hydragnn_tpu.data.loader import GraphLoader  # noqa: E402
from hydragnn_tpu.data.tokens import next_token_samples  # noqa: E402
from hydragnn_tpu.models.base import ModelConfig, model_loss  # noqa: E402
from hydragnn_tpu.models.create import create_model  # noqa: E402
from hydragnn_tpu.models.token_stack import ExpertLayer, LatentAttention, rotary, rotary_angles  # noqa: E402
from hydragnn_tpu.ops import block_attention as ba  # noqa: E402

ARCH = {
    "model_type": "LatentAttentionMoE", "hidden_dim": 64, "num_conv_layers": 3, "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 12, "qk_rope_head_dim": 8, "v_head_dim": 8,
    "first_k_dense_replace": 1, "intermediate_size": 96, "num_experts": 16,
    "num_experts_per_tok": 4, "moe_intermediate_size": 16, "n_shared_experts": 1, "experts_held": 8,
    "expert_offset": 0, "scoring_func": "sigmoid", "routed_scaling_factor": 2.5, "bias_update_speed": 0.001,
    "num_nextn_predict_layers": 1, "vocab_size": 64, "rope_theta": 3.2e7, "rms_norm_eps": 1e-6, "radius": None,
    "output_heads": {"node": {"type": "vocabulary", "num_headlayers": 0, "dim_headlayers": []}},
    "task_weights": [1.0, 0.3],
}
KEYS = ("num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "first_k_dense_replace", "intermediate_size", "num_experts", "num_experts_per_tok",
        "moe_intermediate_size", "n_shared_experts", "experts_held", "expert_offset", "scoring_func",
        "routed_scaling_factor", "bias_update_speed", "num_nextn_predict_layers", "vocab_size", "rope_theta")
HEADS = ("token", "token_mtp")


def model_cfg(**over) -> ModelConfig:
    a = {**ARCH, **over}
    return ModelConfig(
        model_type=a["model_type"], input_dim=3, hidden_dim=a["hidden_dim"], output_dim=(1, 1),
        output_type=("node", "node"), output_names=HEADS, task_weights=tuple(a["task_weights"]),
        num_conv_layers=a["num_conv_layers"], loss_function_type="cross_entropy", **{k: a[k] for k in KEYS},
    )


def ref_cfg(**over) -> ref.Cfg:
    return ref.cfg_from_architecture({**ARCH, **over})


def run_config(**training):
    return {
        "Verbosity": {"level": 0},
        "Dataset": {"name": "tiny_causal", "format": "token_documents",
                    "node_features": {"name": ["token", "index", "copy"], "dim": [1, 1, 1], "column_index": [0, 1, 2]},
                    "graph_features": {"name": [], "dim": [], "column_index": []}},
        "NeuralNetwork": {
            "Architecture": copy.deepcopy(ARCH),
            "Variables_of_interest": {"input_node_features": [0, 1, 2], "output_names": list(HEADS),
                                      "output_index": [0, 0], "type": ["node", "node"]},
            "Training": {"num_epoch": 2, "batch_size": 4, "perc_train": 0.5, "loss_function_type": "cross_entropy",
                         "Optimizer": {"type": "AdamW", "learning_rate": 1e-3}, **training},
        },
    }


def documents(n_docs=16, seed=0, lengths=(8, 12, 16, 24)):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 64, size=int(n)) for n in rng.choice(lengths, size=n_docs)]


def samples(n_docs=16, seed=0, **kw):
    return next_token_samples(documents(n_docs, seed, **kw), HEADS)


@pytest.fixture(scope="module")
def batch():
    return next(iter(GraphLoader(samples(5, seed=3), 5)))


def reference_rows(batch):
    n_node = np.asarray(batch.n_node)[np.asarray(batch.graph_mask)]
    starts = np.concatenate([[0], np.cumsum(n_node)[:-1]])
    t = batch.node_targets
    rows = {"ids": batch.nodes[:, 0], "index": batch.nodes[:, 1], "valid": batch.node_mask,
            "target": t["token"][:, 0], "weight": t["token_weight"][:, 0],
            "target_mtp": t["token_mtp"][:, 0], "weight_mtp": t["token_mtp_weight"][:, 0]}
    return rows, [(int(s), int(n)) for s, n in zip(starts, n_node)]


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


def nudged(params, scale=0.1):
    return jax.tree_util.tree_map(lambda p: p + scale * jax.random.normal(jax.random.PRNGKey(p.size), p.shape), params)


@pytest.fixture(params=["xla", "interpret"])
def path(request, monkeypatch):
    monkeypatch.setenv("HYDRAGNN_PALLAS", "0" if request.param == "xla" else "interpret")
    return request.param


# -- the data: one copy of every document, two targets ----------------------------


def test_next_token_layout():
    (s,) = next_token_samples([np.arange(1, 7)], HEADS)
    assert s.x.dtype == np.int32 and s.x.shape == (6, 3) and s.num_edges == 0
    assert np.array_equal(s.x[:, 0], np.arange(1, 7)) and np.array_equal(s.x[:, 1], np.arange(6))
    assert not s.x[:, 2].any()
    t = s.node_targets
    assert np.array_equal(t["token"][:, 0], [2, 3, 4, 5, 6, 0]) and np.array_equal(t["token_weight"][:, 0], [1, 1, 1, 1, 1, 0])
    assert np.array_equal(t["token_mtp"][:, 0], [3, 4, 5, 6, 0, 0])
    assert np.array_equal(t["token_mtp_weight"][:, 0], [1, 1, 1, 1, 0, 0])
    with pytest.raises(ValueError, match="at least two tokens"):
        next_token_samples([np.arange(1)])


def test_blocks_of_one_clean_row_are_the_document_causal_mask(batch):
    doc, idx, cpy = (np.asarray(a) for a in (batch.node_graph, batch.nodes[:, 1], batch.nodes[:, 2]))
    got = np.asarray(ba.dense_mask(doc, idx, cpy))
    want = (doc[:, None] == doc[None, :]) & (idx[None, :] <= idx[:, None])
    assert np.array_equal(got, want)


# -- rotary and the kernels at two widths -------------------------------------------


def test_interleaved_rotary_matches_the_reference():
    x = jax.random.normal(jax.random.PRNGKey(0), (10, 3, 8))
    index = jnp.arange(10) * 7
    cos, sin = rotary_angles(index, 8, 3.2e7)
    close(rotary(x, cos, sin, interleaved=True), ref.rope_interleaved(x, index, 3.2e7), 1e-6)
    # the half-split form is the interleaved one with the pairs laid out apart
    perm = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2)])
    close(rotary(x[..., perm], cos, sin), rotary(x, cos, sin, interleaved=True)[..., perm], 1e-6)


@pytest.mark.parametrize("heads,tile,kv_per_step", [
    pytest.param(4, 16, 4, id="16"),
    pytest.param(4, 64, 4, id="64"),
    pytest.param(8, 16, 8, id="8-heads-one-block"),
    pytest.param(16, 32, 8, id="16-heads-two-blocks"),
])
def test_attention_kernels_with_wider_queries_than_values(batch, heads, tile, kv_per_step, monkeypatch):
    """Queries and keys of 48, values and outputs of 32, one key-value head
    a query head, the document-causal mask, ``kv_per_step`` heads a grid
    step: the interpreted kernels against the dense path, forward and
    backward."""
    assert ba.kv_heads_per_step(heads, heads, 48, 32, tile) == kv_per_step
    n = batch.nodes.shape[0]
    kq, kk, kv, kd = jax.random.split(jax.random.PRNGKey(5), 4)
    q, k = jax.random.normal(kq, (n, heads, 48)), jax.random.normal(kk, (n, heads, 48))
    v, do = jax.random.normal(kv, (n, heads, 32)), jax.random.normal(kd, (n, heads, 32))
    doc, blk, cpy = batch.node_graph, batch.nodes[:, 1], batch.nodes[:, 2]
    want, pull = jax.vjp(lambda q, k, v: ba.block_attention_xla(q, k, v, doc, blk, cpy, 48**-0.5), q, k, v)
    monkeypatch.setenv("HYDRAGNN_PALLAS", "interpret")
    got, pull_k = jax.vjp(lambda q, k, v: ba.block_attention(q, k, v, doc, blk, cpy, 48**-0.5, tile=tile), q, k, v)
    assert got.shape == (n, heads, 32)
    close(got, want)
    for g, w in zip(pull_k(do), pull(do)):
        close(g, w)


def test_heads_a_grid_step_follow_the_shapes(monkeypatch):
    """The head block is the largest divisor of the key-value heads that
    gives at most eight query heads and whose blocks fit the kernels' VMEM:
    eight of latent attention's 32 one-head groups at the cell's widths,
    one of the block-diffusion stack's four groups of eight."""
    assert ba.kv_heads_per_step(32, 32, 192, 128, 512) == 8
    assert ba.kv_heads_per_step(32, 4, 128, 128, 512) == 1
    for hq, hkv in [(32, 32), (32, 4), (24, 6), (12, 12), (10, 10), (64, 2), (7, 7)]:
        kvb = ba.kv_heads_per_step(hq, hkv, 192, 128, 512)
        assert hkv % kvb == 0 and (kvb * (hq // hkv) <= ba.HEADS_PER_STEP or kvb == 1)
    assert ba.kv_heads_per_step(10, 10, 192, 128, 512) == 5 and ba.kv_heads_per_step(7, 7, 192, 128, 512) == 7

    # wide heads: eight do not fit the budget, so the next smaller divisor that does
    kvb = ba.kv_heads_per_step(32, 32, 1024, 1024, 512)
    assert kvb == 2
    assert ba._vmem_bytes(2, 2, 512, 1024, 1024) <= ba._VMEM_LIMIT < ba._vmem_bytes(4, 4, 512, 1024, 1024)
    monkeypatch.setattr(ba, "_VMEM_LIMIT", 1)  # nothing fits: one key-value head a step, as before the blocks
    assert ba.kv_heads_per_step(32, 32, 192, 128, 512) == 1
    assert ba.kv_heads_per_step(32, 4, 128, 128, 512) == 1


def test_manifest_names_the_attention_grid():
    """``attention_grid`` for the stack's own heads and a train batch's row
    slots: what the kernels' grid will be, skipped steps included."""
    grid = model_cfg().manifest_block(1000)["model"]["token_stack"]["attention_grid"]
    assert grid == {"tile": 512, "query_heads_per_step": 4, "kv_heads_per_step": 4, "grid_steps_per_call": 4}
    assert ba.attention_grid(32, 32, 192, 128, 8208) == {
        "tile": 512, "query_heads_per_step": 8, "kv_heads_per_step": 8, "grid_steps_per_call": 4 * 17 * 17}
    assert ba.attention_grid(32, 4, 128, 128, 16400) == {
        "tile": 512, "query_heads_per_step": 8, "kv_heads_per_step": 1, "grid_steps_per_call": 4 * 33 * 33}


# -- the layers against the reference ---------------------------------------------


def _rows_for_layer(batch, cfg):
    cos, sin = rotary_angles(batch.nodes[:, 1], cfg.qk_rope_head_dim, cfg.rope_theta)
    return (batch.node_graph, batch.nodes[:, 1], batch.nodes[:, 2], cos, sin)


@pytest.mark.parametrize("fault", [None, "full_rope"])
def test_latent_attention_matches_reference(batch, path, fault):
    cfg = model_cfg()
    a = jax.random.normal(jax.random.PRNGKey(1), (batch.nodes.shape[0], cfg.hidden_dim))
    layer = LatentAttention(cfg)
    rows = _rows_for_layer(batch, cfg)
    plan = ba.attention_plan(*rows[:3]) if path == "interpret" else None
    params = nudged(layer.init(jax.random.PRNGKey(2), a, rows, plan)["params"])
    rrows, docs = reference_rows(batch)
    real = batch.node_mask[:, None]

    def prog(p, a):
        return jnp.where(real, layer.apply({"params": p}, a, rows, plan), 0.0)

    def plain(p, a):
        return jnp.where(real, ref.attention(p, a, rrows, docs, ref_cfg(), fault=fault), 0.0)

    got, pull = jax.vjp(prog, params, a)
    want, pull_r = jax.vjp(plain, params, a)
    if fault == "full_rope":  # the planted fault: rotary over the whole head is another layer
        assert np.abs(np.asarray(got) - np.asarray(want)).max() > 1e-2 * np.abs(np.asarray(want)).max()
        return
    close(got, want)
    cot = jax.random.normal(jax.random.PRNGKey(3), got.shape)
    (gp, ga), (wp, wa) = pull(cot), pull_r(cot)
    trees_close(gp, wp)
    close(jnp.where(real, ga, 0.0), jnp.where(real, wa, 0.0))


def _expert_layer(batch, cfg, seed=4):
    m = jax.random.normal(jax.random.PRNGKey(seed), (batch.nodes.shape[0], cfg.hidden_dim))
    layer = ExpertLayer(cfg)
    params = layer.init(jax.random.PRNGKey(seed + 1), m, batch.node_mask)["params"]
    return m, layer, nudged(params, 0.05)


def _bias_of(stats):
    return stats["batch_stats"]["router_bias"]


def test_sigmoid_router_chooses_with_the_bias_and_weighs_without_it(batch):
    """A large bias on experts 2 and 9 puts them among every row's choices,
    and their weights are their sigmoid scores (not score plus bias) among
    the chosen, renormalised, times 2.5."""
    cfg = model_cfg(experts_held=16)
    m, layer, params = _expert_layer(batch, cfg)
    bias = jnp.zeros(16).at[jnp.array([2, 9])].set(10.0)
    valid = batch.node_mask
    weights, chosen = ref.routing(params, m, bias, ref_cfg(experts_held=16))
    assert bool(jnp.all(chosen[valid][:, jnp.array([2, 9])] == 1))
    s = jax.nn.sigmoid(m @ params["router"])
    picked = s * chosen
    close(weights, picked / picked.sum(-1, keepdims=True) * 2.5, 1e-6)
    assert np.allclose(np.asarray(weights.sum(-1)), 2.5, rtol=1e-6)
    # the program's layer routes the same way: its output is the reference's with this bias
    got = layer.apply({"params": params, "batch_stats": {"router_bias": bias}}, m, valid)
    want = ref.moe(params, m, bias, valid, ref_cfg(experts_held=16))[0]
    close(jnp.where(valid[:, None], got, 0.0), jnp.where(valid[:, None], want, 0.0))


def test_the_bias_rule_over_three_steps(batch):
    """Three train passes of one layer: each chooses with the bias the one
    before left and moves it by 0.001 against every expert's load over ALL
    experts (held or not); the reference's rule gives the same bias, and the
    counter reads the hottest expert's load over a balanced share."""
    cfg = model_cfg()
    m, layer, params = _expert_layer(batch, cfg)
    stats = {"router_bias": jnp.zeros(16)}
    bias_r = jnp.zeros(16)
    valid = batch.node_mask
    real = np.asarray(valid)
    balanced = real.sum() * 4 / 16
    for step in range(3):
        m_k = m + 0.3 * step
        load = np.asarray(ref.routing(params, m_k, bias_r, ref_cfg())[1])[real].sum(0)
        _, mutated = layer.apply({"params": params, "batch_stats": stats}, m_k, valid, mutable=["batch_stats"])
        stats = mutated["batch_stats"]
        _, bias_r, _ = ref.moe(params, m_k, bias_r, valid, ref_cfg())
        close(stats["router_bias"], bias_r, 1e-6)
        assert load.sum() == real.sum() * 4
        assert float(stats["routed_load_max_over_mean"]) == pytest.approx(load.max() / balanced, rel=1e-6)
    moved = np.abs(np.asarray(stats["router_bias"]))
    assert 0 < moved.max() <= 0.003 + 1e-7
    # an eval pass (not mutable) reads the bias and moves nothing
    assert layer.apply({"params": params, "batch_stats": stats}, m, valid).shape == m.shape


def test_expert_layer_with_a_shared_expert_matches_the_reference(batch, path):
    cfg = model_cfg()
    m, layer, params = _expert_layer(batch, cfg)
    valid = batch.node_mask
    real = valid[:, None]
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(9), (16,))

    def prog(p, m):
        return jnp.where(real, layer.apply({"params": p, "batch_stats": {"router_bias": bias}}, m, valid), 0.0)

    def plain(p, m):
        return jnp.where(real, ref.moe(p, m, bias, valid, ref_cfg())[0], 0.0)

    got, pull = jax.vjp(prog, params, m)
    want, pull_r = jax.vjp(plain, params, m)
    close(got, want)
    cot = jax.random.normal(jax.random.PRNGKey(6), got.shape)
    (gp, gm), (wp, wm) = pull(cot), pull_r(cot)
    trees_close(gp, wp)
    close(jnp.where(real, gm, 0.0), jnp.where(real, wm, 0.0))


def test_the_shares_add_up_to_the_uncut_layer(batch, path):
    """Four chips of 4 routed experts each, and the shared expert that every
    chip computes alike: the routed parts of the four shares, with the
    shared expert counted once, are what the reference gives for the whole
    layer of 16."""
    whole = model_cfg(experts_held=16)
    m, _, params = _expert_layer(batch, whole)
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(9), (16,))
    valid = batch.node_mask
    real = valid[:, None]
    want = ref.moe(params, m, bias, valid, ref_cfg(experts_held=16))[0]
    shared = ref.swiglu(params["shared_experts"], m)
    total = shared
    for offset in (0, 4, 8, 12):
        share = model_cfg(experts_held=4, expert_offset=offset)
        cut = {k: (v[offset:offset + 4] if k.startswith("experts_") else v) for k, v in params.items()}
        y = ExpertLayer(share).apply({"params": cut, "batch_stats": {"router_bias": bias}}, m, valid)
        close(jnp.where(real, y, 0.0), jnp.where(real, ref.moe(cut, m, bias, valid, ref_cfg(experts_held=4, expert_offset=offset))[0], 0.0))
        total = total + (y - shared)
    close(jnp.where(real, total, 0.0), jnp.where(real, want, 0.0))


# -- the whole model --------------------------------------------------------------


def _bias_tree(batch_stats, cfg):
    t = batch_stats["tokens"]
    return {"layers": jnp.stack([t[f"layer_{i}"]["moe"]["router_bias"] for i in range(cfg.dense, cfg.layers)]),
            "mtp": t["mtp_1"]["layer"]["moe"]["router_bias"]}


def _model(batch, seed=0):
    cfg = model_cfg()
    model, variables = create_model(cfg, batch, seed=seed)
    params = nudged(variables["params"], 0.02)
    stats = jax.tree_util.tree_map(lambda b: b, variables["batch_stats"])
    stats["tokens"]["layer_1"]["moe"]["router_bias"] = 0.01 * jnp.arange(16.0)  # a bias that has moved
    return cfg, model, params, stats


def test_model_loss_and_gradient_match_reference(batch, path):
    cfg, model, params, stats = _model(batch)
    rows, docs = reference_rows(batch)
    bias = _bias_tree(stats, ref_cfg())

    def prog(p):
        outputs = model.apply({"params": p, "batch_stats": stats}, batch)
        return model_loss(cfg, outputs, batch)

    def plain(p):
        total, (main, mtp, _, _) = ref.losses_and_state(p, bias, rows, docs, ref_cfg())
        return total, (main, mtp)

    (got, tasks), grad = jax.jit(jax.value_and_grad(prog, has_aux=True))(params)
    (want, (main, mtp)), grad_r = jax.jit(jax.value_and_grad(plain, has_aux=True))(params)
    assert float(got) == pytest.approx(float(want), rel=2e-5)
    assert float(tasks[0]) == pytest.approx(float(main), rel=2e-5) and float(tasks[1]) == pytest.approx(float(mtp), rel=2e-5)
    assert float(main) > 1.0 and float(mtp) > 1.0  # about log(64)
    trees_close(grad, grad_r, tol=1e-4)
    # each head's second column is the arg-max of the reference's log-probabilities on the rows with a target
    out = model.apply({"params": params, "batch_stats": stats}, batch)
    lp_main, lp_mtp = ref.log_probs(params, bias, rows, docs, ref_cfg())
    for o, lp, w in zip(out, (lp_main, lp_mtp), (rows["weight"], rows["weight_mtp"])):
        keep = np.asarray(batch.node_mask) & (np.asarray(w) > 0)
        assert o.shape == (batch.nodes.shape[0], 2)
        assert np.array_equal(np.asarray(o[:, 1])[keep], np.asarray(jnp.argmax(lp, -1))[keep])


def test_rows_past_a_document_end_weigh_nothing(batch):
    """Any target at a row without one (the last row of a document for the
    main head, the last two for the prediction depth) leaves loss and
    gradient as they are."""
    cfg, model, params, stats = _model(batch)

    def loss(b):
        return model_loss(cfg, model.apply({"params": params, "batch_stats": stats}, b), b)[0]

    t = dict(batch.node_targets)
    for name in HEADS:
        t[name] = jnp.where(t[name + "_weight"] > 0, t[name], 17)
    assert float(loss(batch.replace(node_targets=t))) == pytest.approx(float(loss(batch)), rel=1e-6)


def test_train_pass_moves_every_bias_and_counts(batch):
    cfg, model, params, stats = _model(batch)
    rows, docs = reference_rows(batch)
    _, mutated = model.apply({"params": params, "batch_stats": stats}, batch, train=True, mutable=["batch_stats"])
    _, (_, _, bias_r, held_r) = ref.losses_and_state(params, _bias_tree(stats, ref_cfg()), rows, docs, ref_cfg())
    got = _bias_tree(mutated["batch_stats"], ref_cfg())
    trees_close(got, bias_r, 1e-6)
    flat = {jax.tree_util.keystr(k): float(v) for k, v in jax.tree_util.tree_flatten_with_path(mutated["batch_stats"])[0]
            if np.ndim(v) == 0}
    assert sum(v for k, v in flat.items() if "held_assignments" in k) == float(held_r)
    assert flat["['tokens']['mtp_rows']"] == float(np.sum(np.asarray(rows["weight_mtp"]) > 0))


@pytest.mark.parametrize("fault", ["half_batch", "mtp_next", "softmax_router", "full_rope"])
def test_planted_faults_move_the_reference(batch, fault):
    cfg, _, params, stats = _model(batch)
    rows, docs = reference_rows(batch)
    rows["first_half"] = batch.node_graph < 2
    bias = _bias_tree(stats, ref_cfg())
    sound, broken = (jax.jit(lambda p, f=f: ref.loss_fn(p, bias, rows, docs, ref_cfg(), fault=f))(params)
                     for f in (None, fault))
    assert abs(float(broken) - float(sound)) > 1e-4 * abs(float(sound))


def test_fp8_operands_fail_the_tolerances(batch):
    cfg, _, params, stats = _model(batch)
    rows, docs = reference_rows(batch)
    bias = _bias_tree(stats, ref_cfg())
    def loss(quant):
        return jax.jit(jax.value_and_grad(lambda p: ref.loss_fn(p, bias, rows, docs, ref_cfg(), quant=quant)))(params)

    (want, grad), (fp8, grad8) = loss(None), loss("fp8")
    assert abs(float(fp8) - float(want)) > 2e-5 * abs(float(want))
    with pytest.raises(AssertionError):
        trees_close(grad8, grad, tol=1e-4)


def test_diagnostics_of_the_last_head_from_the_total(batch):
    """Two heads: their gradient norms and cosine derived from the first
    head's pull and the total's are the ones two explicit pulls give."""
    from hydragnn_tpu.models.base import train_loss_closure
    from hydragnn_tpu.obs.introspect import head_diagnostics, linearize_heads

    cfg, model, params, stats = _model(batch)
    loss_fn = train_loss_closure(model, None, stats, batch, jax.random.PRNGKey(0))
    w = cfg.normalized_weights
    full = jax.jit(lambda p: linearize_heads(loss_fn, p, w))(params)
    lean = jax.jit(lambda p: linearize_heads(loss_fn, p, w, last_from_total=True))(params)
    assert len(full[3]) == 2 and len(lean[3]) == 1
    trees_close(lean[4], full[4], 1e-6)
    d_full = head_diagnostics(full[1], full[3], full[4], params, full[4])
    d_lean = head_diagnostics(lean[1], lean[3], lean[4], params, lean[4], w)
    close(d_lean["grad_norms"], d_full["grad_norms"], 1e-4)
    close(d_lean["cosine"], d_full["cosine"], 1e-4)


def test_configuration_errors_name_the_keys():
    with pytest.raises(ValueError, match="q_lora_rank"):
        model_cfg(q_lora_rank=None)
    with pytest.raises(ValueError, match="2 node head"):
        ModelConfig(**{**model_cfg().__dict__, "output_type": ("node",), "output_dim": (1,), "output_names": ("token",),
                       "task_weights": (1.0,)})
    with pytest.raises(ValueError, match="scoring_func"):
        model_cfg(scoring_func="tanh")


# -- through run_training ---------------------------------------------------------


def _run(tmp_path, monkeypatch, name, **training):
    from hydragnn_tpu.api import run_training
    from hydragnn_tpu.obs import read_flight_record

    monkeypatch.setenv("HYDRAGNN_TELEMETRY", "1")
    monkeypatch.setenv("HYDRAGNN_DIAGNOSTICS", "1")
    log_dir = str(tmp_path / name)
    _, state, history, full = run_training(run_config(**training), samples=samples(24, seed=7), log_dir=log_dir)
    events = read_flight_record(glob.glob(log_dir + "/*/flight.jsonl")[0])
    manifest = [e for e in events if e.get("kind") == "run_start"][0]["manifest"]
    return state, history, full, manifest, [e for e in events if e.get("kind") == "epoch"]


def test_run_training_scans_diagnoses_saves_and_resumes(tmp_path, monkeypatch):
    from hydragnn_tpu.api import run_training
    from hydragnn_tpu.utils.config import get_log_name_config

    state, history, full, manifest, epochs = _run(tmp_path, monkeypatch, "logs", num_epoch=3, checkpoint_every=1)
    mode = manifest["dispatch_mode"]
    assert mode["mode"] == "scan_epoch" and mode["diagnostics"]["path"] == "first_step"
    assert mode["test_split"]["path"] == "on_device"
    stack = manifest["model"]["token_stack"]
    assert stack["stack"] == "LatentAttentionMoE" and stack["attention"] == "latent"
    assert (stack["d_qk"], stack["d_v"], stack["d_rope"], stack["kv_lora_rank"], stack["q_lora_rank"]) == (20, 8, 8, 16, 24)
    assert (stack["dense_layers"], stack["shared_experts"], stack["scoring"], stack["mtp_depth"]) == (1, 1, "sigmoid", 1)
    assert stack["block_length"] == 1 and stack["experts_held"] == 8
    plan = manifest["pad_plans"]["train"]
    assert stack["attention_grid"] == ba.attention_grid(4, 4, 20, 8, plan["pad_nodes"])
    assert stack["attention_grid"]["query_heads_per_step"] == 4
    assert plan["plan"] == "fixed_membership" and plan["real_edges_max"] == 0
    losses = history["train_loss"]
    assert len(losses) == 3 and losses[-1] < losses[0] and np.isfinite(history["test_loss"]).all()
    nb = plan["num_batches"]
    for e in epochs:
        assert e["steps"] == nb and e["diagnosed_steps"] == 1 and e["graphs"] == 12
        assert e["rows"] == e["tokens"] > 0 and e["mtp.rows"] > 0
        assert e["moe.dropped"] == 0 and e["moe.held_assignments"] > 0 and e["moe.load_max_over_mean"] > 0
        assert e["moe.routed_load_max_over_mean"] >= 1.0 and e["moe.bias_abs_max"] > 0
        assert "nonfinite" not in e
        assert set(e["heads"]["accuracy"]) == set(HEADS)
    assert epochs[1]["compiles"]["count"] == 0 and epochs[2]["compiles"]["count"] == 0
    bias = state.batch_stats["tokens"]["layer_1"]["moe"]["router_bias"]
    assert 0 < float(jnp.abs(bias).max()) <= 0.001 * 3 * nb + 1e-6  # one move of 0.001 a train step at most
    # a finished run resumes to nothing left to do
    name = get_log_name_config(full)
    cfg2 = run_config(num_epoch=3, checkpoint_every=1)
    cfg2["NeuralNetwork"]["Training"].update({"continue": 1, "startfrom": name})
    _, state2, history2, _ = run_training(cfg2, samples=samples(24, seed=7), log_dir=str(tmp_path / "logs"))
    assert history2["train_loss"] == losses


def test_save_and_resume_reproduces_the_loss_and_the_bias(tmp_path, monkeypatch):
    from hydragnn_tpu.api import run_training
    from hydragnn_tpu.utils.config import get_log_name_config

    monkeypatch.setenv("HYDRAGNN_DIAGNOSTICS", "0")
    _, whole_state, hist, _ = run_training(run_config(num_epoch=3, checkpoint_every=1), samples=samples(24, seed=7),
                                           log_dir=str(tmp_path / "whole"))
    _, _, hist_a, full = run_training(run_config(num_epoch=2, checkpoint_every=1), samples=samples(24, seed=7),
                                      log_dir=str(tmp_path / "cut"))
    assert hist_a["train_loss"] == hist["train_loss"][:2]
    rest = run_config(num_epoch=3, checkpoint_every=1)
    rest["NeuralNetwork"]["Training"].update({"continue": 1, "startfrom": get_log_name_config(full)})
    _, state_b, hist_b, _ = run_training(rest, samples=samples(24, seed=7), log_dir=str(tmp_path / "cut"))
    assert hist_b["train_loss"][2] == pytest.approx(hist["train_loss"][2], rel=1e-6)
    trees_close(state_b.batch_stats, whole_state.batch_stats, 1e-6)


@pytest.mark.parametrize("chunk", [None, 4096], ids=["whole", "chunked"])
def test_a_checkpoint_streams_the_bytes_flax_writes(batch, tmp_path, monkeypatch, chunk):
    """``save_model`` writes a state one leaf at a time (the 5.9 GB state of
    this stack's cell does not fit on a one-chip host twice over beside a
    training run's own copies): the file is byte for byte what
    ``flax.serialization.to_bytes`` makes of the whole host state, with
    arrays over flax's chunk size in chunks, its sha256 sidecar matches,
    and it restores."""
    import hashlib

    from flax import serialization

    from hydragnn_tpu.train import create_train_state, select_optimizer
    from hydragnn_tpu.utils.checkpoint import _to_host, load_existing_model, save_model

    if chunk:
        monkeypatch.setattr(serialization, "MAX_CHUNK_SIZE", chunk)
    _, variables = create_model(model_cfg(), batch)
    state = create_train_state(variables, select_optimizer({"Optimizer": {"type": "AdamW", "learning_rate": 1e-3}}))
    path = save_model(state, "stream", str(tmp_path), keep_last=1)
    want = serialization.to_bytes(jax.tree_util.tree_map(_to_host, state))
    with open(path, "rb") as f:
        assert f.read() == want
    (version,) = glob.glob(str(tmp_path / "stream" / "stream.step*.mp"))
    with open(version + ".sha256") as f:
        assert f.read() == hashlib.sha256(want).hexdigest()
    restored = load_existing_model(create_train_state(variables, select_optimizer(
        {"Optimizer": {"type": "AdamW", "learning_rate": 1e-3}})), "stream", str(tmp_path))
    trees_close(restored.params, state.params, 0.0)
