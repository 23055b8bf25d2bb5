"""The token stacks: pre-norm decoder layers over the rows of packed token
documents, for ``Architecture.model_type: "BlockDiffusionMoE"`` (this
docstring) and ``"LatentAttentionMoE"`` (:class:`LatentStack`: latent
attention, leading dense layers, shared experts, a sigmoid router with a
balancing bias, next-token training with more prediction depths).

Not the conv chassis: no edge list, no BatchNorm + ReLU between layers.
What mixes among a graph's nodes is their ORDER. A graph is a document
(``data/tokens.py``: the noised copy, then the clean copy), ``node_graph``
is the document id, and the attention mask is computed from three integers
a row (``ops/block_attention.py``). One layer, on rows ``h`` (no bias
anywhere):

  a = RMSNorm(h); q, k = RMSNorm_head(a Wq), RMSNorm_head(a Wk); v = a Wv;
  rotary embedding over the whole head, half-split, at the token's index in
  its own document; grouped-query attention under the block-diffusion mask;
  h += o Wo
  m = RMSNorm(h); r = softmax(m Wr) over ALL experts in float32; the
  ``num_experts_per_tok`` largest, renormalised to sum 1; y = the part of
  sum_e r_e Wdown_e (silu(Wgate_e m) * Wup_e m) that the experts HELD here
  give; h += y

then RMSNorm and one matrix onto the vocabulary held. The layer is told
which experts it holds (``experts_held`` of ``num_experts`` from
``expert_offset``): it routes over all of them and computes its own
experts' part, which is what one chip of an expert-parallel group does
before the exchange; on one chip there is no exchange, and nothing stands
in for the absent chips. Each projection of the held experts is ONE stacked
parameter ``[held, in, out]``, which the grouped product
(``jax.lax.ragged_dot``) wants.

Under ``Training.mixed_precision`` parameters and activations arrive in
bfloat16; the norms' statistics, the router's softmax, the rotary angles,
the attention's softmax and the head's log-softmax are float32.

The head never hands out ``[rows, vocabulary]``: it returns, a row, the
log-probability of the row's target and the arg-max (``[N, 2]`` float32),
computed in row chunks that the backward recomputes. ``model_loss`` weighs
the first column; the test pass reads both.

The expert layer's router is the configuration's (``ExpertLayer._route``):
a softmax as above, or sigmoid scores, with or without a balancing bias
(``batch_stats``) that joins the scores for the choice only and that a
train step moves against each expert's load; the chosen weights times
``routed_scaling_factor``; shared experts beside the held ones.

Counters (the ``batch_stats`` collection, so that they ride the train state
through the scanned epoch; ``epoch_counters`` reads them for the flight record):
a layer's held assignments and its hottest held expert's load over a
balanced router's, both of the last train step, and the assignments that
found no slot, summed over the run: always 0, because the layer works its
assignments in as many rounds as they need (``held_experts``: a loop whose
length is data), so imbalance costs time and never an assignment.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import flax.linen as nn
import jax
import jax.numpy as jnp

from hydragnn_tpu.data.tokens import COPY, INDEX, TOKEN
from hydragnn_tpu.ops.block_attention import attention_grid, attention_plan, block_attention, kernel_mode

HEAD_CHUNK_ROWS = 2048  # rows of logits alive at once: [2048, vocabulary] float32


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


class RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + self.eps)
        return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rotary(x, cos, sin, interleaved: bool = False):
    """Rotary embedding of ``x`` [N, heads, W] (all of its last axis: hand
    in the rotated dimensions alone), ``cos`` / ``sin`` [N, W / 2] float32
    from :func:`rotary_angles`. Pair ``i`` turns by angle ``i``; its two
    members are ``x[i]`` and ``x[i + W / 2]`` (half-split) or ``x[2i]`` and
    ``x[2i + 1]`` (``interleaved``)."""
    if interleaved:
        x32 = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
        x1, x2 = x32[..., 0], x32[..., 1]
        c, s = cos[:, None, :], sin[:, None, :]
        return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).reshape(x.shape).astype(x.dtype)
    half = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


def rotary_angles(index, width: int, theta: float):
    """``cos``, ``sin`` [N, width / 2] of a token at ``index`` for a rotated
    ``width``: pair ``i`` at ``index * theta ** (-2i / width)``, whatever
    the pairs' layout."""
    inv = 1.0 / (theta ** (jnp.arange(0, width, 2, dtype=jnp.float32) / width))
    ang = index.astype(jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


class Attention(nn.Module):
    cfg: Any

    @nn.compact
    def __call__(self, a, rows, plan):
        cfg = self.cfg
        n = a.shape[0]
        hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        doc, blk, cpy, cos, sin = rows

        def proj(features, name):
            return nn.Dense(features, use_bias=False, name=name)

        q = proj(hq * d, "q_proj")(a).reshape(n, hq, d)
        k = proj(hkv * d, "k_proj")(a).reshape(n, hkv, d)
        v = proj(hkv * d, "v_proj")(a).reshape(n, hkv, d)
        q = rotary(RMSNorm(cfg.rms_norm_eps, name="q_norm")(q), cos, sin)
        k = rotary(RMSNorm(cfg.rms_norm_eps, name="k_norm")(k), cos, sin)
        o = block_attention(q, k, v, doc, blk, cpy, scale=d**-0.5, plan=plan)
        return proj(cfg.hidden_dim, "o_proj")(o.reshape(n, hq * d))


ROUND_TILE = 512  # a round's assignments are a whole number of these


def round_rows(rows: int, cfg) -> int:
    """Assignments a round of the grouped product takes: what a balanced
    router sends to the held experts, in whole tiles. (Measured on the chip
    at the cell's shapes, PR 31: a layer in rounds of a quarter of that took
    1.7 times as long, forward and backward, at the same load.)"""
    share = rows * cfg.num_experts_per_tok * cfg.experts_held / cfg.num_experts
    return _round_up(max(int(share), 1), ROUND_TILE)


def _places(lo, plan, per_round: int, flat: int):
    """The sorted positions ``lo .. lo + per_round`` of a layer's held
    assignments: (assign ``[per_round]`` which of the ``flat`` choices sits
    at each place, ``flat`` past the last held one; sizes ``[held]`` each
    expert's run, cut to this round). ``plan``: (order ``[flat +
    per_round]`` the choices sorted by expert, the held ones first, padded
    by a round; first ``[held]`` where each expert's run starts; counts
    ``[held]``; held_total ``[]``). A place without an assignment holds a
    zero row and rides with the last expert."""
    order, first, counts, held_total = plan
    live = lo + jnp.arange(per_round) < held_total
    assign = jnp.where(live, jax.lax.dynamic_slice(order, (lo,), (per_round,)), flat)
    sizes = jnp.clip(first + counts - lo, 0, per_round) - jnp.clip(first - lo, 0, per_round)
    return assign, sizes.at[-1].add(per_round - sizes.sum())


def _products(x, weight, gate, up, down, sizes):
    """A round's rows ``x`` [per_round, hidden], sorted by expert in groups
    of ``sizes``, through their experts, times their router weights: float32.
    The three products are ``jax.lax.ragged_dot``, whose groups start anywhere."""
    act = jax.nn.silu(jax.lax.ragged_dot(x, gate, sizes)) * jax.lax.ragged_dot(x, up, sizes)
    return jax.lax.ragged_dot(act, down, sizes).astype(jnp.float32) * weight[:, None]


def _padded(m, weights):
    """A zero row and a zero weight behind the real ones, for the places without an assignment."""
    return (jnp.concatenate([m, jnp.zeros((1, m.shape[1]), m.dtype)]),
            jnp.concatenate([weights, jnp.zeros((1,), weights.dtype)]))


def _num_rounds(plan, per_round: int):
    return jnp.maximum(-(-plan[3] // per_round), 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def held_experts(m, weights, gate, up, down, plan, per_round: int, per_tok: int):
    """The held experts' part of the mixture for rows ``m`` [N, hidden]
    (float32 out), ``weights`` [N * per_tok] the renormalised router
    probabilities of every choice. The assignments to held experts are
    worked in ROUNDS of ``per_round``, as many as they need (a loop whose
    length is data: a balanced router needs one, a router that sends every
    row's every choice here all of them), so none is ever dropped and an
    empty round costs nothing. A round gathers its rows, multiplies, and
    adds its results into the rows' places. The backward runs the same
    rounds again, each recomputed and pulled back, and keeps nothing
    between them."""
    m_pad, w_pad = _padded(m, weights)

    def body(r, acc):
        assign, sizes = _places(r * per_round, plan, per_round, weights.shape[0])
        row = assign // per_tok
        return acc.at[row].add(_products(m_pad[row], w_pad[assign], gate, up, down, sizes))

    return jax.lax.fori_loop(0, _num_rounds(plan, per_round), body, jnp.zeros(m_pad.shape, jnp.float32))[:-1]


def _held_fwd(m, weights, gate, up, down, plan, per_round, per_tok):
    return held_experts(m, weights, gate, up, down, plan, per_round, per_tok), (m, weights, gate, up, down, plan)


def _held_bwd(per_round, per_tok, res, dout):
    m, weights, gate, up, down, plan = res
    m_pad, w_pad = _padded(m, weights)
    dout_pad = jnp.concatenate([dout, jnp.zeros((1, dout.shape[1]), dout.dtype)])

    def body(r, acc):
        assign, sizes = _places(r * per_round, plan, per_round, weights.shape[0])
        row = assign // per_tok
        _, pull = jax.vjp(lambda *a: _products(*a, sizes), m_pad[row], w_pad[assign], gate, up, down)
        dx, dw, *dmats = pull(dout_pad[row])
        gm, gw, *gmats = acc
        return (gm.at[row].add(dx.astype(jnp.float32)), gw.at[assign].add(dw.astype(jnp.float32)),
                *(a + g.astype(jnp.float32) for a, g in zip(gmats, dmats)))

    zero = tuple(jnp.zeros(a.shape, jnp.float32) for a in (m_pad, w_pad, gate, up, down))
    gm, gw, *gmats = jax.lax.fori_loop(0, _num_rounds(plan, per_round), body, zero)
    grads = (gm[:-1], gw[:-1], *gmats)
    return tuple(g.astype(a.dtype) for g, a in zip(grads, (m, weights, gate, up, down))) + (None,)


held_experts.defvjp(_held_fwd, _held_bwd)


class SwiGLU(nn.Module):
    """``W_down (silu(W_gate m) * W_up m)`` of ``width``, no bias: a dense
    layer, and the shared experts of an expert layer."""

    width: int

    @nn.compact
    def __call__(self, m):
        def proj(features, name):
            return nn.Dense(features, use_bias=False, name=name)

        act = jax.nn.silu(proj(self.width, "gate_proj")(m)) * proj(self.width, "up_proj")(m)
        return proj(m.shape[-1], "down_proj")(act)


class ExpertLayer(nn.Module):
    cfg: Any

    def _route(self, logits, valid):
        """(weights ``[N, per_tok]``, experts ``[N, per_tok]``) from the
        router's float32 ``logits`` [N, experts]: the configuration's
        scoring over ALL experts, the ``per_tok`` chosen (by score, or by
        score plus the balancing bias, which the weights never see), their
        scores renormalised to sum 1 and times ``routed_scaling_factor``.
        With the bias, a mutable pass (a train step) moves it against each
        expert's load over every expert of the router (auxiliary-loss-free
        balancing, DeepSeek-V3, arXiv:2412.19437): ``b += speed *
        sign(mean load - load)``; this step chose with the old one."""
        cfg = self.cfg
        total, per_tok = cfg.num_experts, cfg.num_experts_per_tok
        if cfg.scoring_func == "softmax" and not cfg.bias_update_speed:
            top_p, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), per_tok)
            top_p = top_p / top_p.sum(-1, keepdims=True)
            return (top_p * cfg.routed_scaling_factor if cfg.routed_scaling_factor != 1.0 else top_p), top_e
        scores = jax.nn.sigmoid(logits) if cfg.scoring_func == "sigmoid" else jax.nn.softmax(logits, axis=-1)
        bias = self.variable("batch_stats", "router_bias", lambda: jnp.zeros((total,), jnp.float32))
        _, top_e = jax.lax.top_k(scores + jax.lax.stop_gradient(bias.value), per_tok)
        top_p = jnp.take_along_axis(scores, top_e, axis=1)
        top_p = top_p / top_p.sum(-1, keepdims=True) * cfg.routed_scaling_factor
        if self.is_mutable_collection("batch_stats"):
            routed_v = self.variable("batch_stats", "routed_load_max_over_mean", lambda: jnp.zeros((), jnp.float32))
            if not self.is_initializing():
                load = jnp.bincount(jnp.where(valid[:, None], top_e, total).reshape(-1), length=total + 1)[:total]
                mean = valid.sum() * per_tok / total
                bias.value = bias.value + cfg.bias_update_speed * jnp.sign(mean - load).astype(jnp.float32)
                routed_v.value = load.max().astype(jnp.float32) / jnp.maximum(mean, 1.0)
        return top_p, top_e

    @nn.compact
    def __call__(self, m, valid):
        """``m`` [N, hidden]; ``valid`` [N] bool (padding rows route nowhere).
        Returns the held experts' part of the mixture [N, hidden]
        (:func:`held_experts`: in rounds of :func:`round_rows`), plus the
        shared experts' output where the configuration has them."""
        cfg = self.cfg
        n, hidden = m.shape
        total, per_tok, held, off = cfg.num_experts, cfg.num_experts_per_tok, cfg.experts_held, cfg.expert_offset
        width = cfg.moe_intermediate_size
        init = nn.initializers.variance_scaling(1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=(0,))
        router = self.param("router", nn.initializers.lecun_normal(), (hidden, total))
        gate = self.param("experts_gate", init, (held, hidden, width))
        up = self.param("experts_up", init, (held, hidden, width))
        down = self.param("experts_down", init, (held, width, hidden))

        top_p, top_e = self._route(jnp.dot(m, router.astype(m.dtype), preferred_element_type=jnp.float32), valid)

        # assignments to held experts first, sorted by expert; the others behind them
        local = top_e - off
        mine = (local >= 0) & (local < held) & valid[:, None]
        key = jnp.where(mine, local, held).reshape(-1)
        per_round = round_rows(n, cfg)
        order = jnp.pad(jnp.argsort(key, stable=True).astype(jnp.int32), (0, per_round))
        counts = (key[:, None] == jnp.arange(held)[None, :]).sum(0).astype(jnp.int32)
        first = jnp.cumsum(counts) - counts  # where each expert's run starts in the sorted list
        held_total = counts.sum()
        out = held_experts(m, top_p.reshape(-1), gate.astype(m.dtype), up.astype(m.dtype), down.astype(m.dtype),
                           (order, first, counts, held_total), per_round, per_tok)
        if cfg.n_shared_experts:  # every chip of the group computes these alike
            out = out + SwiGLU(cfg.n_shared_experts * width, name="shared_experts")(m).astype(jnp.float32)
        out = out.astype(m.dtype)

        if self.is_mutable_collection("batch_stats"):  # a train step or init; an eval pass counts nothing
            zero_f = lambda: jnp.zeros((), jnp.float32)  # noqa: E731
            held_v = self.variable("batch_stats", "held_assignments", zero_f)
            load_v = self.variable("batch_stats", "load_max_over_mean", zero_f)
            self.variable("batch_stats", "dropped", zero_f)  # stays 0: as many rounds as the assignments need
            if not self.is_initializing():  # init's values hang on no forward pass (models/create.py)
                held_v.value = held_total.astype(jnp.float32)
                load_v.value = counts.max().astype(jnp.float32) / jnp.maximum(valid.sum() * per_tok / total, 1.0)
        return out


class TokenLayer(nn.Module):
    cfg: Any

    @nn.compact
    def __call__(self, h, rows, plan, valid):
        cfg = self.cfg
        h = h + Attention(cfg, name="attention")(RMSNorm(cfg.rms_norm_eps, name="attention_norm")(h), rows, plan)
        return h + ExpertLayer(cfg, name="moe")(RMSNorm(cfg.rms_norm_eps, name="moe_norm")(h), valid)


def _head_rows(h, w, targets):
    logits = jnp.dot(h, w, preferred_element_type=jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
    return picked - lse, jnp.argmax(logits, axis=-1).astype(jnp.float32)


def vocabulary_head(h, w, targets):
    """``[N, 2]`` float32: log p(target) and the arg-max a row, from
    ``h`` [N, hidden] and ``w`` [hidden, vocabulary], a chunk of rows at a
    time; the backward recomputes a chunk's logits."""
    n = h.shape[0]
    chunks = max(n // HEAD_CHUNK_ROWS, 1)
    rows = _round_up(-(-n // chunks), 16)
    pad = chunks * rows - n
    hp = jnp.pad(h, ((0, pad), (0, 0))).reshape(chunks, rows, h.shape[1])
    tp = jnp.pad(targets, (0, pad)).reshape(chunks, rows)
    logp, best = jax.lax.map(lambda c: jax.checkpoint(_head_rows)(c[0], w, c[1]), (hp, tp))
    return jnp.stack([logp.reshape(-1)[:n], best.reshape(-1)[:n]], axis=1)


class TokenStack(nn.Module):
    cfg: Any

    @nn.compact
    def __call__(self, batch):
        cfg = self.cfg
        ids, index, cpy = batch.nodes[:, TOKEN], batch.nodes[:, INDEX], batch.nodes[:, COPY]
        if not jnp.issubdtype(ids.dtype, jnp.integer):
            raise TypeError(f"token documents carry int32 node features, got {ids.dtype}")
        doc, blk = batch.node_graph, index // cfg.block_length
        cos, sin = rotary_angles(index, cfg.head_dim, cfg.rope_theta)
        rows = (doc, blk, cpy, cos, sin)
        plan = attention_plan(doc, blk, cpy) if kernel_mode() != "xla" else None
        embedding = self.param("embedding", nn.initializers.normal(1.0), (cfg.vocab_size, cfg.hidden_dim))
        h = embedding[ids]
        layer = nn.remat(TokenLayer)  # a layer's activations at 16,384 rows do not fit four times over
        for i in range(cfg.num_conv_layers):
            h = layer(cfg, name=f"layer_{i}")(h, rows, plan, batch.node_mask)
        h = RMSNorm(cfg.rms_norm_eps, name="final_norm")(h)
        name = cfg.output_names[0]
        w = self.param("head", nn.initializers.lecun_normal(), (cfg.hidden_dim, cfg.vocab_size))
        return vocabulary_head(h, w.astype(h.dtype), batch.node_targets[name][:, 0])


# --------------------------------------------------------------------------
# the latent-attention stack: next-token training with extra prediction depths
# --------------------------------------------------------------------------


class LatentAttention(nn.Module):
    """Latent attention (MLA, DeepSeek-V2/V3) on normed rows ``a``: queries
    through a latent of ``q_lora_rank`` (normed), keys and values through a
    latent of ``kv_lora_rank`` (normed); each head's key is its own
    ``qk_nope_head_dim`` part and a ``qk_rope_head_dim`` part shared by all
    heads, which alone (and the queries' like part) carries the rotary
    embedding, its pairs interleaved (DeepSeek-V3's layout); values and
    outputs are ``v_head_dim`` wide. Training form: the latents are
    expanded to every head, nothing is absorbed."""

    cfg: Any

    @nn.compact
    def __call__(self, a, rows, plan):
        cfg = self.cfg
        n = a.shape[0]
        heads, nope, rope, dv = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        doc, blk, cpy, cos, sin = rows

        def proj(features, name):
            return nn.Dense(features, use_bias=False, name=name)

        def norm(name):
            return RMSNorm(cfg.rms_norm_eps, name=name)

        q = proj(heads * (nope + rope), "q_b_proj")(norm("q_a_norm")(proj(cfg.q_lora_rank, "q_a_proj")(a)))
        q = q.reshape(n, heads, nope + rope)
        latent = proj(cfg.kv_lora_rank + rope, "kv_a_proj")(a)
        kv = proj(heads * (nope + dv), "kv_b_proj")(norm("kv_a_norm")(latent[:, :cfg.kv_lora_rank]))
        kv = kv.reshape(n, heads, nope + dv)
        k_rope = rotary(latent[:, None, cfg.kv_lora_rank:], cos, sin, interleaved=True)
        q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], cos, sin, interleaved=True)], axis=-1)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope, (n, heads, rope))], axis=-1)
        o = block_attention(q, k, kv[..., nope:], doc, blk, cpy, scale=(nope + rope) ** -0.5, plan=plan)
        return proj(cfg.hidden_dim, "o_proj")(o.reshape(n, heads * dv))


class LatentLayer(nn.Module):
    """Pre-norm: latent attention, then a dense SwiGLU (``dense``) or an
    expert layer (routed experts held here plus the shared ones)."""

    cfg: Any
    dense: bool = False

    @nn.compact
    def __call__(self, h, rows, plan, valid):
        cfg = self.cfg
        h = h + LatentAttention(cfg, name="attention")(RMSNorm(cfg.rms_norm_eps, name="attention_norm")(h), rows, plan)
        m = RMSNorm(cfg.rms_norm_eps, name="ffn_norm")(h)
        if self.dense:
            return h + SwiGLU(cfg.intermediate_size, name="mlp")(m)
        return h + ExpertLayer(cfg, name="moe")(m, valid)


class NextTokenDepth(nn.Module):
    """One more prediction depth (DeepSeek-V3's multi-token prediction
    module): ``h' = W_eh [RMSNorm(h) ; RMSNorm(Emb(t))]`` from the previous
    depth's rows ``h`` (before their final norm) and the embedding of the
    token one further on, then one expert layer under the same mask.
    Returns (``h'``, its final norm), for the next depth and the head."""

    cfg: Any

    @nn.compact
    def __call__(self, h, emb, rows, plan, valid):
        cfg = self.cfg
        eps = cfg.rms_norm_eps
        x = jnp.concatenate([RMSNorm(eps, name="hidden_norm")(h), RMSNorm(eps, name="embedding_norm")(emb)], axis=-1)
        x = nn.Dense(cfg.hidden_dim, use_bias=False, name="eh_proj")(x)
        x = LatentLayer(cfg, name="layer")(x, rows, plan, valid)
        return x, RMSNorm(eps, name="final_norm")(x)


class LatentStack(nn.Module):
    """``Architecture.model_type: "LatentAttentionMoE"``: next-token
    training over ONE copy of every document (``data/tokens.py:
    next_token_samples``) under the document-causal mask (the block mask of
    ``ops/block_attention.py`` with blocks of one token and every row clean).
    Heads in ``output_names`` order: the main one (target ``t[i+1]``), then
    one a prediction depth (``t[i+1+d]``); depth ``d`` takes the embedding of
    head ``d - 1``'s target, and every head is the ONE embedding and the ONE
    head matrix. Returns a ``[N, 2]`` head output (:func:`vocabulary_head`)
    a head."""

    cfg: Any

    @nn.compact
    def __call__(self, batch):
        cfg = self.cfg
        ids, index, cpy = batch.nodes[:, TOKEN], batch.nodes[:, INDEX], batch.nodes[:, COPY]
        if not jnp.issubdtype(ids.dtype, jnp.integer):
            raise TypeError(f"token documents carry int32 node features, got {ids.dtype}")
        doc = batch.node_graph
        cos, sin = rotary_angles(index, cfg.qk_rope_head_dim, cfg.rope_theta)
        rows = (doc, index, cpy, cos, sin)  # blocks of one token: clean -> clean where index[j] <= index[i]
        plan = attention_plan(doc, index, cpy) if kernel_mode() != "xla" else None
        embedding = self.param("embedding", nn.initializers.normal(1.0), (cfg.vocab_size, cfg.hidden_dim))
        w = self.param("head", nn.initializers.lecun_normal(), (cfg.hidden_dim, cfg.vocab_size))
        h = embedding[ids]
        layer = nn.remat(LatentLayer)
        for i in range(cfg.num_conv_layers):
            h = layer(cfg, dense=i < cfg.first_k_dense_replace, name=f"layer_{i}")(h, rows, plan, batch.node_mask)
        w = w.astype(h.dtype)
        targets = [batch.node_targets[name][:, 0] for name in cfg.output_names]
        outputs = [vocabulary_head(RMSNorm(cfg.rms_norm_eps, name="final_norm")(h), w, targets[0])]
        depth = nn.remat(NextTokenDepth)
        for d in range(1, cfg.num_heads):
            h, normed = depth(cfg, name=f"mtp_{d}")(h, embedding[targets[d - 1]], rows, plan, batch.node_mask)
            outputs.append(vocabulary_head(normed, w, targets[d]))
        if cfg.num_heads > 1 and self.is_mutable_collection("batch_stats"):
            rows_v = self.variable("batch_stats", "mtp_rows", lambda: jnp.zeros((), jnp.float32))
            if not self.is_initializing():  # rows with a target at the deepest depth, last train step
                weight = batch.node_targets[cfg.output_names[-1] + "_weight"][:, 0]
                rows_v.value = (batch.node_mask & (weight > 0)).sum().astype(jnp.float32)
        return outputs


def manifest_block(cfg, rows: int) -> Dict[str, Any]:
    """``manifest["model"]["token_stack"]``: which stack, the experts held
    of how many, the vocabulary held, and the attention kernels' grid for
    ``rows`` row slots (``ops/block_attention.py:attention_grid``: the query
    and key-value heads a grid step works, the grid steps of a call); for
    the latent stack also the attention's kind, ranks and widths, the dense
    layers, the shared experts, the router's scoring and bias, and the
    prediction depths."""
    block = {
        "stack": cfg.model_type, "layers": cfg.num_conv_layers, "experts_held": cfg.experts_held,
        "experts": cfg.num_experts, "experts_per_token": cfg.num_experts_per_tok,
        "vocabulary_held": cfg.vocab_size, "block_length": cfg.block_length,
    }
    heads = (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.head_dim)
    if cfg.model_type == "LatentAttentionMoE":
        d_qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        heads = (cfg.num_attention_heads, cfg.num_attention_heads, d_qk, cfg.v_head_dim)
        block.update({
            "block_length": 1, "attention": "latent", "q_lora_rank": cfg.q_lora_rank,
            "kv_lora_rank": cfg.kv_lora_rank, "d_qk": d_qk,
            "d_rope": cfg.qk_rope_head_dim, "d_v": cfg.v_head_dim,
            "dense_layers": cfg.first_k_dense_replace, "shared_experts": cfg.n_shared_experts,
            "scoring": cfg.scoring_func, "routed_scaling_factor": cfg.routed_scaling_factor,
            "bias_update_speed": cfg.bias_update_speed, "mtp_depth": cfg.num_nextn_predict_layers,
        })
    block["attention_grid"] = attention_grid(*heads, rows)
    return {"model": {"token_stack": block}}


def epoch_counters(cfg, train_samples):
    """For the flight record's ``epoch`` event: the real rows through the
    epoch's train steps, the tokens they stand for (half of them under
    block diffusion, which holds two copies of every document), and the
    expert layers' counters: ``moe.held_assignments`` (all layers, last
    train step), ``moe.load_max_over_mean`` (the worst layer, last train
    step), ``moe.dropped`` (all layers, since the start of the run); where
    the router has a balancing bias also ``moe.routed_load_max_over_mean``
    (the hottest of ALL the router's experts over a balanced share, worst
    layer, last train step) and ``moe.bias_abs_max`` (the largest ``|b|``
    of any layer); with prediction depths ``mtp.rows`` (rows with a target
    at the deepest depth, last train step)."""
    rows = int(sum(s.num_nodes for s in train_samples))
    tokens = rows // 2 if cfg.token_objective == "block_diffusion" else rows

    def read(batch_stats) -> Dict[str, Any]:
        held, load, dropped = 0.0, 0.0, 0.0
        extra: Dict[str, Any] = {}
        for path, value in jax.tree_util.tree_flatten_with_path(batch_stats)[0]:
            leaf = jax.tree_util.keystr(path)
            v = jax.device_get(value)
            if "mtp_rows" in leaf:
                extra["mtp.rows"] = int(v)
            if "moe" not in leaf:
                continue
            if "router_bias" in leaf:
                extra["moe.bias_abs_max"] = max(extra.get("moe.bias_abs_max", 0.0), float(abs(v).max()))
            elif "routed_load_max_over_mean" in leaf:
                extra["moe.routed_load_max_over_mean"] = max(extra.get("moe.routed_load_max_over_mean", 0.0), float(v))
            elif "held_assignments" in leaf:
                held += float(v)
            elif "load_max_over_mean" in leaf:
                load = max(load, float(v))
            elif "dropped" in leaf:
                dropped += float(v)
        return {"rows": rows, "tokens": tokens, "moe.held_assignments": int(held),
                "moe.load_max_over_mean": load, "moe.dropped": int(dropped), **extra}

    return read
