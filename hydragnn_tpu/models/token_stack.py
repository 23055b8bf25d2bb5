"""The token stack: pre-norm decoder layers over the rows of packed token
documents, for ``Architecture.model_type: "BlockDiffusionMoE"``.

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
from hydragnn_tpu.ops.block_attention import attention_plan, block_attention, kernel_mode

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


def rotary(x, cos, sin):
    """Half-split rotary embedding: ``x`` [N, heads, D], ``cos`` / ``sin`` [N, D / 2] float32."""
    half = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


def rotary_angles(index, head_dim: int, theta: float):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
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


class ExpertLayer(nn.Module):
    cfg: Any

    @nn.compact
    def __call__(self, m, valid):
        """``m`` [N, hidden]; ``valid`` [N] bool (padding rows route nowhere).
        Returns the held experts' part of the mixture [N, hidden]
        (:func:`held_experts`: in rounds of :func:`round_rows`)."""
        cfg = self.cfg
        n, hidden = m.shape
        total, per_tok, held, off = cfg.num_experts, cfg.num_experts_per_tok, cfg.experts_held, cfg.expert_offset
        width = cfg.moe_intermediate_size
        init = nn.initializers.variance_scaling(1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=(0,))
        router = self.param("router", nn.initializers.lecun_normal(), (hidden, total))
        gate = self.param("experts_gate", init, (held, hidden, width))
        up = self.param("experts_up", init, (held, hidden, width))
        down = self.param("experts_down", init, (held, width, hidden))

        probs = jax.nn.softmax(jnp.dot(m, router.astype(m.dtype), preferred_element_type=jnp.float32), axis=-1)
        top_p, top_e = jax.lax.top_k(probs, per_tok)
        top_p = top_p / top_p.sum(-1, keepdims=True)

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
                           (order, first, counts, held_total), per_round, per_tok).astype(m.dtype)

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


def manifest_block(cfg) -> Dict[str, Any]:
    """``manifest["model"]["token_stack"]``: which stack, the experts held
    of how many, the vocabulary held."""
    return {"model": {"token_stack": {
        "stack": cfg.model_type, "layers": cfg.num_conv_layers, "experts_held": cfg.experts_held,
        "experts": cfg.num_experts, "experts_per_token": cfg.num_experts_per_tok,
        "vocabulary_held": cfg.vocab_size, "block_length": cfg.block_length,
    }}}


def epoch_counters(train_samples):
    """For the flight record's ``epoch`` event: the real rows through the
    epoch's train steps (both copies of every document), the tokens they
    stand for, and the expert layers' counters: ``moe.held_assignments``
    (all layers, last train step), ``moe.load_max_over_mean`` (the worst
    layer, last train step), ``moe.dropped`` (all layers, since the start
    of the run)."""
    rows = int(sum(s.num_nodes for s in train_samples))

    def read(batch_stats) -> Dict[str, Any]:
        held, load, dropped = 0.0, 0.0, 0.0
        for path, value in jax.tree_util.tree_flatten_with_path(batch_stats)[0]:
            leaf = jax.tree_util.keystr(path)
            if "moe" not in leaf:
                continue
            v = float(jax.device_get(value))
            if "held_assignments" in leaf:
                held += v
            elif "load_max_over_mean" in leaf:
                load = max(load, v)
            elif "dropped" in leaf:
                dropped += v
        return {"rows": rows, "tokens": rows // 2, "moe.held_assignments": int(held),
                "moe.load_max_over_mean": load, "moe.dropped": int(dropped)}

    return read
