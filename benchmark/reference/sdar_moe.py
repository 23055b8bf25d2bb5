"""Plain reference of the block-diffusion mixture-of-experts decoder
(`SDAR-30B-A3B-Chat`, ``model_type`` ``sdar_moe``) on its training path.

Written from the published description: the layer of the model's
``config.json`` (grouped-query attention with a norm over each head of q
and k, rotary embedding over the whole head in the half-split form, a
mixture of experts routed by a float32 softmax over ALL experts of which
the largest ``per_tok`` are renormalised to sum 1, no shared expert, no
bias, RMSNorm) under the block-diffusion objective of SDAR
(arXiv:2510.06303) after BD3-LM (arXiv:2503.09573): a document is seen
twice in one pass, noised and clean, under a mask that is bidirectional in
a block and causal across blocks, and the loss is ``-(1/t) log p(x0)`` over
the masked rows of the noised copy, divided by the tokens of the step.

float32, ``jax.numpy`` only, to be run under
``jax.default_matmul_precision("highest")``; a dense mask a document, a
plain loop over the experts held, ``jax.grad``. No kernel, no sorting, no
import from the program. It is given the same share as the program: the
experts ``offset .. offset + held`` of the router's ``experts`` (what the
absent experts would add is left out, and that partial result goes on to
the next layer) and the vocabulary's slice.

Rows: ``ids`` (the token as the model sees it), ``index`` (in its own
document), ``cpy`` (1 noised, 0 clean), ``target``, ``weight``, ``valid``,
all ``[N]``; a step's documents are ``docs``: (first row, tokens) each,
``2 * tokens`` rows from there (the noised copy, then the clean copy).
Attention is computed a document, a key-value head and 128 query rows at a
time, so that the dense scores fit (``[group, 128, 2n]``; sixteen documents'
whole score matrices side by side took 5.5 GB), the experts one after
another over a chunk of rows, and the head's log-softmax a chunk of rows at
a time (loops of one compiled
body each: the program of a whole step stays small enough to compile in a
minute and to run beside 16 bytes a parameter of state); ``tokens`` is
static and the first row may be traced, so that steps which hold the same
lengths in another order are one compiled program.

``quant`` rounds every matrix product's operands to a lower precision
(``reference/common.py:_quantize``: ``"fp8"`` is the control for a
configuration that states bfloat16). ``fault`` plants what a broken step
would do: ``"causal_mask"`` (a plain causal mask over each copy),
``"lost_expert"`` (the first held expert's part left out), ``"half_batch"``
(the second half of the step's documents left out of the loss and of its
count).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from reference.common import _quantize, adamw


@dataclasses.dataclass(frozen=True)
class Cfg:
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int  # the router's width
    per_tok: int
    expert_width: int
    held: int  # experts computed here
    offset: int  # the first of them
    vocab: int
    block: int
    theta: float = 1e6
    eps: float = 1e-6


def cfg_from_architecture(arch: Dict[str, Any]) -> Cfg:
    return Cfg(
        layers=int(arch["num_conv_layers"]), hidden=int(arch["hidden_dim"]),
        heads=int(arch["num_attention_heads"]), kv_heads=int(arch["num_key_value_heads"]),
        head_dim=int(arch["head_dim"]), experts=int(arch["num_experts"]),
        per_tok=int(arch["num_experts_per_tok"]), expert_width=int(arch["moe_intermediate_size"]),
        held=int(arch["experts_held"]), offset=int(arch.get("expert_offset", 0)),
        vocab=int(arch["vocab_size"]), block=int(arch.get("block_length", 4)),
        theta=float(arch.get("rope_theta", 1e6)), eps=float(arch.get("rms_norm_eps", 1e-6)),
    )


def mm(x, w, quant=None):
    return _quantize(x, quant) @ _quantize(w, quant)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rope(x, index, theta):
    """``x`` [n, heads, d]: rotate the halves (x1, x2) by the angle
    ``index * theta ** (-2i / d)``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = index.astype(jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def document_mask(index, cpy, block: int, fault=None):
    """[rows, rows] of ONE document: query row i, key row j."""
    b = index // block
    qb, kb, qc, kc = b[:, None], b[None, :], cpy[:, None], cpy[None, :]
    if fault == "causal_mask":
        return (qc == kc) & (index[None, :] <= index[:, None])
    noised_noised = (qc == 1) & (kc == 1) & (qb == kb)
    noised_clean = (qc == 1) & (kc == 0) & (kb < qb)
    clean_clean = (qc == 0) & (kc == 0) & (kb <= qb)
    return noised_noised | noised_clean | clean_clean


def attention(p, a, rows, docs, cfg: Cfg, quant=None, fault=None):
    """The attention sub-layer's output ``o Wo`` for normed rows ``a``."""
    n = a.shape[0]
    q = mm(a, p["q_proj"]["kernel"], quant).reshape(n, cfg.heads, cfg.head_dim)
    k = mm(a, p["k_proj"]["kernel"], quant).reshape(n, cfg.kv_heads, cfg.head_dim)
    v = mm(a, p["v_proj"]["kernel"], quant).reshape(n, cfg.kv_heads, cfg.head_dim)
    q = rope(rms_norm(q, p["q_norm"]["scale"], cfg.eps), rows["index"], cfg.theta)
    k = rope(rms_norm(k, p["k_norm"]["scale"], cfg.eps), rows["index"], cfg.theta)
    group = cfg.heads // cfg.kv_heads
    scale = 1.0 / jnp.sqrt(jnp.float32(cfg.head_dim))
    # the documents' rows, one document after another in the order of `docs`: a
    # document is then a static slice (its first row in the batch may be traced)
    rows_of = jnp.concatenate([start + jnp.arange(2 * tokens) for start, tokens in docs])
    qs, ks, vs, index, cpy = q[rows_of], k[rows_of], v[rows_of], rows["index"][rows_of], rows["cpy"][rows_of]
    outs, at = [], 0
    for _, tokens in docs:
        size = 2 * tokens
        here = slice(at, at + size)
        mask = document_mask(index[here], cpy[here], cfg.block, fault)

        @jax.checkpoint  # the backward recomputes a document's scores: they are not kept for all documents at once
        def one_document(qd, kd, vd, mask):
            size = qd.shape[0]
            c = next(c for c in (128, 64, 32, 16, 8, 4, 2, 1) if size % c == 0)  # query rows at a time
            mask = mask.reshape(size // c, c, size)

            def one_kv_head(args):
                qh, kh, vh = args  # [size / c, c, group, d], [size, d], [size, d]
                kh, vh = _quantize(kh, quant), _quantize(vh, quant)

                @jax.checkpoint
                def some_queries(a):
                    qc, mc = a  # [c, group, d], [c, size]
                    s = jnp.einsum("igd,jd->gij", _quantize(qc, quant), kh) * scale
                    w = jax.nn.softmax(jnp.where(mc[None], s, -jnp.inf), axis=-1)
                    return jnp.einsum("gij,jd->igd", _quantize(w, quant), vh)

                return jax.lax.map(some_queries, (qh, mask))

            qd = qd.reshape(size // c, c, cfg.kv_heads, group, cfg.head_dim)
            out = jax.lax.map(one_kv_head, (qd.transpose(2, 0, 1, 3, 4), kd.transpose(1, 0, 2), vd.transpose(1, 0, 2)))
            return out.transpose(1, 2, 0, 3, 4).reshape(size, cfg.heads, cfg.head_dim)

        outs.append(one_document(qs[here], ks[here], vs[here], mask))
        at += size
    o = jnp.zeros_like(q).at[rows_of].set(jnp.concatenate(outs))
    return mm(o.reshape(n, cfg.heads * cfg.head_dim), p["o_proj"]["kernel"], quant)


def routing(p, m, cfg: Cfg, quant=None):
    """[N, experts]: the renormalised probability of each expert among the
    row's ``per_tok`` largest, 0 for the others."""
    r = jax.nn.softmax(mm(m, p["router"], quant), axis=-1)
    top, idx = jax.lax.top_k(r, cfg.per_tok)
    top = top / top.sum(-1, keepdims=True)
    return (jax.nn.one_hot(idx, cfg.experts, dtype=r.dtype) * top[..., None]).sum(1)


def experts(p, m, cfg: Cfg, quant=None, fault=None, held: Optional[Tuple[int, int]] = None, weights=None):
    """The part of the mixture that the experts ``offset .. offset + count``
    give (``held``: (offset, count); default the configuration's). The
    stacked parameters hold exactly those experts."""
    offset, count = held if held is not None else (cfg.offset, cfg.held)
    if weights is None:
        weights = routing(p, m, cfg, quant)
    mine = weights[:, offset:offset + count].T  # [count, N]
    if fault == "lost_expert":
        mine = mine.at[0].set(0.0)

    def some_rows(args):
        m_, mine_ = args  # [rows, hidden], [count, rows]

        @jax.checkpoint
        def one_expert(y, e):
            w_gate, w_up, w_down, r = e
            return y + r[:, None] * mm(jax.nn.silu(mm(m_, w_gate, quant)) * mm(m_, w_up, quant), w_down, quant), None

        # plain: one expert after another over every row
        return jax.lax.scan(one_expert, jnp.zeros_like(m_), (p["experts_gate"], p["experts_up"], p["experts_down"], mine_))[0]

    # a chunk of rows at a time (the scan keeps its running sum for every expert: 16 x [rows, hidden])
    n = m.shape[0]
    chunks = max(n // 4096, 1)
    pad = -n % chunks
    mp = jnp.pad(m, ((0, pad), (0, 0))).reshape(chunks, -1, m.shape[1])
    wp = jnp.pad(mine, ((0, 0), (0, pad))).reshape(count, chunks, -1).transpose(1, 0, 2)
    return jax.lax.map(jax.checkpoint(some_rows), (mp, wp)).reshape(-1, m.shape[1])[:n]


def layer(p, h, rows, docs, cfg: Cfg, quant=None, fault=None):
    """(rows after the layer, [N, experts] which experts each row was sent to)."""
    h = h + attention(p["attention"], rms_norm(h, p["attention_norm"]["scale"], cfg.eps), rows, docs, cfg, quant, fault)
    m = rms_norm(h, p["moe_norm"]["scale"], cfg.eps)
    weights = routing(p["moe"], m, cfg, quant)
    return h + experts(p["moe"], m, cfg, quant, fault, weights=weights), jax.lax.stop_gradient(weights > 0)


def stack_layers(params, layers: int, stack=jnp.stack):
    """The program's tree (``layer_0`` .. ``layer_<n-1>``) with the layers'
    parameters stacked under ``layers``: the layout :func:`hidden` scans.
    A caller that follows many steps keeps its state in this layout (with
    ``stack=np.stack`` on the host), so that no step stacks a copy."""
    t = params["tokens"]
    rest = {k: v for k, v in t.items() if not k.startswith("layer_")}
    rest["layers"] = jax.tree_util.tree_map(lambda *xs: stack(xs), *[t[f"layer_{i}"] for i in range(layers)])
    return {"tokens": rest}


def unstack_layers(params, layers: int):
    """Back to the program's names (views of the stacked arrays on the host)."""
    t = dict(params["tokens"])
    stacked = t.pop("layers")
    for i in range(layers):
        t[f"layer_{i}"] = jax.tree_util.tree_map(lambda x: x[i], stacked)
    return {"tokens": t}


def hidden(params, rows, docs, cfg: Cfg, quant=None, fault=None):
    """(normed rows in front of the head, [layers, N, experts] the routed
    sets). The layers are alike, so they are ONE compiled body scanned over
    their stacked parameters (and recomputed in the backward): a fourth of
    the program to compile."""
    t = params["tokens"]
    stacked = t["layers"] if "layers" in t else stack_layers(params, cfg.layers)["tokens"]["layers"]
    h, routed = jax.lax.scan(
        jax.checkpoint(lambda h_, p: layer(p, h_, rows, docs, cfg, quant, fault)), t["embedding"][rows["ids"]], stacked
    )
    return rms_norm(h, t["final_norm"]["scale"], cfg.eps), routed


def log_probs(params, rows, docs, cfg: Cfg, quant=None, fault=None):
    """[N, vocab] log-softmax of the head over the slice held."""
    h, _ = hidden(params, rows, docs, cfg, quant, fault)
    return jax.nn.log_softmax(mm(h, params["tokens"]["head"], quant), axis=-1)


def target_log_probs(h, w_head, target, quant=None, chunk_rows: int = 2048):
    """[N]: log p(target) under the head's softmax, a chunk of rows at a
    time (and recomputed in the backward), so that [N, vocab] is never
    held whole; :func:`log_probs` is the same numbers in one piece."""
    n = h.shape[0]
    chunks = max(n // chunk_rows, 1)
    pad = -n % chunks

    @jax.checkpoint
    def one_chunk(args):
        hc, tc = args
        return jnp.take_along_axis(jax.nn.log_softmax(mm(hc, w_head, quant), axis=-1), tc[:, None], axis=1)[:, 0]

    hp = jnp.pad(h, ((0, pad), (0, 0))).reshape(chunks, -1, h.shape[1])
    tp = jnp.pad(target, (0, pad)).reshape(chunks, -1)
    return jax.lax.map(one_chunk, (hp, tp)).reshape(-1)[:n]


def loss_and_held(params, rows, docs, cfg: Cfg, quant=None, fault=None):
    """``-sum(weight * log p(target)) / tokens`` over the real rows (the
    tokens of a step are the real rows of the noised copy), and the real
    rows' assignments to the experts held, over all layers."""
    h, routed = hidden(params, rows, docs, cfg, quant, fault)
    logp = target_log_probs(h, params["tokens"]["head"], rows["target"], quant)
    keep = rows["valid"]
    if fault == "half_batch":
        keep = keep & rows["first_half"]
    w = jnp.where(keep, rows["weight"], 0.0)
    tokens = jnp.maximum((keep & (rows["cpy"] == 1)).sum().astype(jnp.float32), 1.0)
    held = (routed[:, :, cfg.offset:cfg.offset + cfg.held] & rows["valid"][None, :, None]).sum()
    return -(w * logp).sum() / tokens, held


def loss_fn(params, rows, docs, cfg: Cfg, quant=None, fault=None):
    return loss_and_held(params, rows, docs, cfg, quant, fault)[0]


def _docs(batch, sizes):
    return [(batch["starts"][i], int(n)) for i, n in enumerate(sizes)]


def routing_flips(params, batch, sizes, cfg: Cfg):
    """The share of real rows that some layer sends to another set of
    experts when every matrix product's operands are rounded to bfloat16."""
    docs = _docs(batch, sizes)

    @jax.jit  # one program for both passes
    def moved_rows(p, r):
        return (hidden(p, r, docs, cfg)[1] != hidden(p, r, docs, cfg, "bf16")[1]).any(axis=(0, 2))

    moved = moved_rows(params, batch["rows"])
    valid = batch["rows"]["valid"]
    return (moved & valid).sum() / jnp.maximum(valid.sum(), 1)


def make_step(cfg: Cfg, sizes: Sequence[int], quant=None, fault=None, count_held: bool = False):
    """One jitted optimizer step: (params, mu, nu, t, rate, batch) ->
    (params, mu, nu, loss), ``reference/common.py:follow``'s contract, and
    with ``count_held`` the step's assignments to held experts behind the
    loss. ``batch``: {"rows": ..., "starts": [documents] first rows, in the
    order of ``sizes``} (``sizes`` static: the documents' tokens). The
    state is donated: at 16 bytes a parameter two copies do not fit."""

    def step(params, mu, nu, t, rate, batch):
        (loss, held), grads = jax.value_and_grad(
            lambda p: loss_and_held(p, batch["rows"], _docs(batch, sizes), cfg, quant, fault), has_aux=True
        )(params)
        if fault != "state_unchanged":
            params, mu, nu = adamw(params, grads, mu, nu, t, rate)
        return (params, mu, nu, loss, held) if count_held else (params, mu, nu, loss)

    return jax.jit(step, donate_argnums=(0, 1, 2))
