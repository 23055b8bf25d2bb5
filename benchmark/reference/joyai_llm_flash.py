"""Plain reference of the latent-attention mixture-of-experts decoder
(`JoyAI-LLM-Flash`, ``model_type`` ``joyai_llm_flash``) on its training
path: next-token prediction with one multi-token-prediction depth.

Written from the published description: the keys of the model's
``config.json`` are DeepSeek-V3's layer (arXiv:2412.19437), whose equations
are these, on rows ``h`` (no bias anywhere, RMSNorm):

  attention   a = RMSNorm(h); q = RMSNorm(a W_dq) W_uq (heads of
              ``nope + rope``); [c_kv | k_r] = a W_dkv; [k_nope | v] =
              RMSNorm(c_kv) W_ukv; rotary embedding over the ``rope``
              dimensions of q and over k_r (shared by all heads), pairs
              interleaved, at the token's index in its own document;
              k = [k_nope | k_r]; o = softmax(q k^T / sqrt(nope + rope) + M) v
              under the document-causal mask; h += o W_o
  dense layer m = RMSNorm(h); h += W_down (silu(W_gate m) * W_up m)
  expert layer m = RMSNorm(h); s = sigmoid(m W_r) over ALL experts; the
              ``per_tok`` largest of s + b chosen, their s (not s + b)
              renormalised to sum 1 and times ``routed_scaling_factor``;
              h += SharedExpert(m) + sum_e g_e Expert_e(m)
  the bias    after every train step b_e += gamma * sign(mean load - load_e),
              the loads counted over the step's assignments to ALL experts
              (auxiliary-loss-free balancing); the step chose with the old b
  main head   RMSNorm, then W_head; target t[i+1]
  MTP depth 1 h' = W_eh [RMSNorm(h_L) ; RMSNorm(Emb(t[i+1]))], one more
              expert layer under the same mask, its own RMSNorm, the SHARED
              embedding and head; target t[i+2]
  loss        (L_main + lambda L_mtp) / (1 + lambda), each term a mean over
              the rows that have its target

Departures from the published description: the loss is divided by ``1 +
lambda`` (the program's multi-task weighting normalises its task weights to
sum 1; AdamW's update hardly sees a constant factor); on one chip the loads
of the bias rule are this chip's rows alone (a deployment adds the
data-parallel group's counts first); ``W_eh``'s input is [hidden ;
embedding] in the paper's order (a released checkpoint may store the two
halves the other way round: with weights drawn from a seed this is a
naming, not a difference).

float32, ``jax.numpy`` only, to be run under
``jax.default_matmul_precision("highest")``; a dense mask a document, a
plain loop over the experts held, ``jax.grad``. No kernel, no sorting, no
import from the program. It is given the same share as the program: the
experts ``offset .. offset + held`` of the router's ``experts`` (what the
absent experts would add is left out, and that partial result goes on to
the next layer; the shared expert is every chip's) and the vocabulary's
slice.

Rows: ``ids``, ``index`` (in its own document), ``target`` / ``weight``
(t[i+1]), ``target_mtp`` / ``weight_mtp`` (t[i+2]), ``valid``, all ``[N]``;
a step's documents are ``docs``: (first row, tokens) each. As in
``reference/sdar_moe.py``, attention is computed a document, a group of
heads and 128 query rows at a time, the experts one after another over a
chunk of rows, the head a chunk of rows at a time, and the expert layers
of the main stack are ONE compiled body scanned over their stacked
parameters.

``quant`` rounds every matrix product's operands to a lower precision
(``reference/common.py:_quantize``: ``"fp8"`` is the control for a
configuration that states bfloat16). ``fault`` plants what a broken step
would do: ``"half_batch"`` (the second half of the step's documents left
out of the loss and of its count), ``"mtp_next"`` (the MTP depth predicts
t[i+1] instead of t[i+2]), ``"softmax_router"`` (a softmax in place of the
sigmoid), ``"full_rope"`` (rotary embedding over the whole 192-wide head
instead of its 64 rope dimensions).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

from reference.common import _quantize, adamw

HEAD_GROUP = 8  # attention heads at a time
QUERY_ROWS = 128  # query rows at a time


@dataclasses.dataclass(frozen=True)
class Cfg:
    """What the equations read that the parameters' shapes do not say."""

    layers: int  # the main stack's, the dense ones among them
    dense: int  # leading dense layers
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    experts: int  # the router's width
    per_tok: int
    shared: int
    held: int  # experts computed here
    offset: int  # the first of them
    scaling: float
    gamma: float
    main_weight: float  # 1 over (1 + lambda)
    mtp_weight: float  # lambda over (1 + lambda)
    theta: float = 1e6
    eps: float = 1e-6


def cfg_from_architecture(arch: Dict[str, Any]) -> Cfg:
    w = [float(x) for x in arch["task_weights"]]
    return Cfg(
        layers=int(arch["num_conv_layers"]), dense=int(arch.get("first_k_dense_replace", 0)),
        heads=int(arch["num_attention_heads"]), kv_rank=int(arch["kv_lora_rank"]),
        nope=int(arch["qk_nope_head_dim"]), rope=int(arch["qk_rope_head_dim"]), v_dim=int(arch["v_head_dim"]),
        experts=int(arch["num_experts"]), per_tok=int(arch["num_experts_per_tok"]),
        shared=int(arch.get("n_shared_experts", 0)), held=int(arch["experts_held"]),
        offset=int(arch.get("expert_offset", 0)), scaling=float(arch.get("routed_scaling_factor", 1.0)),
        gamma=float(arch.get("bias_update_speed", 0.0)), main_weight=w[0] / sum(w), mtp_weight=w[1] / sum(w),
        theta=float(arch.get("rope_theta", 1e6)), eps=float(arch.get("rms_norm_eps", 1e-6)),
    )


def mm(x, w, quant=None):
    return _quantize(x, quant) @ _quantize(w, quant)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rope_interleaved(x, index, theta):
    """``x`` [n, heads, d]: the pairs (x[2i], x[2i+1]) turned by the angle
    ``index * theta ** (-2i / d)``, as complex numbers."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = index.astype(jnp.float32)[:, None, None] * inv
    z = (x[..., 0::2] + 1j * x[..., 1::2]) * jnp.exp(1j * ang)
    return jnp.stack([z.real, z.imag], axis=-1).reshape(x.shape).astype(x.dtype)


def swiglu(p, m, quant=None):
    return mm(jax.nn.silu(mm(m, p["gate_proj"]["kernel"], quant)) * mm(m, p["up_proj"]["kernel"], quant),
              p["down_proj"]["kernel"], quant)


def attention(p, a, rows, docs, cfg: Cfg, quant=None, fault=None):
    """The attention sub-layer's output ``o W_o`` for normed rows ``a``."""
    n, heads, nope, rope = a.shape[0], cfg.heads, cfg.nope, cfg.rope
    q = mm(rms_norm(mm(a, p["q_a_proj"]["kernel"], quant), p["q_a_norm"]["scale"], cfg.eps),
           p["q_b_proj"]["kernel"], quant).reshape(n, heads, nope + rope)
    latent = mm(a, p["kv_a_proj"]["kernel"], quant)
    kv = mm(rms_norm(latent[:, :cfg.kv_rank], p["kv_a_norm"]["scale"], cfg.eps), p["kv_b_proj"]["kernel"],
            quant).reshape(n, heads, nope + cfg.v_dim)
    k_rope = jnp.broadcast_to(latent[:, None, cfg.kv_rank:], (n, heads, rope))
    if fault == "full_rope":
        q = rope_interleaved(q, rows["index"], cfg.theta)
        k = rope_interleaved(jnp.concatenate([kv[..., :nope], k_rope], axis=-1), rows["index"], cfg.theta)
    else:
        q = jnp.concatenate([q[..., :nope], rope_interleaved(q[..., nope:], rows["index"], cfg.theta)], axis=-1)
        k = jnp.concatenate([kv[..., :nope], rope_interleaved(k_rope, rows["index"], cfg.theta)], axis=-1)
    v = kv[..., nope:]
    scale = 1.0 / jnp.sqrt(jnp.float32(nope + rope))
    rows_of = jnp.concatenate([start + jnp.arange(tokens) for start, tokens in docs])
    qs, ks, vs, index = q[rows_of], k[rows_of], v[rows_of], rows["index"][rows_of]
    group = min(HEAD_GROUP, heads)
    groups = heads // group
    outs, at = [], 0
    for _, size in docs:
        here = slice(at, at + size)
        idx = index[here]
        mask = idx[None, :] <= idx[:, None]  # query row i, key row j of ONE document

        @jax.checkpoint  # the backward recomputes a document's scores
        def one_document(qd, kd, vd, mask):
            size = qd.shape[0]
            c = next(c for c in (QUERY_ROWS, 64, 32, 16, 8, 4, 2, 1) if size % c == 0)
            mask = mask.reshape(size // c, c, size)

            def one_group(args):
                qg, kg, vg = args  # [size / c, c, group, d], [size, group, d], [size, group, dv]
                kg, vg = _quantize(kg, quant), _quantize(vg, quant)

                @jax.checkpoint
                def some_queries(b):
                    qc, mc = b  # [c, group, d], [c, size]
                    s = jnp.einsum("igd,jgd->gij", _quantize(qc, quant), kg) * scale
                    w = jax.nn.softmax(jnp.where(mc[None], s, -jnp.inf), axis=-1)
                    return jnp.einsum("gij,jgd->igd", _quantize(w, quant), vg)

                return jax.lax.map(some_queries, (qg, mask))

            qd = qd.reshape(size // c, c, groups, group, nope + rope).transpose(2, 0, 1, 3, 4)
            kd = kd.reshape(size, groups, group, nope + rope).transpose(1, 0, 2, 3)
            vd = vd.reshape(size, groups, group, cfg.v_dim).transpose(1, 0, 2, 3)
            out = jax.lax.map(one_group, (qd, kd, vd))  # [groups, size / c, c, group, dv]
            return out.transpose(1, 2, 0, 3, 4).reshape(size, heads, cfg.v_dim)

        outs.append(one_document(qs[here], ks[here], vs[here], mask))
        at += size
    o = jnp.zeros((n, heads, cfg.v_dim), q.dtype).at[rows_of].set(jnp.concatenate(outs))
    return mm(o.reshape(n, heads * cfg.v_dim), p["o_proj"]["kernel"], quant)


def routing(p, m, bias, cfg: Cfg, quant=None, fault=None):
    """([N, experts] each expert's weight in the row's mixture, 0 where not
    chosen; [N, experts] 1 where chosen)."""
    logits = mm(m, p["router"], quant)
    s = jax.nn.softmax(logits, axis=-1) if fault == "softmax_router" else jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + bias, cfg.per_tok)
    chosen = jax.nn.one_hot(idx, cfg.experts, dtype=s.dtype).sum(1)
    w = chosen * s
    return w / w.sum(-1, keepdims=True) * cfg.scaling, chosen


def experts(p, m, weights, cfg: Cfg, quant=None):
    """The part of the mixture that the held experts give (the stacked
    parameters hold exactly those), a chunk of rows at a time."""
    mine = weights[:, cfg.offset:cfg.offset + cfg.held].T  # [held, N]

    def some_rows(args):
        m_, mine_ = args

        @jax.checkpoint
        def one_expert(y, e):
            w_gate, w_up, w_down, r = e
            return y + r[:, None] * mm(jax.nn.silu(mm(m_, w_gate, quant)) * mm(m_, w_up, quant), w_down, quant), None

        return jax.lax.scan(one_expert, jnp.zeros_like(m_), (p["experts_gate"], p["experts_up"], p["experts_down"], mine_))[0]

    n = m.shape[0]
    chunks = max(n // 4096, 1)
    pad = -n % chunks
    mp = jnp.pad(m, ((0, pad), (0, 0))).reshape(chunks, -1, m.shape[1])
    wp = jnp.pad(mine, ((0, 0), (0, pad))).reshape(cfg.held, chunks, -1).transpose(1, 0, 2)
    return jax.lax.map(jax.checkpoint(some_rows), (mp, wp)).reshape(-1, m.shape[1])[:n]


def moe(p, m, bias, valid, cfg: Cfg, quant=None, fault=None):
    """(the expert layer's output: the held experts' part plus the shared
    expert, the bias after this step's rule, the real rows' assignments to
    the held experts)."""
    weights, chosen = routing(p, m, bias, cfg, quant, fault)
    y = experts(p, m, weights, cfg, quant)
    if cfg.shared:
        y = y + swiglu(p["shared_experts"], m, quant)
    chosen = jax.lax.stop_gradient(chosen) * valid[:, None]
    load = chosen.sum(0)
    mean = valid.sum() * cfg.per_tok / cfg.experts
    new_bias = bias + cfg.gamma * jnp.sign(mean - load)
    return y, new_bias, chosen[:, cfg.offset:cfg.offset + cfg.held].sum()


def layer(p, h, bias, rows, docs, cfg: Cfg, quant=None, fault=None, dense=False):
    """(rows after the layer, the bias after the rule, held assignments)."""
    h = h + attention(p["attention"], rms_norm(h, p["attention_norm"]["scale"], cfg.eps), rows, docs, cfg, quant, fault)
    m = rms_norm(h, p["ffn_norm"]["scale"], cfg.eps)
    if dense:
        return h + swiglu(p["mlp"], m, quant), bias, jnp.zeros((), jnp.float32)
    y, bias, held = moe(p["moe"], m, bias, rows["valid"], cfg, quant, fault)
    return h + y, bias, held


def stack_layers(params, cfg: Cfg, stack=jnp.stack):
    """The program's tree with the main stack's expert layers
    (``layer_<dense>`` .. ``layer_<layers-1>``) stacked under ``layers``: the
    layout :func:`forward` scans. A caller that follows many steps keeps its
    state in this layout (``stack=np.stack`` on the host)."""
    t = params["tokens"]
    moe_layers = [f"layer_{i}" for i in range(cfg.dense, cfg.layers)]
    rest = {k: v for k, v in t.items() if k not in moe_layers}
    rest["layers"] = jax.tree_util.tree_map(lambda *xs: stack(xs), *[t[k] for k in moe_layers])
    return {"tokens": rest}


def unstack_layers(params, cfg: Cfg):
    """Back to the program's names (views of the stacked arrays on the host)."""
    t = dict(params["tokens"])
    stacked = t.pop("layers")
    for j, i in enumerate(range(cfg.dense, cfg.layers)):
        t[f"layer_{i}"] = jax.tree_util.tree_map(lambda x: x[j], stacked)
    return {"tokens": t}


def zero_bias(cfg: Cfg):
    """The balancing bias of every expert layer: the main stack's (stacked)
    and the MTP depth's."""
    return {"layers": jnp.zeros((cfg.layers - cfg.dense, cfg.experts), jnp.float32),
            "mtp": jnp.zeros((cfg.experts,), jnp.float32)}


def forward(params, bias, rows, docs, cfg: Cfg, quant=None, fault=None):
    """(normed rows in front of the main head, normed rows of the MTP depth,
    the bias after this step, held assignments over all expert layers)."""
    t = params["tokens"]
    stacked = t["layers"] if "layers" in t else stack_layers(params, cfg)["tokens"]["layers"]
    h = t["embedding"][rows["ids"]]
    for i in range(cfg.dense):
        h, _, _ = jax.checkpoint(lambda h_, p: layer(p, h_, None, rows, docs, cfg, quant, fault, dense=True))(h, t[f"layer_{i}"])

    def body(h_, pb):
        p, b = pb
        h_, b, held = layer(p, h_, b, rows, docs, cfg, quant, fault)
        return h_, (b, held)

    h, (main_bias, held) = jax.lax.scan(jax.checkpoint(body), h, (stacked, bias["layers"]))
    d = t["mtp_1"]
    emb = t["embedding"][rows["target"]]
    x = jnp.concatenate([rms_norm(h, d["hidden_norm"]["scale"], cfg.eps), rms_norm(emb, d["embedding_norm"]["scale"], cfg.eps)], axis=-1)
    x = mm(x, d["eh_proj"]["kernel"], quant)
    x, mtp_bias, mtp_held = jax.checkpoint(lambda x_, p, b: layer(p, x_, b, rows, docs, cfg, quant, fault))(x, d["layer"], bias["mtp"])
    return (rms_norm(h, t["final_norm"]["scale"], cfg.eps), rms_norm(x, d["final_norm"]["scale"], cfg.eps),
            {"layers": main_bias, "mtp": mtp_bias}, held.sum() + mtp_held)


def target_log_probs(h, w_head, target, quant=None, chunk_rows: int = 2048):
    """[N]: log p(target) under the head's softmax, a chunk of rows at a
    time (and recomputed in the backward)."""
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


def log_probs(params, bias, rows, docs, cfg: Cfg):
    """([N, vocab], [N, vocab]): the two heads' log-softmax over the slice
    held, in one piece (for tests at a small size)."""
    main, mtp, _, _ = forward(params, bias, rows, docs, cfg)
    w = params["tokens"]["head"]
    return jax.nn.log_softmax(main @ w, axis=-1), jax.nn.log_softmax(mtp @ w, axis=-1)


def losses_and_state(params, bias, rows, docs, cfg: Cfg, quant=None, fault=None):
    """(weighted loss, (main loss, MTP loss, the bias after this step, held
    assignments)). Each head's loss is ``-sum(log p(target))`` over the real
    rows that have its target, divided by their number."""
    main, mtp, new_bias, held = forward(params, bias, rows, docs, cfg, quant, fault)
    w_head = params["tokens"]["head"]
    keep = rows["valid"]
    if fault == "half_batch":
        keep = keep & rows["first_half"]
    mtp_target, mtp_weight = (rows["target"], rows["weight"]) if fault == "mtp_next" else (rows["target_mtp"], rows["weight_mtp"])

    def head_loss(h, target, weight):
        w = jnp.where(keep, weight, 0.0)
        logp = target_log_probs(h, w_head, target, quant)
        return -(w * logp).sum() / jnp.maximum((w > 0).sum().astype(jnp.float32), 1.0)

    l_main = head_loss(main, rows["target"], rows["weight"])
    l_mtp = head_loss(mtp, mtp_target, mtp_weight)
    return cfg.main_weight * l_main + cfg.mtp_weight * l_mtp, (l_main, l_mtp, new_bias, held)


def loss_fn(params, bias, rows, docs, cfg: Cfg, quant=None, fault=None):
    return losses_and_state(params, bias, rows, docs, cfg, quant, fault)[0]


def _docs(batch, sizes):
    return [(batch["starts"][i], int(n)) for i, n in enumerate(sizes)]


def make_step(cfg: Cfg, sizes: Sequence[int], quant=None, fault=None):
    """One jitted optimizer step: (params, mu, nu, bias, t, rate, batch) ->
    (params, mu, nu, bias, loss, held). ``batch``: {"rows": ...,
    "starts": [documents] first rows, in the order of ``sizes``} (``sizes``
    static: the documents' tokens). The state is donated."""

    def step(params, mu, nu, bias, t, rate, batch):
        (loss, (_, _, bias, held)), grads = jax.value_and_grad(
            lambda p: losses_and_state(p, bias, batch["rows"], _docs(batch, sizes), cfg, quant, fault), has_aux=True
        )(params)
        params, mu, nu = adamw(params, grads, mu, nu, t, rate)
        return params, mu, nu, bias, loss, held

    return jax.jit(step, donate_argnums=(0, 1, 2, 3))
