"""The main path's kernels, compiled by the TPU's own compiler at the
flagship's width — for a chip that is described, not attached.

Interpret mode (every other Pallas test here) cannot show what Mosaic
refuses: a slice off the tiling, too much scoped VMEM, a kernel that
cannot lower. These compiles can, in about a second each and with no
chip. Nothing runs, so nothing here says a kernel is right or fast.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every xdist
worker imports every test file. Keep these tests in this one file.
"""

import importlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# flagship batch (hydragnn_tpu/flagship.py at bench.py's sizes: batch
# 1024 of BCC cells (2, 4), run-aligned): edges, nodes, sender-window blocks
E, N, NB, H = 811_008, 32_752, 256, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _kernels(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; the number of Mosaic
    kernels (``tpu_custom_call``) in the result."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text().count("tpu_custom_call")


def _sp():
    return importlib.import_module("hydragnn_tpu.ops.segment_pallas")


def _fc():
    # the package re-exports a function under the module's name
    return importlib.import_module("hydragnn_tpu.ops.fused_conv")


DTYPES = [pytest.param(jnp.float32, id="f32"), pytest.param(jnp.bfloat16, id="bf16")]
i32, b1, f32 = jnp.int32, jnp.bool_, jnp.float32


@pytest.mark.parametrize("dt", DTYPES)
def test_family_kernel_compiles(one_chip, dt):
    sp = _sp()
    fn = lambda d, i, m: sp._csr_kernel_call(d, i, m, N, False, True)
    assert _kernels(fn, one_chip, ((E, H), dt), ((E,), i32), ((E,), b1)) == 1


@pytest.mark.parametrize("dt", DTYPES)
def test_sum_kernel_compiles(one_chip, dt):
    sp = _sp()
    fn = lambda d, i: sp._csr_kernel_call(d, i, None, N, False, False)
    assert _kernels(fn, one_chip, ((E, H), dt), ((E,), i32)) == 1


@pytest.mark.parametrize("dt", DTYPES)
@pytest.mark.parametrize("sorted_ids", [True, False], ids=["sorted", "local"])
def test_gather_kernel_compiles(one_chip, dt, sorted_ids):
    sp = _sp()
    fn = lambda t, i: sp._bcast_kernel_call(t, i, False, sorted_ids=sorted_ids)
    assert _kernels(fn, one_chip, ((N, H), dt), ((E,), i32)) == 1


@pytest.mark.parametrize("dt", DTYPES)
def test_gather_stats_kernel_compiles(one_chip, dt):
    sp = _sp()
    fn = lambda t, i, m: sp._gather_stats_call(t, i, m, 8, False)
    assert _kernels(fn, one_chip, ((N, H), dt), ((E,), i32), ((E,), b1)) == 1


@pytest.mark.parametrize("dt", DTYPES)
def test_local_window_sum_kernel_compiles(one_chip, dt):
    sp = _sp()
    fn = lambda d, i, w: sp.segment_sum_local_pallas.__wrapped__(d, i, w, N, False)
    assert _kernels(fn, one_chip, ((E, H), dt), ((E,), i32), ((2, NB), i32)) == 1


@pytest.mark.parametrize("dt", DTYPES)
def test_pna_backward_kernels_compile(one_chip, dt):
    sp = _sp()
    fn = lambda v, r, m, bo, gs, gss, gb: sp._pna_bwd_kernels(
        v, r, m, bo, gs, gss, gb, N, False
    )
    shapes = (
        ((E, H), dt), ((E,), i32), ((E,), b1), ((N, 2 * H), dt),
        ((N, H), f32), ((N, H), f32), ((N, 2 * H), f32),
    )
    assert _kernels(fn, one_chip, *shapes) == 2  # tie counts, then the grad


FUSED_MODES = {
    # spec, then which of (w, b, rtab, eterm, scale) the mode passes
    "identity": ((0, ()), ()),
    "scale": ((1, ("none",)), ("w", "b", "scale")),  # SchNet filter
    "gated": ((2, ("sigmoid", "softplus")), ("w", "b", "rtab", "eterm")),  # CGCNN
}


@pytest.mark.parametrize("dt", DTYPES)
@pytest.mark.parametrize("real_edges", [False, True], ids=["full", "occupancy"])
@pytest.mark.parametrize("mode", list(FUSED_MODES))
def test_fused_conv_kernel_compiles(one_chip, dt, mode, real_edges):
    fc = _fc()
    spec, present = FUSED_MODES[mode]
    k = max(spec[0], 1)
    operand_shapes = {
        "w": ((H, k * H), dt), "b": ((1, k * H), f32), "rtab": ((N, k * H), dt),
        "eterm": ((E, k * H), dt), "scale": ((E, H), dt),
    }
    names = list(present) + (["re"] if real_edges else [])

    def fn(x, s, r, m, *rest):
        kw = dict(zip(names, rest))
        return fc._fused_kernel_call(
            x, s, r, m, kw.get("w"), kw.get("b"), kw.get("rtab"), kw.get("eterm"),
            kw.get("scale"), kw.get("re"), N, spec, False,
        )

    shapes = [((N, H), dt), ((E,), i32), ((E,), i32), ((E,), b1)]
    shapes += [operand_shapes[n] for n in present]
    if real_edges:
        shapes.append(((1,), i32))
    assert _kernels(fn, one_chip, *shapes) == 1


def test_resident_stack_kernel_compiles_at_its_vmem_budget(one_chip):
    """The VMEM-resident stack at the largest node count its own budget
    rule (residency_vmem_bytes <= residency_vmem_budget_bytes) lets
    through — the shape most likely to be refused for scoped VMEM."""
    fc = _fc()
    n = 128
    while fc.residency_vmem_bytes(n + 128, H) <= fc.residency_vmem_budget_bytes():
        n += 128
    e, layers = 8 * n, 3
    fn = lambda x, s, r, m, w, b: fc._stack_kernel_call(
        x, s, r, m, w, b, None, n, ("none", "relu", layers), False
    )
    shapes = (
        ((n, H), f32), ((e,), i32), ((e,), i32), ((e,), b1),
        ((layers, H, H), f32), ((layers, 1, H), f32),
    )
    assert _kernels(fn, one_chip, *shapes) == 1


# -- the token stacks' kernels at the published widths of their cells ------------
# (benchmark/configs/sdar-30b-a3b-chat.json: 32 query and 4 key-value heads of
# 128, 16 held experts 2,048 -> 768 -> 2,048; 16,896 rows = 33 tiles of 512 for
# the attention; benchmark/configs/joyai-llm-flash.json: latent attention's 32
# heads scoring at 128 + 64 and carrying values of 128, one key-value head a
# query head; 8,704 rows = 17 tiles)

ATTENTION = {"grouped_query": (16_896, 32, 4, 128, 128), "latent": (8_704, 32, 32, 192, 128)}


def _attention_args(sharding, rows, hq, hkv, dk, dv):
    ba = importlib.import_module("hydragnn_tpu.ops.block_attention")
    nt = rows // ba.TILE
    pair = [((nt * nt,), i32)] * 4 + [((1,), i32)]
    shapes = [((hq, rows, dk), jnp.bfloat16), ((hkv, rows, dk), jnp.bfloat16), ((hkv, rows, dv), jnp.bfloat16),
              ((rows, ba.META), i32)] + pair + pair
    return ba, [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]


@pytest.mark.parametrize("widths", sorted(ATTENTION))
def test_block_attention_forward_kernel_compiles(one_chip, widths):
    ba, args = _attention_args(one_chip, *ATTENTION[widths])
    dk = ATTENTION[widths][3]

    def fn(q, k, v, meta, *pairs):
        return ba._attend(q, k, v, meta, (pairs[:5], pairs[5:]), dk**-0.5, ba.TILE, False)

    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and "block_attention_fwd" in text


@pytest.mark.parametrize("widths", sorted(ATTENTION))
def test_block_attention_backward_kernels_compile(one_chip, widths):
    ba, args = _attention_args(one_chip, *ATTENTION[widths])
    dk = ATTENTION[widths][3]

    def fn(q, k, v, meta, *pairs):
        out, pull = jax.vjp(lambda q, k, v: ba._attend(q, k, v, meta, (pairs[:5], pairs[5:]), dk**-0.5, ba.TILE, False),
                            q, k, v)
        return pull(out)

    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    assert "block_attention_dq" in text and "block_attention_dkv" in text


@pytest.mark.parametrize("backward", [False, True], ids=["forward", "backward"])
def test_held_experts_compile_to_grouped_products(one_chip, backward):
    """The held experts' rounds at the cell's widths: the TPU compiler makes
    ``jax.lax.ragged_dot`` its own grouped kernel (not a product a group
    over every row), forward and backward."""
    ts = importlib.import_module("hydragnn_tpu.models.token_stack")
    rows, hidden, width, held, per_tok, per_round = 16_400, 2048, 768, 16, 8, 16_896
    bf16 = jnp.bfloat16
    shapes = [((rows, hidden), bf16), ((rows * per_tok,), jnp.float32), ((held, hidden, width), bf16),
              ((held, hidden, width), bf16), ((held, width, hidden), bf16),
              ((rows * per_tok + per_round,), i32), ((held,), i32), ((held,), i32), ((), i32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]

    def fn(m, weights, gate, up, down, *plan):
        def layer(m, weights, gate, up, down):
            return ts.held_experts(m, weights, gate, up, down, plan, per_round, per_tok)

        if not backward:
            return layer(m, weights, gate, up, down)
        out, pull = jax.vjp(layer, m, weights, gate, up, down)
        return pull(out)

    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    products = 3 if not backward else 12  # a round's three; the backward recomputes them and pulls each back twice
    assert "ragged-dot" in text and text.count("tpu_custom_call") >= products
    dense = 2 * per_round * hidden * width  # one product of a round's rows with ONE expert's matrix
    assert compiled.cost_analysis()["flops"] < products * dense * 1.5
