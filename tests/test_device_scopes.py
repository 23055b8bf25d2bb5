"""The program's device scopes (``obs/spans.py:scope``) and the rule that
reads a train step's passes off each instruction's ``op_name``
(``benchmark/device_scopes.py:region``): forward, backward, remat
recompute, optimizer, diagnostics. A tiny flax model with known
matrix products is the ground truth; then each model family's own train
programs. CPU only."""

import collections
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import flax.linen as nn
import numpy as np
import optax
import pytest

from hydragnn_tpu.obs.introspect import head_diagnostics, linearize_heads
from hydragnn_tpu.obs.spans import SCOPES, scope

_SPEC = importlib.util.spec_from_file_location(
    "device_scopes", os.path.join(os.path.dirname(__file__), os.pardir, "benchmark", "device_scopes.py"))
ds = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(ds)

LAYERS, HEADS, WIDTH = 2, 2, 16
# instructions that are no work of a pass: constants, and the barriers remat puts in
_NOT_WORK = {"stablehlo.constant", "stablehlo.optimization_barrier"}


class _Layer(nn.Module):
    @nn.compact
    def __call__(self, x):
        return jnp.tanh(nn.Dense(WIDTH, name="proj")(x))


class _Model(nn.Module):
    remat: bool

    @nn.compact
    def __call__(self, x):
        layer = nn.remat(_Layer) if self.remat else _Layer
        for i in range(LAYERS):
            x = layer(name=f"layer_{i}")(x)
        return jnp.stack([nn.Dense(1, name=f"head_{j}")(x).mean() for j in range(HEADS)])


def _ops(text: str):
    """(region, op) of every operation of a lowered module's text, by the
    name location it carries."""
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    out = []
    for line in text.splitlines():
        m = re.search(r'= ("?[\w.]+"?)[ (<].*loc\((#loc\d+)\)$', line.strip())
        if m and m.group(2) in locs and m.group(1).strip('"') not in _NOT_WORK:
            out.append((ds.region(locs[m.group(2)]), m.group(1).strip('"')))
    return out


def _count(ops, region=None, op=None):
    return sum(1 for r, o in ops if (region is None or r == region) and (op is None or o == op))


def _lowered(remat: bool, diagnosed: bool) -> str:
    """A train step as ``train/state.py`` composes it: the plain one
    (``value_and_grad`` of the weighted loss, the update under
    ``optimizer``) or the diagnosed one (one linearisation, a pull a head
    under ``diagnostics``, the weight-vector pull for the update)."""
    model = _Model(remat=remat)
    x = jnp.ones((8, WIDTH))
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    tx = optax.adamw(1e-3)
    weights = (0.75, 0.25)

    def loss_fn(p):
        tasks = model.apply({"params": p}, x)
        return (jnp.asarray(weights) * tasks).sum(), (tasks, {})

    def step(params, opt_state):
        if diagnosed:
            loss, tasks, _, head_grads, grads = linearize_heads(loss_fn, params, weights)
        else:
            (loss, (tasks, _)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        with scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            new = optax.apply_updates(params, updates)
        if diagnosed:
            with scope("diagnostics"):
                return new, opt_state, loss, head_diagnostics(tasks, head_grads, grads, params, updates, weights)
        return new, opt_state, loss

    return jax.jit(step).lower(params, tx.init(params)).as_text(debug_info=True)


# the matrix products of one backward pass: a dense layer's weight gradient and,
# but for the first layer (no gradient w.r.t. the input), its input's
BACKWARD_DOTS = (2 * LAYERS - 1) + 2 * HEADS
FORWARD_DOTS = LAYERS + HEADS


def test_scope_takes_the_programs_names_only():
    assert SCOPES == ("optimizer", "diagnostics")
    for name in SCOPES:
        with scope(name):
            pass
    with pytest.raises(ValueError):
        scope("forward")


@pytest.mark.parametrize("remat", [False, True])
def test_regions_of_a_plain_step(remat):
    ops = _ops(_lowered(remat, diagnosed=False))
    assert _count(ops, "forward", "stablehlo.dot_general") == FORWARD_DOTS
    assert _count(ops, "backward", "stablehlo.dot_general") == BACKWARD_DOTS
    # remat computes each layer's forward once more, inside the backward
    assert _count(ops, "recompute", "stablehlo.dot_general") == (LAYERS if remat else 0)
    assert _count(ops, "diagnostics") == 0 and _count(ops, "unscoped") == 0
    # Adam's square roots are the update's; the model has none
    assert _count(ops, "optimizer", "stablehlo.sqrt") > 0
    assert _count(ops, op="stablehlo.sqrt") == _count(ops, "optimizer", "stablehlo.sqrt")
    assert _count(ops, "optimizer", "stablehlo.dot_general") == 0


def test_the_recompute_is_what_the_step_without_remat_lacks():
    plain, rematted = (collections.Counter(_ops(_lowered(r, diagnosed=False))) for r in (False, True))
    by_op = lambda c, region: collections.Counter({o: n for (r, o), n in c.items() if r == region})  # noqa: E731
    # the backward and the update are the same operations with and without remat
    assert by_op(rematted, "backward") == by_op(plain, "backward")
    assert by_op(rematted, "optimizer") == by_op(plain, "optimizer")
    # what the rematted step has beyond the plain one is recompute, and the
    # recompute is that plus the forward's own work that moved into it
    extra = collections.Counter({o: n for (_, o), n in (rematted - plain).items()})
    recompute = by_op(rematted, "recompute")
    assert extra and not recompute - extra - (by_op(plain, "forward") - by_op(rematted, "forward"))
    assert not extra - recompute


@pytest.mark.parametrize("remat", [False, True])
def test_regions_of_a_diagnosed_step(remat):
    ops = _ops(_lowered(remat, diagnosed=True))
    # the weight-vector pull makes the update: one backward, as in the plain step
    assert _count(ops, "backward", "stablehlo.dot_general") == BACKWARD_DOTS
    assert _count(ops, "recompute", "stablehlo.dot_general") == (LAYERS if remat else 0)
    # a pull a head (with its own recompute under remat) and the heads' gram
    # matrix, one dot a parameter leaf a pair of heads, are the diagnostics
    leaves = 2 * (LAYERS + HEADS)
    pull = BACKWARD_DOTS + (LAYERS if remat else 0)
    assert _count(ops, "diagnostics", "stablehlo.dot_general") == HEADS * pull + HEADS * HEADS * leaves
    assert _count(ops, "forward", "stablehlo.dot_general") == FORWARD_DOTS
    assert _count(ops, "optimizer", "stablehlo.dot_general") == 0


@pytest.mark.parametrize("op_name, want", [
    ("", "unscoped"),
    ("jit(step)/jvp(M)/layer_0/proj/dot_general", "forward"),
    ("jit(step)/transpose(jvp(M))/head_0/dot_general", "backward"),
    ("jit(step)/transpose(jvp(M))/jvp(M)/checkpoint/layer_0/proj/dot_general", "backward"),
    ("jit(step)/transpose(jvp(M))/jvp(M)/checkpoint/rematted_computation/layer_0/tanh", "recompute"),
    ("jit(step)/optimizer/mul", "optimizer"),
    ("jit(step)/diagnostics/transpose(jvp(M))/jvp(M)/checkpoint/rematted_computation/layer_0/tanh", "diagnostics"),
    ("jit(step)/while/body/closed_call/jit(_threefry_split)/step/xor", "forward"),
    ("jit(step)/jvp(M)/optimizer_net/mul", "forward"),  # a module named like a scope is no scope
])
def test_region_rule(op_name, want):
    assert ds.region(op_name) == want


@pytest.mark.parametrize("op_name, want", [
    ("jit(s)/while/body/closed_call/jvp(HydraModel)/HydraModel._apply_conv/conv_3/PNAConv_0/mul", "conv_3"),
    ("jit(s)/transpose(jvp(HydraModel))/MaskedBatchNorm_1/reduce_sum", "MaskedBatchNorm_1"),
    ("jit(s)/jvp(HydraModel)/graph_shared/Dense_0/dot_general", "Dense_0"),
    ("jit(s)/transpose(jvp(HydraModel))/tokens/jvp(HydraModel)/tokens/checkpoint/rematted_computation/"
     "layer_2/attention/dot_general", "layer_2"),
    ("jit(s)/jvp(HydraModel)/tokens/gather", "tokens"),
    ("jit(s)/optimizer/mul", "(root)"),
    ("", "(none)"),
])
def test_component_rule(op_name, want):
    assert ds.component(op_name) == want


def test_a_fusion_takes_its_roots_region_and_is_mixed_when_it_spans_two():
    hlo_pb2 = pytest.importorskip("tensorflow.compiler.xla.service.hlo_pb2")

    def f(x, y):
        with scope("optimizer"):
            a = x * 3.0 + 1.0
        return jnp.sin(a) * y  # one elementwise loop: its root is outside the scope

    x = jnp.ones((64, 64))
    exe = jax.jit(f).lower(x, x).compile().runtime_executable()
    (module,) = exe.hlo_modules()
    proto = hlo_pb2.HloModuleProto.FromString(module.as_serialized_hlo_module_proto())
    table = ds.instructions(proto)
    fusions = {name: i for name, i in table.items() if i.opcode == "fusion"}
    assert fusions, "the CPU compiler should fuse the elementwise chain"
    roots = {}
    comps = {c.id: c for c in proto.computations}
    for comp in proto.computations:
        for ins in comp.instructions:
            if ins.opcode == "fusion":
                body = comps[ins.called_computation_ids[0]]
                root = next(i for i in body.instructions if i.id == body.root_id)
                roots[ins.name] = ds.region(root.metadata.op_name)
    for name, ins in fusions.items():
        assert ins.region == roots[name] == "forward"
    assert any(i.mixed and {"forward", "optimizer"} <= set(i.fused) for i in fusions.values())


# -- each family's own train programs ------------------------------------------


def _family(kind: str):
    """(run config, fresh samples) at the sizes the family's tests use."""
    from hydragnn_tpu.data.synthetic import deterministic_graph_data

    if kind in ("PNA", "SchNet"):
        from test_data_pipeline import base_config

        cfg = base_config(multihead=True)
        cfg["NeuralNetwork"]["Architecture"]["model_type"] = kind
        if kind == "SchNet":
            cfg["NeuralNetwork"]["Architecture"].update(num_gaussians=8, num_filters=8)
        cfg["NeuralNetwork"]["Training"]["batch_size"] = 6
        return cfg, deterministic_graph_data(number_configurations=24, seed=7)
    if kind == "block_diffusion":
        import test_token_stack as t

        return t.run_config(None), t.samples(12, seed=1)
    import test_latent_stack as t

    return t.run_config(), t.samples(12, seed=1)


@pytest.mark.parametrize("kind, rematted", [("PNA", False), ("SchNet", False), ("block_diffusion", True),
                                            ("latent", True)])
def test_each_familys_train_programs_carry_the_scopes(kind, rematted):
    from hydragnn_tpu.api import prepare_loaders_and_config
    from hydragnn_tpu.models.create import create_model_config
    from hydragnn_tpu.train import create_train_state, select_optimizer
    from hydragnn_tpu.train.state import make_diagnosed_first_step, make_scan_epoch

    cfg, samples = _family(kind)
    loader, _, _, cfg = prepare_loaders_and_config(cfg, samples)
    nn_cfg = cfg["NeuralNetwork"]
    tx = select_optimizer(nn_cfg["Training"])
    model, variables = create_model_config(nn_cfg, next(iter(loader)))
    state = create_train_state(variables, tx)
    stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *list(loader))
    order, consec = jnp.arange(len(loader), dtype=jnp.int32), jnp.zeros((), jnp.int32)
    first = make_diagnosed_first_step(model, tx, guard_nonfinite=True)
    heads = len(model.cfg.normalized_weights)
    head = (jnp.zeros(()), jnp.zeros((heads,)), jnp.zeros(()), jnp.zeros(()))
    scan = make_scan_epoch(model, tx, guard_nonfinite=True)
    regions = {}
    for name, low in (("diagnosed", first.lower(state, stacked, order, consec)),
                      ("scan", scan.lower(state, stacked, order, consec, head))):
        regions[name] = collections.Counter(r for r, _ in _ops(low.as_text(debug_info=True)))
    for name in ("diagnosed", "scan"):
        assert regions[name]["optimizer"] > 0 and regions[name]["forward"] > 0 and regions[name]["backward"] > 0
        assert (regions[name]["recompute"] > 0) == rematted
        assert regions[name]["unscoped"] == 0
    assert regions["diagnosed"]["diagnostics"] > 0 and regions["scan"]["diagnostics"] == 0
