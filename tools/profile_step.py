"""Decomposition profile of the flagship train step on the real chip.

Times each segment op at the flagship shape (E=699368 pad, H=128,
N=32752 pad) plus the whole step under auto-Pallas vs forced-XLA, via
the scan-slope protocol (2 dispatches per measurement, RTT cancels).
Scratch tooling — not part of the package.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from hydragnn_tpu.utils.profile import scan_slope_ms

WHICH = os.environ.get("PROF_WHICH", "ops,step").split(",")
results = {}


def chain_op(fn, *args, k1=2, k2=8):
    """Scan-slope time fn(*args) with a data dependency threaded through
    the carry so the chain cannot be parallelized or DCE'd."""

    def make_chain(k):
        def body(carry, _):
            out = fn(*args, carry)
            return out, ()

        chained = jax.jit(lambda c: jax.lax.scan(body, c, None, length=k)[0])

        def run():
            out = chained(jnp.zeros((), jnp.float32))
            np.asarray(out)

        return run

    return scan_slope_ms(make_chain, k1, k2)


def main():
    E, N, H = 699368, 32752, 128
    key = jax.random.PRNGKey(0)
    # receiver-sorted edges with realistic degree (~21 edges/node)
    recv = jnp.sort(jax.random.randint(key, (E,), 0, N, jnp.int32))
    send = jax.random.randint(jax.random.PRNGKey(1), (E,), 0, N, jnp.int32)
    perm = jnp.argsort(send)
    mask = jnp.ones((E,), bool)
    v = jax.random.normal(jax.random.PRNGKey(2), (E, H), jnp.bfloat16)
    xnode = jax.random.normal(jax.random.PRNGKey(3), (N, H), jnp.bfloat16)
    g_node = jax.random.normal(jax.random.PRNGKey(4), (N, H), jnp.bfloat16)

    from hydragnn_tpu.graph import segment as S
    from hydragnn_tpu.ops import segment_sum_family

    if "ops" in WHICH:
        # --- forward ops (carry c threads the dependency) ---
        def f_family(c):
            s, sq, cnt = segment_sum_family(
                v + c, recv, N, mask=mask, indices_are_sorted=True
            )
            return s.sum().astype(jnp.float32)

        def f_max(c):
            return S.segment_max(
                v + c, recv, N, mask=mask, indices_are_sorted=True
            ).sum().astype(jnp.float32)

        def f_minmax_fused(c):
            both = jnp.concatenate([v + c, -(v + c)], axis=-1)
            out = S.segment_max(both, recv, N, mask=mask, indices_are_sorted=True)
            return out.sum().astype(jnp.float32)

        def f_gather(c):
            return S.gather_rows_permuted(xnode + c, send, perm, N).sum().astype(
                jnp.float32
            )

        # --- fwd+bwd versions ---
        def g_of(f):
            grad = jax.grad(lambda c: f(c))
            return grad

        for name, f in [
            ("family_fwd", f_family),
            ("max_fwd", f_max),
            ("minmax_fused2H_fwd", f_minmax_fused),
            ("gather_fwd", f_gather),
        ]:
            ms = chain_op(lambda c, _f=f: _f(c))
            results[name] = round(ms, 3)
            print(name, results[name], flush=True)

        for name, f in [
            ("family_fwdbwd", f_family),
            ("max_fwdbwd", f_max),
            ("minmax_fused2H_fwdbwd", f_minmax_fused),
            ("gather_fwdbwd", f_gather),
        ]:
            gf = g_of(f)
            ms = chain_op(lambda c, _g=gf: _g(c))
            results[name] = round(ms, 3)
            print(name, results[name], flush=True)

    if "step" in WHICH:
        from hydragnn_tpu.flagship import build_flagship
        from hydragnn_tpu.train import (
            create_train_state,
            make_train_step,
            select_optimizer,
        )
        from hydragnn_tpu.train.state import _train_step_body

        config, model, variables, loader = build_flagship(
            n_samples=1280,
            hidden_dim=128,
            num_conv_layers=6,
            batch_size=1024,
            unit_cells=(2, 4),
        )
        tx = select_optimizer(config["NeuralNetwork"]["Training"])
        state = create_train_state(variables, tx)
        body = _train_step_body(model, tx, compute_dtype=jnp.bfloat16)
        batch0 = next(iter(loader))

        def make_chain(k):
            def f(st, _):
                st, loss, _ = body(st, batch0)
                return st, loss

            fn = jax.jit(lambda st: jax.lax.scan(f, st, None, length=k))

            def run():
                _, losses = fn(state)
                np.asarray(losses[-1])

            return run

        results["step_auto"] = round(scan_slope_ms(make_chain, 4, 12), 3)
        print("step_auto", results["step_auto"], flush=True)

        # forced XLA step
        os.environ["HYDRAGNN_PALLAS"] = "0"
        body_xla = _train_step_body(model, tx, compute_dtype=jnp.bfloat16)

        def make_chain_xla(k):
            def f(st, _):
                st, loss, _ = body_xla(st, batch0)
                return st, loss

            fn = jax.jit(lambda st: jax.lax.scan(f, st, None, length=k))

            def run():
                _, losses = fn(state)
                np.asarray(losses[-1])

            return run

        results["step_xla"] = round(scan_slope_ms(make_chain_xla, 4, 12), 3)
        print("step_xla", results["step_xla"], flush=True)
        os.environ["HYDRAGNN_PALLAS"] = "auto"

    print(json.dumps(results))


if __name__ == "__main__":
    main()
