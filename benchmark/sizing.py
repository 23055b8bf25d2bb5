"""Compile a cell's train program for a DESCRIBED v5e chip, on the CPU.

    JAX_PLATFORMS=cpu python benchmark/sizing.py --workload <cell> [--seed 1]

Builds the cell's data, loaders and model exactly as ``run_training``
would (same calls, on the CPU backend), then lowers the program the
window drives (the scanned epoch on one chip, the sharded per-step
program on four) for ``v5e:2x2`` and prints ``memory_analysis()``, the
number of ``tpu_custom_call``s and of all-reduces. Nothing runs on a
chip: a compile that passes is not a chip run. It is how a cell's batch
is sized before any chip time is spent (PERF.md, section 4).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    import cell as cellmod

    cell = cellmod.load_cell(args.workload)
    if cell.chips > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = f"{flags} --xla_force_host_platform_device_count={cell.chips}"

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)

    from hydragnn_tpu.api import prepare_loaders_and_config
    from hydragnn_tpu.models.create import create_model_config
    from hydragnn_tpu.train import create_train_state, select_optimizer
    from hydragnn_tpu.train.state import make_scan_epoch

    raw = cell.fam.generate(cell.traffic, args.seed)
    stack = cell.chips
    train_loader, val_loader, test_loader, config = prepare_loaders_and_config(
        cell.run_config, cell.fam.program_samples(raw), device_stack=stack
    )
    nn = config["NeuralNetwork"]
    example = next(iter(train_loader))
    one = jax.tree_util.tree_map(lambda x: x[0], example) if stack > 1 else example
    tx = select_optimizer(nn["Training"])
    part = None
    if stack > 1:
        from hydragnn_tpu.parallel import Partitioner

        part = Partitioner.from_config(nn, device_stack=stack)
    # initialized on the CPU, with the CPU's paths; only then do the
    # dispatchers see a TPU backend and pick the Pallas kernels
    model, variables = create_model_config(
        nn, one, bn_axis_name=part.bn_axis_name if part else None
    )
    state = create_train_state(variables, tx)
    jax.default_backend = lambda: "tpu"
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    out = {"workload": cell.name, "batch_size": cell.batch_size,
           "train_batches": len(train_loader),
           "pad_nodes": train_loader.pad_nodes, "pad_edges": train_loader.pad_edges,
           "real_nodes_first_batch": int(np.asarray(example.node_mask).sum()),
           "real_edges_first_batch": int(np.asarray(example.edge_mask).sum())}

    def shapes(tree, sharding):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype if not hasattr(x, "dtype") else x.dtype, sharding=sharding),
            tree,
        )

    if stack == 1:
        dev = SingleDeviceSharding(topo.devices[0])
        host = [train_loader._make_batch(np.arange(b * cell.batch_size, (b + 1) * cell.batch_size))
                for b in range(len(train_loader))]
        stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *host)
        scan = make_scan_epoch(model, tx, compute_dtype=jnp.bfloat16, guard_nonfinite=True)
        lowered = scan.lower(
            shapes(state, dev), shapes(stacked, dev),
            jax.ShapeDtypeStruct((len(host),), jnp.int32, sharding=dev),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=dev),
        )
        out["stacked_bytes"] = int(sum(np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(stacked)))
        # the diagnostics step (default on, once an epoch) is a gradient
        # program of its own and can need more memory than the train step
        from hydragnn_tpu.obs.introspect import make_diagnostics_step

        diag = make_diagnostics_step(model, tx, compute_dtype=jnp.bfloat16)
        try:
            dmem = diag.lower(shapes(state, dev), shapes(host[0], dev)).compile().memory_analysis()
            out["diagnostics_temp_bytes"] = dmem.temp_size_in_bytes
        except Exception as exc:  # the compiler's refusal is the answer
            out["diagnostics_error"] = str(exc)[:300]
    else:
        from hydragnn_tpu.parallel.sharded import make_sharded_train_step

        mesh = Mesh(np.array(topo.devices[:stack]), ("data",))
        rep = NamedSharding(mesh, P())
        lead = NamedSharding(mesh, P("data"))
        step = make_sharded_train_step(model, tx, mesh, compute_dtype=jnp.bfloat16)
        lowered = step.lower(shapes(state, rep), shapes(example, lead))
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    out.update(
        argument_bytes=mem.argument_size_in_bytes, output_bytes=mem.output_size_in_bytes,
        temp_bytes=mem.temp_size_in_bytes, alias_bytes=mem.alias_size_in_bytes,
        generated_code_bytes=mem.generated_code_size_in_bytes,
        tpu_custom_calls=text.count("tpu_custom_call"), all_reduces=text.count("all-reduce("),
        params=int(sum(np.prod(x.shape) for x in jax.tree_util.tree_leaves(state.params))),
    )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
