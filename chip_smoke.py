"""Bring-up smoke: the flagship train and serve path, once, on the chip.

    python chip_smoke.py              one TPU chip; every phase below
    python chip_smoke.py --chips 4    four chips; the sharded phase only
    python chip_smoke.py --list-phases [--chips 4]   phase names (no JAX)

One process, because a chip belongs to one process at a time: every
phase runs here and nothing that needs the device is started as a child.
The model is the repo's flagship (``hydragnn_tpu/flagship.py``:
multi-head PNA, hidden 128, 6 conv layers, batch 1024 on the
deterministic BCC data, mixed precision) with random weights from
``--seed``, driven through the entry points a user calls —
``run_training`` in both dispatch modes, then ``serve_model`` on the
checkpoint that run wrote.

Every phase prints one JSON object on its own line. The LAST line of
stdout is the verdict and nothing more:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The script fails (non-zero exit, ``"ok": false``) unless
``jax.devices()[0].platform == "tpu"``; it never sets or pins a platform
itself. ``--rehearse`` runs the same control flow at a tiny size on
whatever backend JAX finds (set ``HYDRAGNN_PALLAS=interpret`` to take
the kernels along) and ALWAYS ends ``"ok": false`` with exit code 3 — a
rehearsal can find a wrong path or argument, never pass for a chip run.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

ONE_CHIP_PHASES = (
    "device", "train_scan", "train_per_step", "compiled_step", "serve", "selfcheck"
)
FOUR_CHIP_PHASES = ("device", "four_chip")
# everything the smoke writes, it writes into these under --out
OUT_SUBDIRS = ("native_build", "scan", "per_step", "exec_cache", "data4", "data1")

# loss after the same few steps, data=4 (SyncBatchNorm) against one
# device: the two differ by bf16 reduction order and by per-shard
# padding in the node-head means, not by what they learn
FOUR_CHIP_LOSS_RTOL = 5e-2
# served forward (kernels) against the plain-XLA forward of the same f32
# model: the two round their matmuls differently on the MXU
SERVE_RTOL, SERVE_ATOL = 5e-3, 5e-3


class SmokeFailure(Exception):
    pass


def emit(phase: str, **kw) -> None:
    print(json.dumps({"phase": phase, **kw}, default=str), flush=True)


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--list-phases", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--epochs", type=int, default=3, help="one optimizer step each")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out", "smoke"))
    return ap.parse_args(argv)


def sizes(args) -> dict:
    """The flagship at full width (bench.py's headline sizes); tiny only
    under --rehearse."""
    if args.rehearse:
        return dict(hidden=16, layers=2, batch=32, samples=40, unit_cells=(1, 3))
    return dict(hidden=128, layers=6, batch=1024, samples=1280, unit_cells=(2, 4))


# -- compile-time accounting ------------------------------------------------

_COMPILE = {"s": 0.0, "n": 0}


def _watch_compiles() -> None:
    import jax

    def on_duration(event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILE["s"] += secs
            _COMPILE["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)


@contextlib.contextmanager
def phase_clock(out: dict):
    s0, n0, t0 = _COMPILE["s"], _COMPILE["n"], time.perf_counter()
    yield
    out["compile_s"] = round(_COMPILE["s"] - s0, 2)
    out["compiles"] = _COMPILE["n"] - n0
    out["wall_s"] = round(time.perf_counter() - t0, 2)


# -- shared pieces ----------------------------------------------------------


def make_config(sz: dict, epochs: int, **training):
    from hydragnn_tpu.flagship import flagship_config

    cfg = flagship_config(sz["hidden"], sz["layers"], sz["batch"], num_epoch=epochs)
    cfg["NeuralNetwork"]["Training"].update(mixed_precision=True, **training)
    return cfg


def make_samples(sz: dict, seed: int):
    """Fresh samples per call: the dataset pipeline normalizes in place."""
    from hydragnn_tpu.data.synthetic import deterministic_graph_data

    uc = sz["unit_cells"]
    return deterministic_graph_data(
        number_configurations=sz["samples"],
        unit_cell_x_range=uc, unit_cell_y_range=uc, unit_cell_z_range=uc,
        seed=seed,
    )


def flight_of(config: dict, log_dir: str):
    from hydragnn_tpu.obs.flight import read_flight_record
    from hydragnn_tpu.utils.config import get_log_name_config

    return read_flight_record(
        os.path.join(log_dir, get_log_name_config(config), "flight.jsonl")
    )


def peak_memory(devices) -> list:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def train_phase(name, args, sz, log_dir, want_mode, want_reason, **training) -> dict:
    """A few optimizer steps through run_training; the flight record the
    run wrote says which dispatch mode executed them."""
    import numpy as np

    from hydragnn_tpu import run_training

    info: dict = {}
    with phase_clock(info):
        model, state, history, config = run_training(
            make_config(sz, args.epochs, **training),
            samples=make_samples(sz, args.seed),
            log_dir=log_dir,
        )
    events = flight_of(config, log_dir)
    start = next(e for e in events if e["kind"] == "run_start")["manifest"]
    mode = start["dispatch_mode"]
    skipped = sum(
        (e.get("nonfinite") or {}).get("skipped", 0)
        for e in events if e["kind"] == "epoch"
    )
    losses = [float(x) for x in history["train_loss"]]
    emit(
        name, dispatch_mode=mode["mode"], dispatch_auto=mode["auto"],
        dispatch_reason=mode["reason"], steps=int(state.step),
        loss_first=losses[0], loss_last=losses[-1], skipped_steps=skipped,
        nonfinite_guard=start.get("nonfinite_guard"), **info,
    )
    require(mode["mode"] == want_mode, f"{name}: ran {mode['mode']}, wanted {want_mode} ({mode['reason']})")
    require(want_reason in mode["reason"], f"{name}: dispatch reason {mode['reason']!r}")
    require(int(state.step) == args.epochs, f"{name}: {int(state.step)} optimizer steps, wanted {args.epochs}")
    require(skipped == 0, f"{name}: {skipped} non-finite steps were skipped")
    require(all(np.isfinite(losses)), f"{name}: non-finite loss {losses}")
    require(losses[-1] < losses[0], f"{name}: loss did not fall: {losses}")
    return {"model": model, "state": state, "config": config}


# -- phases -----------------------------------------------------------------


def phase_device(args, device: dict) -> None:
    """Bring the backend up (no platform set here), place the compile
    cache, and say what this process runs on and with. Fills ``device``
    — the verdict line carries it whether or not the phase passes."""
    import jax

    from hydragnn_tpu import native
    from hydragnn_tpu.obs.introspect import peak_flops, peak_hbm_bw
    from hydragnn_tpu.ops import segment_pallas
    from hydragnn_tpu.utils.platform import check_backend, place_compile_cache

    cache_dir = place_compile_cache()
    devices = check_backend()
    d0 = devices[0]
    device.update(platform=d0.platform, kind=d0.device_kind, count=len(devices))
    native.load(os.path.join(args.out, "native_build"))
    emit(
        "device", **device, jax=jax.__version__,
        compile_cache_dir=cache_dir,
        compile_cache_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        HAVE_NATIVE=native.HAVE_NATIVE,
        tiles={k: segment_pallas._TILE_DEFAULTS[k] for k in ("BN", "CE", "BCAST_CE", "source")},
        # which row of obs/introspect.py's peak tables this device_kind
        # gets (None = not in the table; a later benchmark must treat
        # that as an error)
        peak_bf16_flops=peak_flops(d0), peak_hbm_bytes_s=peak_hbm_bw(d0),
    )
    if not args.rehearse:
        require(d0.platform == "tpu", f"no TPU: jax.devices()[0].platform == {d0.platform!r}")
        require(len(devices) == args.chips, f"{len(devices)} devices, wanted {args.chips}")
        require(
            segment_pallas._TILE_DEFAULTS["source"] == "TUNE_TILES.json",
            "TUNE_TILES.json was not read",
        )


def phase_compiled_step(args, trained, train_loader, config) -> None:
    """The train step the per-step run dispatched, lowered again on the
    same arguments: the kernels must be IN it (``tpu_custom_call``), not
    quietly replaced by XLA."""
    import jax.numpy as jnp

    from hydragnn_tpu.train import make_train_step, select_optimizer

    info: dict = {}
    with phase_clock(info):
        tx = select_optimizer(config["NeuralNetwork"]["Training"])
        step = make_train_step(
            trained["model"], tx, compute_dtype=jnp.bfloat16, guard_nonfinite=True
        )
        compiled = step.lower(
            trained["state"], next(iter(train_loader)), jnp.zeros((), jnp.int32)
        ).compile()
        text = compiled.as_text()
        n_kernels = text.count("tpu_custom_call")
        mem = compiled.memory_analysis()
    emit(
        "compiled_step", tpu_custom_calls=n_kernels,
        temp_bytes=getattr(mem, "temp_size_in_bytes", None),
        argument_bytes=getattr(mem, "argument_size_in_bytes", None), **info,
    )
    if not args.rehearse:
        require(n_kernels >= 1, "no tpu_custom_call in the compiled train step")


def phase_serve(args, sz, log_dir, test_loader) -> None:
    """serve_model on the checkpoint the scan run wrote, stood up TWICE
    with the executable cache (``utils/exec_cache.py``) under ``--out``:
    the first start compiles every bucket and stores it, the second must
    read every bucket back from disk — the warm start a replica makes —
    and it is that second server that answers: a handful of requests of
    mixed graph size, each compared with the plain-XLA natural-pad
    forward of the same graph."""
    import glob

    import numpy as np

    from hydragnn_tpu.api import serve_model
    from hydragnn_tpu.graph.batch import batch_graphs
    from hydragnn_tpu.ops.segment_pallas import xla_segment_ops
    from hydragnn_tpu.serve import ServeConfig, request_to_dict

    cache_dir = os.path.join(args.out, "exec_cache")

    def start(which: str):
        info: dict = {}
        with phase_clock(info):
            server = serve_model(
                make_config(sz, args.epochs), samples=make_samples(sz, args.seed),
                log_dir=log_dir, serve_config=ServeConfig(exec_cache_dir=cache_dir),
            )
        snap = server.metrics_snapshot()
        emit(
            "serve_start", start=which, buckets=[
                {"node_pad": b.node_pad, "edge_pad": b.edge_pad, "max_batch": b.max_batch}
                for b in server.buckets
            ],
            live_compiles=snap["compile_warmup"], exec_cache_hits=snap["exec_cache_hits"],
            exec_cache_misses=snap["exec_cache_miss_reasons"],
            exec_cache_entries=len(glob.glob(os.path.join(cache_dir, "*.bin"))), **info,
        )
        return server, snap

    server, cold = start("cold")
    n_buckets = len(server.buckets)
    server.stop()
    require(
        cold["exec_cache_hits"] == 0 and cold["compile_warmup"] == n_buckets,
        f"serve: the cold start did not compile its {n_buckets} buckets: {cold['compile_warmup']} live, {cold['exec_cache_hits']} from disk",
    )
    require(
        len(glob.glob(os.path.join(cache_dir, "*.bin"))) == n_buckets,
        "serve: the cold start did not store every bucket's executable",
    )
    server, warm = start("warm")
    try:
        require(
            warm["exec_cache_hits"] == n_buckets and warm["compile_warmup"] == 0,
            f"serve: the warm start read {warm['exec_cache_hits']} of {n_buckets} buckets from disk "
            f"and compiled {warm['compile_warmup']} ({warm['exec_cache_miss_reasons']})",
        )
        # requests prepared the way the dataset was: the test split of
        # the same pipeline, from its smallest graph to its largest
        pool = sorted(test_loader.all_samples, key=lambda s: int(np.asarray(s.x).shape[0]))
        last = len(pool) - 1
        picks = [pool[i] for i in sorted({0, last // 4, last // 2, (3 * last) // 4, last})]
        served = server.served
        cfg = served.cfg
        for i, sample in enumerate(picks):
            before = server.metrics_snapshot()
            t0 = time.perf_counter()
            got = server.predict(sample, timeout=600)
            dt = time.perf_counter() - t0
            after = server.metrics_snapshot()
            if after["oversize_eager"] > before["oversize_eager"]:
                route = "eager"
            elif after["oversize_largest_bucket"] > before["oversize_largest_bucket"]:
                route = "aot_largest_bucket"
            else:
                route = "aot_bucket"
            g = request_to_dict(sample)
            n = int(np.asarray(g["x"]).shape[0])
            with xla_segment_ops():
                outs = served.forward(served.variables, batch_graphs([g]))
            worst = 0.0
            for ih in range(cfg.num_heads):
                name = cfg.output_names[ih]
                ref = np.asarray(outs[ih], np.float32)
                ref = ref[0] if cfg.output_type[ih] == "graph" else ref[:n]
                val = np.asarray(got[name], np.float32)
                require(val.shape == ref.shape, f"serve: head {name} shape {val.shape} != {ref.shape}")
                require(bool(np.all(np.isfinite(val))), f"serve: head {name} non-finite")
                worst = max(worst, float(np.max(np.abs(val - ref))))
                require(
                    bool(np.allclose(val, ref, rtol=SERVE_RTOL, atol=SERVE_ATOL)),
                    f"serve: head {name} differs from the reference forward by {worst}",
                )
            emit(
                "serve_request", i=i, nodes=n, answered=True, route=route,
                new_compiles=after["compile_misses"] - before["compile_misses"],
                max_abs_diff_vs_reference=worst, wall_s=round(dt, 3),
            )
        snap = server.metrics_snapshot()
        emit(
            "serve", requests=len(picks), answered=snap["results_total"] - warm["results_total"],
            errors=snap["errors"], quarantined=snap["quarantined"],
            compile_misses=snap["compile_misses"],
        )
        require(snap["results_total"] - warm["results_total"] == len(picks), "serve: not every request was answered")
        require(
            snap["errors"] == 0 and snap["quarantined"] == 0,
            f"serve: {snap['errors']} errors, {snap['quarantined']} quarantined requests",
        )
    finally:
        server.stop()


def phase_selfcheck(args) -> None:
    """The kernel-versus-XLA checks of tools/tpu_selfcheck.py, in THIS
    process (one JSON line per check comes from the module)."""
    from hydragnn_tpu.tools import tpu_selfcheck

    if args.rehearse:
        emit("selfcheck", ok=None, note="needs the chip; not run in a rehearsal")
        return
    info: dict = {}
    with phase_clock(info):
        ok = tpu_selfcheck.check_kernels()
        ok &= tpu_selfcheck.check_train_step()
    emit("selfcheck", ok=bool(ok), **info)
    require(bool(ok), "selfcheck: a kernel disagrees with XLA on this chip")


def phase_four_chip(args, sz, out) -> None:
    """run_training goes data-parallel by itself on a four-chip host;
    compare it with the one-device run of the same seed (the two calls
    run_training itself makes, at device_stack=1)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hydragnn_tpu import run_training
    from hydragnn_tpu.api import (
        _choose_device_stack, prepare_loaders_and_config, train_with_loaders,
    )
    from hydragnn_tpu.parallel import Partitioner
    from hydragnn_tpu.train import select_optimizer

    def config():
        cfg = make_config(sz, args.epochs)
        cfg["NeuralNetwork"]["Architecture"]["SyncBatchNorm"] = True
        return cfg

    n_dev = len(jax.devices())
    require(_choose_device_stack(config()) == n_dev, "run_training would not use every device")

    info: dict = {}
    with phase_clock(info):
        model, state, history, cfg = run_training(
            config(), samples=make_samples(sz, args.seed),
            log_dir=os.path.join(out, "data4"),
        )
    events = flight_of(cfg, os.path.join(out, "data4"))
    start = next(e for e in events if e["kind"] == "run_start")["manifest"]
    loss4 = [float(x) for x in history["train_loss"]]
    leaf_devices = [
        sorted(d.id for d in leaf.sharding.device_set)
        for leaf in jax.tree_util.tree_leaves(state.params)
    ]
    param_devices = sorted({i for ids in leaf_devices for i in ids})
    emit(
        "four_chip_sharded", losses=loss4, steps=int(state.step),
        dispatch_mode=start["dispatch_mode"], mesh=(start.get("parallel") or {}).get("mesh"),
        param_device_ids=param_devices, **info,
    )
    require(all(len(ids) == n_dev for ids in leaf_devices), "a parameter is not on every device")

    # the same sharded step, lowered on a sharded batch: where do the
    # batch shards live, and is there an all-reduce in the program?
    info = {}
    with phase_clock(info):
        train_loader, _, _, cfg4 = prepare_loaders_and_config(
            config(), make_samples(sz, args.seed), device_stack=n_dev
        )
        part = Partitioner.from_config(cfg4["NeuralNetwork"], device_stack=n_dev)
        part.attach_loader(train_loader)
        batch = next(iter(train_loader))
        shard_ids = sorted({s.device.id for s in batch.nodes.addressable_shards})
        tx = select_optimizer(cfg4["NeuralNetwork"]["Training"])
        text = part.shard_train_step(model, tx, compute_dtype=jnp.bfloat16).lower(
            state, batch
        ).compile().as_text()
    emit(
        "four_chip_program", batch_shard_device_ids=shard_ids,
        all_reduces=text.count("all-reduce"), tpu_custom_calls=text.count("tpu_custom_call"),
        **info,
    )
    require(len(shard_ids) == n_dev, f"batch shards on devices {shard_ids}, not {n_dev} distinct")
    require(text.count("all-reduce") >= 1, "no all-reduce in the sharded train step")

    info = {}
    with phase_clock(info):
        loaders = prepare_loaders_and_config(
            config(), make_samples(sz, args.seed), device_stack=1
        )
        _, state1, history1 = train_with_loaders(
            loaders[3], *loaders[:3], log_dir=os.path.join(out, "data1"), device_stack=1
        )
    loss1 = [float(x) for x in history1["train_loss"]]
    rel = abs(loss4[-1] - loss1[-1]) / max(abs(loss1[-1]), 1e-12)
    emit(
        "four_chip_compare", losses_one_device=loss1, losses_data4=loss4,
        rel_diff_last=rel, rtol=FOUR_CHIP_LOSS_RTOL,
        peak_bytes_in_use=peak_memory(jax.devices()), **info,
    )
    require(all(np.isfinite(loss4 + loss1)), "non-finite loss")
    require(loss4[-1] < loss4[0], f"sharded loss did not fall: {loss4}")
    require(rel <= FOUR_CHIP_LOSS_RTOL, f"data=4 loss {loss4[-1]} vs one device {loss1[-1]}: rel {rel}")


# -- driver -----------------------------------------------------------------


def run(args, device: dict) -> None:
    knob = os.environ.get("HYDRAGNN_PALLAS")
    allowed = (None, "auto", "interpret") if args.rehearse else (None, "auto")
    require(knob in allowed, f"HYDRAGNN_PALLAS={knob!r}: the smoke runs the default kernel dispatch only")

    # nothing generated is trusted: what an earlier smoke left under
    # --out goes (those sub-directories only — --out may hold more), and
    # the native library is built there (phase_device) rather than taken
    # from native/build, which a copied disk may carry
    for sub in OUT_SUBDIRS:
        shutil.rmtree(os.path.join(args.out, sub), ignore_errors=True)
    os.makedirs(args.out, exist_ok=True)

    phase_device(args, device)
    _watch_compiles()
    sz = sizes(args)
    emit("sizes", **sz, epochs=args.epochs, seed=args.seed, chips=args.chips)

    if args.chips == 4:
        phase_four_chip(args, sz, args.out)
        return

    import jax

    scan_dir = os.path.join(args.out, "scan")
    train_phase(
        "train_scan", args, sz, scan_dir, "scan_epoch",
        "single-device mesh + device-resident stacked loader",
    )
    trained = train_phase(
        "train_per_step", args, sz, os.path.join(args.out, "per_step"), "per_step",
        "Training.scan_epoch=false", scan_epoch=False,
    )
    # one more pass of the dataset pipeline serves both checks below: a
    # train batch to lower the step on, the test split as requests
    from hydragnn_tpu.api import prepare_loaders_and_config

    train_loader, _, test_loader, config = prepare_loaders_and_config(
        make_config(sz, args.epochs), make_samples(sz, args.seed)
    )
    phase_compiled_step(args, trained, train_loader, config)
    phase_serve(args, sz, scan_dir, test_loader)
    phase_selfcheck(args)
    emit("memory", peak_bytes_in_use=peak_memory(jax.devices()))


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.list_phases:
        print(json.dumps(list(FOUR_CHIP_PHASES if args.chips == 4 else ONE_CHIP_PHASES)))
        return 0
    device: dict = {}
    try:
        run(args, device)
    except Exception as exc:  # the last line must say so, whatever it was
        import traceback

        traceback.print_exc()
        print(json.dumps({"ok": False, "error": f"{type(exc).__name__}: {exc}"[:600], "device": device or None}))
        return 1
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": True, "device": device}))
        return 3
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
