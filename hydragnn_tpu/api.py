"""Top-level entry points: ``run_training``, ``run_prediction``, ``serve_model``.

Mirrors the reference pipelines (reference: hydragnn/run_training.py:42-133
and hydragnn/run_prediction.py:27-83): log setup -> distributed init ->
data load/split -> config inference -> model factory -> optimizer ->
optional checkpoint-continue -> epoch loop -> save model -> timers.
Differences by design: the "DDP wrap" disappears (data parallelism is a
sharding annotation in the train step, not a model wrapper), and H2D
movement happens in the loader (fixed-shape batches).

Both accept a config file path or dict (the reference uses singledispatch,
run_training.py:42-57); the dataset comes either from
``Dataset.path["total"]`` raw files or from an in-memory ``samples`` list
(the synthetic/test path).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from hydragnn_tpu.data.ingest import load_raw_samples, prepare_dataset
from hydragnn_tpu.data.loader import GraphLoader
from hydragnn_tpu.models.create import create_model_config
from hydragnn_tpu.obs.spans import drain, span
from hydragnn_tpu.train import (
    create_train_state,
    make_eval_step,
    select_optimizer,
    test_epoch,
    train_validate_test,
)
from hydragnn_tpu.train.loop import scan_dispatch_planned
from hydragnn_tpu.utils.checkpoint import (
    load_existing_model,
    load_existing_model_config,
    save_model,
)
from hydragnn_tpu.utils.config import (
    get_log_name_config,
    load_config,
    save_config,
    update_config,
)
from hydragnn_tpu.utils.platform import check_backend
from hydragnn_tpu.utils.print_utils import setup_log
from hydragnn_tpu.utils.time_utils import Timer, print_timers


def prepare_loaders_and_config(
    config: Dict[str, Any],
    samples: Optional[List] = None,
    device_stack: int = 1,
) -> Tuple[GraphLoader, GraphLoader, GraphLoader, Dict[str, Any]]:
    """Data load + split + config inference (reference:
    dataset_loading_and_splitting + update_config, run_training.py:67-78).

    ``device_stack`` > 1 makes every loader yield batches with a leading
    device axis for the sharded (data-parallel) step functions."""
    if samples is None:
        path = config["Dataset"]["path"]
        if "total" in path:
            samples = load_raw_samples(config, path["total"])
            train, val, test, mm_g, mm_n = prepare_dataset(samples, config)
        else:
            # per-split raw paths (reference: Dataset.path train/validate/
            # test layout, load_data.py:352-393); split membership is
            # pre-defined, normalization spans all splits
            from hydragnn_tpu.data.ingest import prepare_presplit_dataset

            splits = {}
            for key in ("train", "validate", "test"):
                if key not in path:
                    raise ValueError(
                        f"Dataset.path needs 'total' or 'train'/'validate'/'test'; missing {key!r}"
                    )
                splits[key] = load_raw_samples(config, path[key])
            train, val, test, mm_g, mm_n = prepare_presplit_dataset(
                splits["train"], splits["validate"], splits["test"], config
            )
    else:
        train, val, test, mm_g, mm_n = prepare_dataset(samples, config)

    voi = config["NeuralNetwork"]["Variables_of_interest"]
    voi["minmax_graph_feature"] = mm_g.tolist()
    voi["minmax_node_feature"] = mm_n.tolist()
    config = update_config(config, train, val, test)

    train_loader, val_loader, test_loader = create_dataloaders(
        train, val, test, config, device_stack=device_stack
    )
    return train_loader, val_loader, test_loader, config


def create_dataloaders(
    train: List,
    val: List,
    test: List,
    config: Dict[str, Any],
    device_stack: int = 1,
) -> Tuple[GraphLoader, GraphLoader, GraphLoader]:
    """Per-split loaders over prepared sample lists (the reference's
    ``create_dataloaders``, hydragnn/preprocess/load_data.py:226-283; the
    DistributedSampler role is played by num_shards/shard_rank)."""
    nn_config = config["NeuralNetwork"]
    training = nn_config["Training"]
    bs = int(training["batch_size"])
    nproc, rank = jax.process_count(), jax.process_index()
    kw = dict(
        num_shards=nproc,
        shard_rank=rank,
        device_stack=device_stack,
        cache_device_batches=bool(training.get("cache_device_batches", False)),
        scan_reshuffle_every=int(training.get("scan_reshuffle_every", 0)),
    )
    # A run that will train through the whole-epoch scan only ever permutes
    # the ORDER of the train batches, so the train loader can cut its pad
    # plan to the batches that exist. The plan must be final here (the
    # example batch, introspection and the executable cache read shapes
    # before the loop resolves its dispatch mode), so ask what the loop
    # will ask, of the topology the partitioner will be built from (a
    # sharded run brings its own step and never scans).
    from hydragnn_tpu.parallel.partitioner import ParallelConfig

    topology = ParallelConfig.from_config(nn_config, device_stack, multihost=nproc > 1)
    scans, _ = scan_dispatch_planned(nn_config, topology.single_device)
    train_loader = GraphLoader(train, bs, shuffle=True, fixed_membership=scans, **kw)
    val_loader = GraphLoader(val, bs, **kw)
    test_loader = GraphLoader(test, bs, **kw)
    return train_loader, val_loader, test_loader


def _example_for_init(example, device_stack: int):
    """Strip the leading device axis off a loader example when the loader
    stacks sub-batches, so model init sees one sub-batch's shapes."""
    if device_stack > 1:
        return jax.tree_util.tree_map(lambda x: x[0], example)
    return example


def _choose_device_stack(config: Dict[str, Any]) -> int:
    """Batch device-axis width for this process: all local devices
    (divided by ``Parallel.edge``, which shards WITHIN each sub-batch)
    when the per-process batch size divides evenly, else single-device.
    Multi-host runs combine this with a global mesh over every process's
    devices (each process feeds its own shard; ``globalize_batch``
    assembles the logical batch), so the reference's DDP-over-mpirun
    launch shape maps to one process per host here. The width feeds
    ``Partitioner.from_config``, which splits it into ``data × fsdp``."""
    n_local = jax.local_device_count()
    nn = config["NeuralNetwork"]
    par = nn.get("Parallel") or {}
    fsdp = int(par.get("fsdp", 1) or 1)
    edge = int(par.get("edge", 1) or 1)
    if n_local % edge:
        raise ValueError(
            f"Parallel.edge={edge} must divide local_device_count={n_local}"
        )
    usable = n_local // edge
    bs = int(nn["Training"]["batch_size"])
    if usable > 1 and bs % usable != 0:
        if fsdp > 1:
            # an explicit fsdp request must not silently degrade to a
            # replicated single-device run that may not even fit HBM
            raise ValueError(
                f"Parallel.fsdp={fsdp} is set but batch_size={bs} is not "
                f"divisible by the usable device width {usable}; pick a "
                "batch size the device width divides"
            )
        import warnings

        warnings.warn(
            f"batch_size={bs} is not divisible by the usable device "
            f"width {usable}; falling back to SINGLE-DEVICE execution "
            f"(~{usable}x throughput loss). Use a batch_size divisible "
            f"by {usable} to engage all local devices.",
            RuntimeWarning,
            stacklevel=2,
        )
        return 1
    if fsdp > 1 and (usable < fsdp or usable % fsdp):
        raise ValueError(
            f"Parallel.fsdp={fsdp} must divide the usable device width "
            f"{usable} (local_device_count={n_local}, edge={edge})"
        )
    return usable


def train_with_loaders(
    config: Dict[str, Any],
    train_loader: GraphLoader,
    val_loader: GraphLoader,
    test_loader: GraphLoader,
    log_dir: str = "./logs/",
    device_stack: int = 1,
):
    """Model creation + optimizer + epoch loop + checkpoint save, on
    already-built loaders whose config has been through ``update_config``
    — the manual-wiring tail every reference example driver repeats
    (e.g. examples/qm9/qm9.py:66-95). Returns (model, state, history)."""
    with span("setup.model_init"):
        verbosity = config.get("Verbosity", {}).get("level", 0)
        log_name = get_log_name_config(config)
        setup_log(log_name, log_dir)
        save_config(config, log_name, log_dir)

        nn_config = config["NeuralNetwork"]
        # Taken BEFORE any mesh is attached to the loaders, so the example is
        # a host-local batch regardless of the distribution mode.
        example = next(iter(train_loader))
        multihost = jax.process_count() > 1
        example_one = _example_for_init(example, device_stack)

        training = nn_config["Training"]
        # Restart-supervisor resume (hydragnn_tpu/resilience/supervisor.py):
        # a restarted child runs with HYDRAGNN_AUTO_RESUME=1 and picks up
        # its own checkpoint via the ordinary continue/startfrom machinery.
        from hydragnn_tpu.resilience import auto_resume_config

        auto_resume_config(training, log_name, log_dir)
        freeze = bool(nn_config["Architecture"].get("freeze_conv_layers"))
        tx = select_optimizer(training, freeze_conv=freeze)

        train_step = eval_step = eval_step_out = stats_step = None
        # ONE sharding story (docs/PARALLELISM.md): the Partitioner owns the
        # composed (data, fsdp, edge) mesh, the loader placement, the state
        # layout (replicated / ZeRO-1 / FSDP), and every partitioned step.
        from hydragnn_tpu.parallel import Partitioner

        if multihost:
            # Global mesh over every process's devices; each process feeds
            # its shard of the logical batch (the reference's one-DDP-rank-
            # per-GPU launch becomes one-process-per-host + a data mesh).
            # Heterogeneous hosts can locally derive different widths
            # (device_stack falls back to 1 when batch_size doesn't divide
            # its local device count); meshes/batch shapes must agree
            # everywhere or the collectives fail opaquely downstream, so the
            # widths are validated BEFORE the partitioner builds its global
            # mesh from them. Gather every process's (validity, width)
            # BEFORE raising: if only some processes raised, the rest would
            # block forever inside this collective.
            from jax.experimental import multihost_utils

            ok = device_stack in (1, jax.local_device_count())
            info = np.asarray(
                multihost_utils.process_allgather(
                    np.asarray([int(ok), device_stack], dtype=np.int64)
                )
            ).reshape(-1, 2)
            if not info[:, 0].all():
                bad = [int(s) for o, s in info.tolist() if not o]
                raise ValueError(
                    "multi-host device_stack must be 1 or local_device_count; "
                    f"invalid widths across processes: {bad}"
                )
            stacks = info[:, 1]
            if not (stacks == device_stack).all():
                raise ValueError(
                    f"device_stack must agree across processes, got {stacks.tolist()}"
                )
        partitioner = Partitioner.from_config(
            nn_config, device_stack=device_stack, multihost=multihost
        )
        sharded = not partitioner.single_device or multihost
        if sharded:
            model, variables = create_model_config(
                nn_config, example_one, bn_axis_name=partitioner.bn_axis_name
            )
            for loader in (train_loader, val_loader, test_loader):
                partitioner.attach_loader(loader)
            state = create_train_state(variables, tx)
            del variables  # the state holds its own copy; at 4 bytes a parameter the second one counts
            # place BEFORE restoring: the restore target then carries the run's
            # real (FSDP/ZeRO-1) shardings, so orbax places shards directly and
            # the msgpack path re-places onto them
            state = partitioner.shard_init(state)
        else:
            model, variables = create_model_config(nn_config, example_one)
            state = create_train_state(variables, tx)
            del variables
    with span("setup.restore"):
        state = load_existing_model_config(state, training, log_dir)
    if sharded:
        with span("setup.step_builders"):
            compute_dtype = jax.numpy.bfloat16 if training.get("mixed_precision") else None
            train_step = partitioner.shard_train_step(
                model,
                tx,
                compute_dtype=compute_dtype,
                remat=bool(training.get("remat", False)),
            )
            eval_step = partitioner.shard_eval_step(model)
            eval_step_out = partitioner.shard_eval_step(model, with_outputs=True)
            stats_step = partitioner.shard_stats_step(model)

    if jax.process_index() == 0:
        from hydragnn_tpu.utils.print_utils import print_model

        with span("setup.manifest"):
            print_model(state.params, verbosity)

    viz = config.get("Visualization", {})
    state, history = train_validate_test(
        model,
        tx,
        state,
        train_loader,
        val_loader,
        test_loader,
        nn_config,
        log_name=log_name,
        verbosity=verbosity,
        create_plots=bool(viz.get("create_plots", False)),
        plot_init_solution=bool(viz.get("plot_init_solution", False)),
        plot_hist_solution=bool(viz.get("plot_hist_solution", False)),
        log_dir=log_dir,
        train_step=train_step,
        eval_step=eval_step,
        eval_step_out=eval_step_out,
        stats_step=stats_step,
        # the FULL resolved config goes into the flight-record manifest
        # (the NeuralNetwork section alone loses Dataset/Verbosity —
        # docs/OBSERVABILITY.md documents the manifest contract)
        run_config=config,
        partitioner=partitioner,
    )

    save_model(state, log_name, log_dir, verbosity)
    return model, state, history


def run_training(
    config_file_or_dict,
    samples: Optional[List] = None,
    log_dir: str = "./logs/",
):
    """Full training pipeline; returns (model, state, history, config).

    Telemetry knobs (``NeuralNetwork.Training``, docs/OBSERVABILITY.md):
    ``diagnostics`` (default true) samples per-head gradient norms, the
    inter-task conflict matrix, per-head eval MAE/RMSE and the
    hardware-efficiency ledger (MFU + memory watermark) into the run's
    flight record every ``diag_every`` steps (0 = once per epoch);
    ``prometheus_dir`` additionally writes an atomic ``train.prom``
    textfile snapshot per epoch for a node-exporter textfile collector.
    All of it is inert under ``HYDRAGNN_TELEMETRY=0``."""
    drain()  # spans an earlier run of this process left unflushed are not this run's
    with span("setup.backend"):
        config = load_config(config_file_or_dict)
        verbosity = config.get("Verbosity", {}).get("level", 0)
        # the typed error (not a raw jax traceback) when the backend cannot
        # come up or is not the one JAX_PLATFORMS names: a supervised child
        # whose chip another process holds then fails fast (run_guard)
        check_backend()

    timer = Timer("total_training")
    timer.start()
    # stop on ANY exit: the registry timer is process-global, and a run
    # that raised mid-training would otherwise poison every later
    # run_training in the process with "Timer already running"
    try:
        with span("setup.data"):
            device_stack = _choose_device_stack(config)
            train_loader, val_loader, test_loader, config = prepare_loaders_and_config(
                config, samples, device_stack=device_stack
            )
        model, state, history = train_with_loaders(
            config,
            train_loader,
            val_loader,
            test_loader,
            log_dir=log_dir,
            device_stack=device_stack,
        )
    finally:
        timer.stop()
    print_timers(verbosity)
    return model, state, history, config


def serve_model(
    config_file_or_dict,
    samples: Optional[List] = None,
    log_dir: str = "./logs/",
    serve_config=None,
    start: bool = True,
    flight=None,
):
    """Stand up a batched online-inference server over a trained run.

    Where :func:`run_prediction` re-pads and re-dispatches the whole test
    set offline, this loads the checkpoint ONCE (same restore machinery),
    AOT-compiles a ladder of padded batch shapes, and returns a
    :class:`hydragnn_tpu.serve.ModelServer` answering single-graph
    requests with deadline micro-batching — the online counterpart for
    the paper's one-encoder/N-heads design, where one warm model serves
    every property endpoint concurrently.

    The dataset pipeline runs exactly as in prediction (normalization,
    radius edges, config inference) — its prepared samples size the
    bucket ladder and fix the request field spec; requests must be
    prepared the same way. Predictions are returned in MODEL space
    (normalized targets) — apply ``postprocess.output_denormalize`` for
    physical units.

    Returns the server (started unless ``start=False``); callers own its
    lifecycle (``server.stop()``, or use it as a context manager).
    """
    config = load_config(config_file_or_dict)
    train_loader, val_loader, test_loader, config = prepare_loaders_and_config(
        config, samples
    )
    log_name = get_log_name_config(config)
    reference = (
        list(train_loader.all_samples)
        + list(val_loader.all_samples)
        + list(test_loader.all_samples)
    )

    from hydragnn_tpu.serve import ModelRegistry, ModelServer, ServeConfig

    # Serving under the SAME sharding story as training: Parallel.fsdp
    # shards the served parameters over the fsdp axis (a model beyond one
    # chip's HBM serves from N chips); the bucket-ladder AOT compiles run
    # under the partitioner's mesh instead of an implicit single device.
    from hydragnn_tpu.parallel import Partitioner

    par = config["NeuralNetwork"].get("Parallel") or {}
    fsdp = int(par.get("fsdp", 1) or 1)
    if fsdp > jax.local_device_count():
        import warnings

        warnings.warn(
            f"Parallel.fsdp={fsdp} exceeds local_device_count="
            f"{jax.local_device_count()}; serving single-device "
            "(replicated parameters)",
            RuntimeWarning,
            stacklevel=2,
        )
        fsdp = 1
    partitioner = Partitioner(fsdp=fsdp)

    registry = ModelRegistry(log_dir)
    served = registry.load(
        log_name,
        config["NeuralNetwork"],
        example_graph=reference[0],
        partitioner=partitioner,
    )
    server = ModelServer(served, reference, serve_config or ServeConfig(), flight=flight)
    # reload("run_name") without an explicit log_dir restores from the
    # same checkpoint root this server was stood up from
    server.log_dir = log_dir
    if start:
        server.start()
    return server


def run_prediction(
    config_file_or_dict,
    samples: Optional[List] = None,
    log_dir: str = "./logs/",
) -> Tuple[float, np.ndarray, List[np.ndarray], List[np.ndarray]]:
    """Load data + trained weights, run the full test pass, optionally
    denormalize; returns (error, error_rmse_task, true_values,
    predicted_values) (reference: run_prediction.py:27-83). Single-host
    multi-device runs shard the eval over the local data mesh, like
    training."""
    config = load_config(config_file_or_dict)
    verbosity = config.get("Verbosity", {}).get("level", 0)

    device_stack = _choose_device_stack(config) if jax.process_count() == 1 else 1
    _, _, test_loader, config = prepare_loaders_and_config(
        config, samples, device_stack=device_stack
    )
    log_name = get_log_name_config(config)

    nn_config = config["NeuralNetwork"]
    example = next(iter(test_loader))
    example_one = _example_for_init(example, device_stack)
    model, variables = create_model_config(nn_config, example_one)
    # Same optimizer chain as training: freeze_conv changes the opt_state
    # pytree structure, and the checkpoint schema must match to deserialize.
    tx = select_optimizer(
        nn_config["Training"],
        freeze_conv=bool(nn_config["Architecture"].get("freeze_conv_layers")),
    )
    # Eval never reads the optimizer state; the restore target carries it
    # as HOST arrays only (create_eval_state), so a ZeRO-1-trained
    # checkpoint whose optimizer state cannot fit un-sharded on a device
    # restores fine, and the drop below keeps it off the mesh entirely.
    from hydragnn_tpu.train import create_eval_state

    state = create_eval_state(variables, tx)
    state = load_existing_model(state, log_name, log_dir)
    state = state.replace(opt_state=())

    from hydragnn_tpu.parallel import Partitioner

    partitioner = Partitioner.from_config(nn_config, device_stack=device_stack)
    if not partitioner.single_device:
        partitioner.attach_loader(test_loader)
        state = partitioner.shard_init(state)
        eval_step = partitioner.shard_eval_step(model, with_outputs=True)
    else:
        eval_step = make_eval_step(model, with_outputs=True)
    error, error_rmse_task, true_values, predicted_values = test_epoch(
        test_loader, state, eval_step, model.cfg, verbosity, return_samples=True
    )

    voi = nn_config["Variables_of_interest"]
    if voi.get("denormalize_output"):
        from hydragnn_tpu.postprocess.postprocess import output_denormalize

        true_values, predicted_values = output_denormalize(
            voi["y_minmax"], true_values, predicted_values
        )

    return error, error_rmse_task, true_values, predicted_values
