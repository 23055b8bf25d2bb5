"""Incremental fine-tune child: retrain the serving model over a
pinned request-spool window, warm-started from the serving checkpoint.

Runnable (the pilot launches it under the restart supervisor)::

    python -m hydragnn_tpu.pilot.tune \
        --log-dir ./logs/ --serving-run <run> --spool-dir <spool> \
        --candidate <run>-pilot-c1 [--shards shard-000001,...] [--epochs 2]

The child re-derives nothing: it loads the serving run's SAVED
resolved config (``<log_dir>/<run>/config.json`` — already through
``update_config``, minmax and head layouts included) and the spool
shards' samples, which are already prepared/model-space (obs/spool.py
stores predictions as target fields, so a shard loads as a labelled
dataset with the old weights' predictions as pseudo-labels). Loaders
are built directly over those samples — no re-normalization pass that
would distort already-normalized data — the fresh state is restored
from the serving checkpoint through the validating loader, and
``train_validate_test`` runs a short fine-tune under a DISTINCT
candidate run name so the serving checkpoint is never written to.

Exit-code contract (resilience/preempt.py, what the supervisor
classifies): 0 completed, 78 config error (deterministic — retrying
cannot help: missing config/checkpoint/too-few samples), anything
else crash-class (retried with backoff).
"""

from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, List, Optional, Sequence

from hydragnn_tpu.resilience import inject
from hydragnn_tpu.resilience.preempt import EXIT_CONFIG_ERROR
from hydragnn_tpu.utils import knobs


def _split(samples: Sequence) -> tuple:
    """Deterministic ~80/10/10 split that never leaves a split empty
    (the loaders need at least one sample each)."""
    n = len(samples)
    if n < 3:
        raise ValueError(
            f"fine-tune needs at least 3 spooled samples, got {n}"
        )
    val = [s for i, s in enumerate(samples) if i % 10 == 8]
    test = [s for i, s in enumerate(samples) if i % 10 == 9]
    train = [s for i, s in enumerate(samples) if i % 10 < 8]
    if not val:
        val = [train.pop()]
    if not test:
        test = [train.pop()]
    return train, val, test


def _load_window(
    spool_dir: Optional[str], shards: Optional[Sequence[str]]
) -> List[Any]:
    """Samples of the pinned window (specific shards when given, the
    whole spool otherwise)."""
    from hydragnn_tpu.data.container import ContainerDataset
    from hydragnn_tpu.obs.spool import list_shards

    if spool_dir is None:
        raise ValueError("fine-tune needs a spool directory")
    if shards:
        dirs = [os.path.join(spool_dir, os.path.basename(s)) for s in shards]
    else:
        dirs = list_shards(spool_dir)
    out: List[Any] = []
    for d in dirs:
        out.extend(ContainerDataset(d).samples())
    return out


def fine_tune(
    log_dir: str,
    serving_run: str,
    candidate: str,
    spool_dir: Optional[str] = None,
    shards: Optional[Sequence[str]] = None,
    epochs: Optional[int] = None,
) -> Dict[str, Any]:
    """Run the incremental fine-tune; returns a small result manifest.
    Raises ``ValueError``/``FileNotFoundError`` on deterministic
    configuration problems (the CLI maps those to exit 78)."""
    # injected wedge (HYDRAGNN_INJECT_PILOT_HUNG_TUNE) fires before any
    # work so the supervisor's wall-clock belt is what kills us
    inject.maybe_pilot_hang()

    cfg_path = os.path.join(log_dir, serving_run, "config.json")
    with open(cfg_path) as f:
        config = json.load(f)
    nn_config = config["NeuralNetwork"]
    training = nn_config["Training"]
    training["num_epoch"] = int(
        epochs
        if epochs is not None
        else knobs.get_int("HYDRAGNN_PILOT_TUNE_EPOCHS", 2)
    )
    # the serving run's own continue/startfrom must not leak into the
    # fine-tune; the warm start below is explicit
    training.pop("continue", None)
    training.pop("startfrom", None)

    samples = _load_window(spool_dir, shards)
    train, val, test = _split(samples)

    from hydragnn_tpu.api import _example_for_init, create_dataloaders
    from hydragnn_tpu.models.create import create_model_config
    from hydragnn_tpu.train import (
        create_train_state,
        select_optimizer,
        train_validate_test,
    )
    from hydragnn_tpu.utils.checkpoint import load_existing_model, save_model
    from hydragnn_tpu.utils.config import save_config

    train_loader, val_loader, test_loader = create_dataloaders(
        train, val, test, config
    )
    example = _example_for_init(next(iter(train_loader)), 1)
    model, variables = create_model_config(nn_config, example)
    freeze = bool(nn_config["Architecture"].get("freeze_conv_layers"))
    tx = select_optimizer(training, freeze_conv=freeze)
    state = create_train_state(variables, tx)
    # warm start: the serving checkpoint through the VALIDATING loader
    # (sha256 sidecars, torn-pointer fallback — utils/checkpoint.py)
    state = load_existing_model(state, serving_run, log_dir)
    state, history = train_validate_test(
        model,
        tx,
        state,
        train_loader,
        val_loader,
        test_loader,
        nn_config,
        log_name=candidate,
        log_dir=log_dir,
        run_config=config,
        manifest_extra={
            "fine_tune": {
                "from_run": serving_run,
                "spool_dir": spool_dir,
                "shards": list(shards or []),
                "num_samples": len(samples),
            }
        },
    )
    save_model(state, candidate, log_dir)
    save_config(config, candidate, log_dir)
    return {
        "candidate": candidate,
        "serving_run": serving_run,
        "num_samples": len(samples),
        "epochs": training["num_epoch"],
        "splits": [len(train), len(val), len(test)],
    }


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--log-dir", required=True)
    p.add_argument("--serving-run", required=True)
    p.add_argument("--candidate", required=True)
    p.add_argument("--spool-dir", default=None)
    p.add_argument(
        "--shards",
        default=None,
        help="comma-separated shard basenames (the pinned window); "
        "default: every shard in the spool",
    )
    p.add_argument("--epochs", type=int, default=None)
    args = p.parse_args(argv)

    # injected pre-training crash (HYDRAGNN_INJECT_PILOT_TRAIN_CRASH):
    # crash-class exit; the supervisor's strip-on-restart makes the
    # retried attempt run clean
    if inject.pilot_train_crashes() > 0:
        print("pilot.tune: injected train crash", file=sys.stderr)
        return 70

    # This child is started by a server that holds its chip, and a chip
    # belongs to one process at a time: on a one-chip host the backend
    # cannot come up here. Say so and fail fast (config error: the
    # supervisor does not burn its restarts on it) instead of crashing
    # somewhere inside the first jit.
    from hydragnn_tpu.utils.platform import BackendInitError, check_backend

    try:
        check_backend()
    except BackendInitError as exc:
        print(
            f"pilot.tune: no backend for the fine-tune child: {exc}\n"
            "pilot.tune: a chip belongs to one process at a time and the "
            "serving parent holds it — give the tuner its own chip or host, "
            "or run it with JAX_PLATFORMS=cpu",
            file=sys.stderr,
        )
        return EXIT_CONFIG_ERROR

    shards = args.shards.split(",") if args.shards else None
    try:
        out = fine_tune(
            args.log_dir,
            args.serving_run,
            args.candidate,
            spool_dir=args.spool_dir,
            shards=shards,
            epochs=args.epochs,
        )
    except (FileNotFoundError, ValueError, KeyError) as exc:
        print(f"pilot.tune: config error: {exc!r}", file=sys.stderr)
        return EXIT_CONFIG_ERROR
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
