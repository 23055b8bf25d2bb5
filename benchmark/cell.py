"""A cell = one configuration file under one workload file.

``workloads/<cell>.json`` names its configuration (``configs/<name>.json``),
its chips, its traffic parameters (read by the family's ``generate``) and
the ``Training`` / ``Architecture`` keys a user of ``run_training`` would
set for this job (batch size, checkpoint interval, SyncBatchNorm on a
mesh). The configuration names its family (``families/<family>.py``): the
samples, their type, the reference and the exact checks are reached
through ``cell.fam`` alone. Nothing here is specific to one cell or one
family: a new cell is a new pair of files, a new family one file more.
"""

from __future__ import annotations

import copy
import dataclasses
import json
import os
from typing import Any, Dict

import families

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    chips: int
    traffic: Dict[str, Any]
    run_config: Dict[str, Any]  # the dictionary handed to run_training
    family: str  # module under benchmark/families/
    reference: str  # module under benchmark/reference/
    cost_model: str  # key in cost.MODELS
    warmup_epochs: int
    trace_epochs: int
    check_steps: int
    rehearse: bool
    limits: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def fam(self):
        return families.load(self.family)

    @property
    def training(self) -> Dict[str, Any]:
        return self.run_config["NeuralNetwork"]["Training"]

    @property
    def batch_size(self) -> int:
        return int(self.training["batch_size"])

    @property
    def ckpt_every(self) -> int:
        return max(int(self.training.get("checkpoint_every", 0) or 0), 1)


def load_cell(name: str, rehearse: bool = False) -> Cell:
    wl_path = os.path.join(HERE, "workloads", f"{name}.json")
    if not os.path.isfile(wl_path):
        raise SystemExit(f"no such workload: {wl_path}")
    wl = _load(wl_path)
    cfg_file = _load(os.path.join(HERE, "configs", f"{wl['config']}.json"))
    run_config = copy.deepcopy(cfg_file["run_training"])
    over = wl.get("rehearse", {}) if rehearse else {}
    traffic = over.get("traffic", wl["traffic"])
    nn = run_config["NeuralNetwork"]
    nn["Training"].update(wl.get("training", {}))
    nn["Architecture"].update(wl.get("architecture", {}))
    if rehearse:
        nn["Training"].update(over.get("training", {}))
        families.load(cfg_file["family"]).rehearsal_overrides(nn, dict(over.get("architecture", {})))
    # far more epochs than any window needs: the harness ends the run
    nn["Training"]["num_epoch"] = 1_000_000
    return Cell(
        name=name,
        config_name=wl["config"],
        chips=int(wl["chips"]),
        traffic=traffic,
        run_config=run_config,
        family=cfg_file["family"],
        reference=cfg_file["reference"],
        cost_model=cfg_file["cost_model"],
        warmup_epochs=int(over.get("warmup_epochs", wl["warmup_epochs"])),
        trace_epochs=int(wl.get("trace_epochs", 3)),
        check_steps=int(wl.get("check_steps", 3)),
        rehearse=rehearse,
        limits=dict(over.get("limits", wl.get("limits", {}))),
    )


def place_compile_cache(rehearse: bool) -> None:
    """JAX's persistent cache at a fixed path inside the checkout (the path
    is part of the key), every program kept, the sub-second eager ones too:
    a training start makes some hundreds of them. Off in a rehearsal."""
    import jax

    if rehearse:
        jax.config.update("jax_enable_compilation_cache", False)
        return
    jax.config.update("jax_compilation_cache_dir", os.path.join(HERE, "_cache", "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

