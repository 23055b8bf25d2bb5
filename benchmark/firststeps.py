"""The first steps of a cell's own ``run_training``, with no window: what
the readings (``readings.py``) and the tests compare with the reference.

One epoch through the same entry, taps and compiled program as a measured
run; the run ends by itself.
"""

from __future__ import annotations

import copy
import os
import shutil


def capture(cell, seed: int, work_dir: str, epochs: int = 1):
    """Returns (taps, raw samples) after ``epochs`` epochs of the cell."""
    from taps import Taps

    from hydragnn_tpu.api import run_training

    cell = copy.copy(cell)
    cell.run_config = copy.deepcopy(cell.run_config)
    cell.run_config["NeuralNetwork"]["Training"]["num_epoch"] = epochs
    raw = cell.fam.generate(cell.traffic, seed)
    samples = cell.fam.program_samples(raw)
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir, exist_ok=True)
    taps = Taps(cell, seed, float("inf"), samples)
    with taps:
        run_training(cell.run_config, samples=samples, log_dir=work_dir)
    shutil.rmtree(work_dir, ignore_errors=True)
    return taps, raw
