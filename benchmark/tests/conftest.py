"""The benchmark's own tests run on the CPU, at tiny sizes, on the program's
plain-XLA paths (its dispatchers engage the Pallas kernels only on a TPU;
the kernels and their backward passes are compared on the chip, by every
run's own check). Run them from the repository's root:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python -m pytest benchmark/tests -q -p no:cacheprovider

``run_training`` spreads a batch over every device it finds, so a cell's
tests run only in a process that has exactly the cell's chips: the first
call covers the one-chip cells, the second the four-chip cell (the others
skip).
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("NPROC", "16")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (os.path.dirname(BENCH), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
