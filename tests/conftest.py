"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's CI strategy of testing distributed behavior without
a cluster (reference: .github/workflows/CI.yml runs pytest serial + under
``mpirun -n 2``). Here multi-device paths are exercised on 8 virtual XLA
CPU devices so sharding/collective code compiles and runs in CI.

Must run before jax is imported anywhere.
"""

import os
import sys

os.environ.setdefault("JAX_ENABLE_X64", "0")
# Model-level introspection OFF for the suite (production default is
# ON): every tiny training test would otherwise compile the separate
# per-head diagnostics executable and lower the train step for the
# hardware ledger — measured ~2+ minutes across the suite's dozens of
# training runs, which blows the tier-1 time budget. The dedicated
# introspection tests (tests/test_introspect.py, the flight-record e2e
# in test_obs.py) and the ci.sh telemetry smoke opt back in explicitly.
os.environ.setdefault("HYDRAGNN_DIAGNOSTICS", "0")
# Persistent compilation cache: repeated test runs skip recompilation.
# Placed by the program's own rule (utils/platform.place_compile_cache,
# below): JAX_COMPILATION_CACHE_DIR when set, else one fixed directory
# of the checkout.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

# The platform helper is loaded by file path (via the jax-free
# __graft_entry__ loader) because importing it through the package would
# execute hydragnn_tpu/__init__, which imports jax before the mesh pin.
# require_ fails fast if something initialized the backend earlier.
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir))
from __graft_entry__ import _load_platform_module  # noqa: E402

_platform = _load_platform_module()
_platform.pin_virtual_cpu_mesh(8)
_platform.place_compile_cache()
_platform.require_virtual_cpu_mesh(8)
