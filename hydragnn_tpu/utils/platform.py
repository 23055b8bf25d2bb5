"""Backend bring-up: the platform check, the virtual CPU mesh the tests
run on, and where JAX's persistent compilation cache goes.

Stock JAX honours ``JAX_PLATFORMS``; nothing here sets or overrides a
platform except :func:`pin_virtual_cpu_mesh`, which the tests and the
multi-chip dry run use on purpose. A TPU that is attached to this
machine belongs to ONE process at a time: a backend that fails to come
up means the configuration is wrong or another process holds the chip —
a fault of whoever started two, not weather — so a failed init fails at
once with :class:`BackendInitError` and is never retried.

This module imports no jax at module level, so callers can load it (by
file path if need be) before jax.
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
#: where JAX's persistent compilation cache goes when the environment
#: names no place for it: one fixed, git-ignored directory of the
#: checkout (the path is part of the cache's key, so it must not move)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_compile_cache")
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


class BackendInitError(RuntimeError):
    """The JAX backend failed to come up, or the one that came up is not
    the one ``JAX_PLATFORMS`` asked for.

    Driver-facing scripts (bench.py, bench_serve.py) catch this and emit
    ``.record`` — a compact structured failure line — rather than dying
    mid-traceback. ``.record`` keeps the backend's message truncated so
    the whole record survives a ~2000-char stdout tail capture."""

    def __init__(self, platform: str, cause: BaseException, stage: str = "backend_init"):
        msg = str(cause).strip() or repr(cause)
        # keep the tail: jax backend errors put the actionable line last
        short = msg[-400:] if len(msg) > 400 else msg
        super().__init__(
            f"JAX backend init failed for JAX_PLATFORMS={platform!r}: {short}"
        )
        self.record = {
            "failure": "backend_init",
            "stage": stage,
            "jax_platforms": platform,
            "error": short,
            "error_type": type(cause).__name__,
        }


def pin_virtual_cpu_mesh(n_devices: int = 8) -> None:
    """Force jax onto a virtual CPU mesh of at least ``n_devices`` devices.

    The single source of the recipe used by ``tests/conftest.py`` and
    ``__graft_entry__.dryrun_multichip``: set ``JAX_PLATFORMS=cpu``,
    ensure ``XLA_FLAGS`` requests >= ``n_devices`` host devices (raising
    a pre-existing smaller count, since XLA honors whatever value is
    present when the backend initializes), and size XLA's CPU thread
    pools (``NPROC``) so that the devices' collectives cannot starve.

    Must be called before the jax backend initializes.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(_COUNT_FLAG + r"=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (flags + f" {_COUNT_FLAG}={n_devices}").strip()
    elif int(m.group(1)) < n_devices:
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0), f"{_COUNT_FLAG}={n_devices}"
        )

    # XLA:CPU runs each virtual device's share of a collective on one
    # thread of a pool sized to the host's cores (NPROC overrides), and
    # a participant holds its thread until all have arrived. With as
    # many devices as threads, one more task in the pool starves the
    # last participant and XLA ends the process after 40 s ("Expected 8
    # threads to join the rendezvous, but only 7 of them arrived on
    # time"). Give the pool twice the devices.
    room = max(os.cpu_count() or 1, 2 * n_devices)
    nproc = os.environ.get("NPROC", "")
    if not nproc.isdigit() or int(nproc) < room:
        os.environ["NPROC"] = str(room)

    import jax

    try:
        # jax read the env at import; a caller that imported it before
        # this call still lands on the CPU
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass  # backend already up; require_virtual_cpu_mesh diagnoses it


def require_virtual_cpu_mesh(n_devices: int) -> None:
    """Fail fast (explicit raise — survives ``python -O``) if jax did not
    land on a CPU backend with >= ``n_devices`` devices, i.e. the backend
    initialized before :func:`pin_virtual_cpu_mesh` took effect."""
    import jax

    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "expected the virtual CPU mesh but the jax backend is "
            f"{jax.default_backend()!r} — jax initialized before "
            "pin_virtual_cpu_mesh() was called"
        )
    if len(jax.devices()) < n_devices:
        raise RuntimeError(
            f"need {n_devices} virtual CPU devices, got {len(jax.devices())} "
            "— XLA_FLAGS was read before "
            f"{_COUNT_FLAG} took effect (backend initialized too early)"
        )


def check_backend():
    """Bring the backend up and check that it is one ``JAX_PLATFORMS``
    asked for. Returns ``jax.devices()``.

    A backend that cannot come up, or a mismatch (jax initialized under
    a different setting before the variable was changed), raises
    :class:`BackendInitError` at once — no retry: on a locally attached
    chip "busy" means a second process holds it.

    This INITIALIZES the backend, so it is never called at import: a
    multi-process driver must reach ``jax.distributed.initialize()``
    (``parallel/mesh.py:setup_distributed``) first. Its callers are
    ``run_training``, the root scripts and ``pilot.tune``'s child."""
    import jax

    plat = os.environ.get("JAX_PLATFORMS", "")
    try:
        devices = jax.devices()
    # RuntimeError on current jax; backends() can surface a bare
    # AssertionError when no platform comes up
    except (RuntimeError, AssertionError) as exc:
        raise BackendInitError(plat, exc) from exc
    # JAX_PLATFORMS may be a priority list ("tpu,cpu"); any entry is a
    # legitimate outcome (jax falls back down the list)
    wants = [p.strip().lower() for p in plat.split(",") if p.strip()]
    got = devices[0].platform.lower()
    if wants and got not in wants:
        raise BackendInitError(
            plat,
            RuntimeError(
                f"JAX_PLATFORMS={plat!r} requested but the jax backend is "
                f"{got!r} — jax initialized before the variable was set"
            ),
            stage="platform_check",
        )
    return devices


def place_compile_cache() -> str:
    """Decide where JAX's persistent compilation cache lives and return
    the directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
    it itself and this sets no other; where it is not, the cache goes to
    :data:`DEFAULT_COMPILE_CACHE_DIR`. Call before the first compile."""
    env = os.environ.get(_CACHE_ENV)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR
