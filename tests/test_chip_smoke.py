"""chip_smoke.py's contract as far as a CPU can show it, and the
bring-up rules around it: where the compile cache goes, a native library
keyed on its sources, a tile table that is not swallowed, an executable
cache that loads onto the device the compile used.

Children run with ``JAX_PLATFORMS=cpu``; none of them trains.
"""

import glob
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _child_env(**extra):
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("JAX_COMPILATION_CACHE_DIR", "HYDRAGNN_PALLAS", "XLA_FLAGS")
    }
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def _run(args, cwd=REPO, **env):
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=_child_env(**env),
        capture_output=True, text=True, timeout=300,
    )


def _last_line(proc):
    return json.loads(proc.stdout.strip().splitlines()[-1])


# -- chip_smoke.py ----------------------------------------------------------


def test_smoke_fails_on_cpu_before_any_training(tmp_path):
    proc = _run([SMOKE, "--out", str(tmp_path / "out")])
    assert proc.returncode != 0
    last = _last_line(proc)
    assert last["ok"] is False and "no TPU" in last["error"]
    assert last["device"]["platform"] == "cpu"
    phases = [json.loads(l)["phase"] for l in proc.stdout.splitlines()[:-1]]
    assert phases == ["device"]  # nothing ran after the device phase
    # no run directory: no training (the device phase builds the native library)
    assert [p.name for p in (tmp_path / "out").iterdir()] == ["native_build"]


@pytest.mark.parametrize(
    "args, phases",
    [
        ([], ["device", "train_scan", "train_per_step", "compiled_step", "serve", "selfcheck"]),
        (["--chips", "4"], ["device", "four_chip"]),
    ],
)
def test_smoke_phase_list_needs_no_jax(args, phases):
    # a jax import in this child would fail loudly: the name is poisoned
    code = (
        "import sys, runpy; sys.modules['jax'] = None; "
        f"sys.argv = ['chip_smoke.py', '--list-phases', *{args!r}]; "
        f"runpy.run_path({SMOKE!r}, run_name='__main__')"
    )
    proc = _run(["-c", code])
    assert proc.returncode == 0, proc.stderr
    assert json.loads(proc.stdout) == phases


def test_smoke_refuses_a_kernel_knob(tmp_path):
    proc = _run([SMOKE, "--out", str(tmp_path / "out")], HYDRAGNN_PALLAS="0")
    assert proc.returncode != 0
    last = _last_line(proc)
    assert last["ok"] is False and "HYDRAGNN_PALLAS" in last["error"]
    assert last["device"] is None  # refused before the backend came up


def test_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc = _run([str(tmp_path / "chip_smoke.py")], cwd=str(tmp_path), PYTHONPATH="")
    assert proc.returncode != 0
    assert _last_line(proc)["ok"] is False


# -- the compile cache ------------------------------------------------------

_CACHE_PROBE = (
    "import json, jax; "
    "from hydragnn_tpu.utils.platform import place_compile_cache; "
    "d = place_compile_cache(); "
    "print(json.dumps([d, jax.config.jax_compilation_cache_dir]))"
)


def test_compile_cache_env_dir_is_left_alone(tmp_path):
    want = str(tmp_path / "some" / "dir")
    proc = _run(["-c", _CACHE_PROBE], JAX_COMPILATION_CACHE_DIR=want)
    assert proc.returncode == 0, proc.stderr
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == [want, want]


def test_compile_cache_default_is_one_fixed_dir_of_the_checkout(tmp_path):
    seen = []
    for cwd in (REPO, str(tmp_path)):  # two processes, two working directories
        proc = _run(["-c", _CACHE_PROBE], cwd=cwd, PYTHONPATH=REPO)
        assert proc.returncode == 0, proc.stderr
        seen.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    assert seen[0] == seen[1]
    helper, configured = seen[0]
    assert helper == configured == os.path.join(REPO, ".jax_compile_cache")


def test_compile_cache_placement_uses_no_tempfile_pid_or_time():
    import inspect

    from hydragnn_tpu.utils import platform as plat

    src = inspect.getsource(plat)
    for banned in ("tempfile", "getpid", "time.", "mkdtemp", "uuid"):
        assert banned not in src, banned
    # every entry script that places the cache does it through the helper
    for script in ("chip_smoke.py", "bench.py", "bench_scaling.py", "tests/conftest.py"):
        with open(os.path.join(REPO, script)) as f:
            text = f.read()
        assert "place_compile_cache()" in text, script
        assert "jax_compilation_cache_dir" not in text, script


# -- no backend before the distributed runtime ------------------------------

_DRIVERS = sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "examples", "*", "*.py"))
    if "place_compile_cache" in open(p).read()
)

_IMPORT_THEN_INITIALIZE = """
import importlib.util, socket, sys
import jax
from jax._src import xla_bridge
for path in sys.argv[1:]:
    spec = importlib.util.spec_from_file_location("driver", path)
    spec.loader.exec_module(importlib.util.module_from_spec(spec))
    assert not xla_bridge.backends_are_initialized(), path
# what setup_distributed() does in a multi-process launch; it raises when
# a backend is already up
s = socket.socket(); s.bind(("localhost", 0)); port = s.getsockname()[1]; s.close()
jax.distributed.initialize(coordinator_address=f"localhost:{port}", num_processes=1, process_id=0)
print("ok", jax.process_count())
"""


def test_importing_an_example_driver_starts_no_backend():
    """The drivers call setup_distributed() inside main(); importing one
    (which places the compile cache) must leave the backend down, or a
    multi-process launch dies at jax.distributed.initialize()."""
    assert len(_DRIVERS) == 8, _DRIVERS
    proc = _run(["-c", _IMPORT_THEN_INITIALIZE, *_DRIVERS])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split()[-2:] == ["ok", "1"]


def test_backend_check_is_never_made_at_import():
    """check_backend() brings the backend up, so outside a function body
    it may stand only in scripts that never join a distributed runtime."""
    offenders = []
    for root in ("examples", "hydragnn_tpu", "tools"):
        for path in glob.glob(os.path.join(REPO, root, "**", "*.py"), recursive=True):
            with open(path) as f:
                for n, line in enumerate(f, 1):
                    if line.startswith("check_backend("):
                        offenders.append(f"{os.path.relpath(path, REPO)}:{n}")
    assert offenders == []


def test_virtual_mesh_gives_xla_thread_pool_room(monkeypatch):
    """Eight virtual devices block eight pool threads in every
    all-reduce; a pool of eight then starves the last one and XLA ends
    the process ("only 7 of them arrived on time"). The mesh recipe sizes
    the pool (NPROC) to twice the devices, raising a smaller setting."""
    from hydragnn_tpu.utils import platform as plat

    assert int(os.environ["NPROC"]) >= 16  # conftest's pin, before jax started
    monkeypatch.setenv("NPROC", "4")
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    plat.pin_virtual_cpu_mesh(8)
    assert int(os.environ["NPROC"]) >= 16


# -- built from what git would commit ---------------------------------------


def test_native_library_is_keyed_on_its_sources(tmp_path, monkeypatch):
    from hydragnn_tpu import native

    path = native._build_library()
    assert path is not None, "g++ build failed"
    assert os.path.basename(path) == f"libhgc-{native._source_digest()}.so"
    # a foreign binary under the old fixed name is never picked up
    (tmp_path / "libhgc.so").write_bytes(b"not a library")
    fresh = native._build_library(str(tmp_path))
    assert fresh is not None and fresh != str(tmp_path / "libhgc.so")
    # different sources -> a different name
    other = tmp_path / "other.cpp"
    other.write_text("int x;\n")
    monkeypatch.setattr(native, "_SRCS", [*native._SRCS, str(other)])
    assert f"libhgc-{native._source_digest()}.so" != os.path.basename(fresh)


@pytest.mark.parametrize(
    "content, outcome",
    [(None, "baked"), ('{"default": {"default": {"BN": 64}}}', "TUNE_TILES.json"), ("{not json", "raises")],
)
def test_tile_table_is_read_or_refused_never_swallowed(tmp_path, content, outcome):
    """Absent table -> baked defaults (and it says so); good table ->
    its values; mangled table -> an error."""
    from hydragnn_tpu.ops.segment_pallas import _tile_defaults

    path = tmp_path / "TUNE_TILES.json"
    if content is not None:
        path.write_text(content)
    if outcome == "raises":
        with pytest.raises(ValueError, match="not valid JSON"):
            _tile_defaults(str(path))
        return
    out = _tile_defaults(str(path))
    assert out["source"] == outcome
    assert out["BN"] == (64 if outcome == "TUNE_TILES.json" else 128)
    assert out["CE"] == 512


@pytest.mark.parametrize(
    "exc, unstackable",
    [
        (ValueError("all input arrays must have the same shape"), True),
        (jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of memory allocating 9GB"), True),
        (jax.errors.JaxRuntimeError("INTERNAL: something else broke"), False),
        (KeyError("nodes"), False),
    ],
)
def test_only_anticipated_stacking_failures_fall_back(exc, unstackable):
    from hydragnn_tpu.train.loop import _stack_refusal

    class Loader:
        def stacked_device_batches(self, epoch):
            raise exc

    if unstackable:
        assert type(exc).__name__ in _stack_refusal(Loader())
    else:
        with pytest.raises(type(exc)):
            _stack_refusal(Loader())


# -- the executable cache lands where the compile did -----------------------


def test_exec_cache_loads_onto_the_compiled_device(tmp_path):
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.utils.exec_cache import ExecCache, compat_manifest

    dev = jax.devices()[3]  # not the default device, on the 8-device mesh
    x = jax.device_put(jnp.arange(8.0), dev)
    fn = jax.jit(lambda a: a * 2.0 + 1.0)
    cache = ExecCache(str(tmp_path))
    compat = compat_manifest()
    fresh, hit, _ = cache.get_or_compile("k", fn, (x,), compat)
    assert not hit
    loaded, hit, _ = cache.get_or_compile("k", fn, (x,), compat)
    assert hit
    out = loaded(x)
    assert out.sharding.device_set == {dev}
    np.testing.assert_array_equal(np.asarray(out), np.asarray(fresh(x)))


def test_exec_cache_entry_is_sound_when_jax_cache_holds_the_program(tmp_path):
    """On the CPU an executable that JAX read back from its persistent
    cache cannot be serialized again (tools/chip_probe.py reserialize);
    the executable cache must still store an entry that loads and runs."""
    import jax.numpy as jnp

    from hydragnn_tpu.utils.exec_cache import ExecCache, compat_manifest

    def program():  # a new function object each time: no in-memory hit
        return jax.jit(lambda a: jnp.tanh(a @ a.T) * 0.25 + 3.0)

    x = jnp.arange(256.0).reshape(16, 16)
    hits = []

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits.append(event)

    jax.monitoring.register_event_listener(on_event)
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    size = jax.config.jax_persistent_cache_min_entry_size_bytes
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        want = np.asarray(program().lower(x).compile()(x))  # JAX's cache holds it now
        program().lower(x).compile()
        assert hits, "JAX's persistent cache did not supply the second compile"
        cache = ExecCache(str(tmp_path))
        compat = compat_manifest()
        _, hit, _ = cache.get_or_compile("k", program(), (x,), compat)
        assert not hit
        loaded, hit, _ = cache.get_or_compile("k", program(), (x,), compat)
        assert hit
        np.testing.assert_array_equal(np.asarray(loaded(x)), want)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", size)
        jax.monitoring.unregister_event_listener(on_event)


# -- one process per chip: launchers fail clearly, never hang ---------------


def _chip_taken():
    from hydragnn_tpu.utils import platform as plat

    def taken():
        raise plat.BackendInitError(
            "", RuntimeError("The TPU is already in use by process with pid 7")
        )

    return plat, taken


def test_pilot_tune_child_fails_fast_when_the_parent_holds_the_chip(monkeypatch, capsys):
    from hydragnn_tpu.pilot import tune
    from hydragnn_tpu.resilience.preempt import EXIT_CONFIG_ERROR

    plat, taken = _chip_taken()
    monkeypatch.setattr(plat, "check_backend", taken)
    monkeypatch.setattr(tune, "fine_tune", lambda *a, **k: pytest.fail("trained without a backend"))
    rc = tune.main(["--log-dir", "x", "--serving-run", "r", "--candidate", "c"])
    assert rc == EXIT_CONFIG_ERROR  # fail-fast class: the supervisor does not retry it
    err = capsys.readouterr().err
    assert "one process at a time" in err and "already in use" in err


def test_run_guard_classifies_a_held_chip_as_fail_fast(capsys):
    from hydragnn_tpu.resilience.preempt import EXIT_CONFIG_ERROR, run_guard

    _, taken = _chip_taken()
    with pytest.raises(SystemExit) as ei:
        with run_guard():
            taken()
    assert ei.value.code == EXIT_CONFIG_ERROR
    assert "one process per chip" in capsys.readouterr().err
