#!/usr/bin/env bash
# CI protocol runner — the committed encoding of the test discipline
# (VERDICT r02 item 7), mirroring the reference's CI pipeline
# (/root/reference/.github/workflows/CI.yml: black format gate, serial
# pytest, the same suite again under mpirun -n 2).
#
# Stages:
#   1. format gate      — `black --check .` when black is installed; the
#                         baked TPU image ships no formatter, so the gate
#                         degrades to a full-tree syntax check (compileall)
#                         and prints which gate ran.
#   2. graftlint +      — tools/graftlint.py (docs/LINT.md): the
#      graftsync          `--changed` pre-commit fast path first, then
#                         the AST invariant linter over the whole tree
#                         (HG001 host-sync-in-hot-path ... HG008
#                         tracer-leak) with an empty committed baseline,
#                         JSON findings artifact, committed-artifact
#                         schema validation (--artifacts: flight JSONLs
#                         + the SCALING_*/TUNE_TILES/
#                         BENCH_CI_BASELINE/BENCH_FLEET machine JSON
#                         schemas), and a self-test that injects one
#                         violation per guarded rule (HG001/HG002/
#                         HG005/HG006 — including the aliased `from
#                         jax.sharding import Mesh as M` case the old
#                         grep missed) and requires the linter to fail
#                         on each. Then tools/graftsync.py (docs/LINT.md
#                         HS rules): the thread-safety/lock-discipline
#                         analyzer — same --changed fast path, full-tree
#                         scan with an EMPTY committed baseline, and a
#                         self-test injecting one violation per HS rule
#                         (HS001 unguarded shared state ... HS006
#                         lock-order cycle), each of which must
#                         individually fail the gate.
#   3. graftcheck       — tools/graftcheck.py (docs/LINT.md, CC rules):
#                         the compiled-IR contract checker — lowers the
#                         hot entry points under the pure-DP and fsdp=2
#                         layouts on the forced 8-device host mesh and
#                         proves CC001 host-transfer freedom, CC002
#                         bf16 dtype discipline, CC003 collective
#                         layout, CC004 bucket-stable compiles, CC005
#                         donation landing, and CC006 static VMEM
#                         budgeting from the StableHLO / post-SPMD HLO
#                         (JSON findings artifact next to graftlint's);
#                         then a self-test injects one REAL violation
#                         per contract (HYDRAGNN_INJECT_GRAFTCHECK) and
#                         requires each contract to reject its own.
#   4. chip hygiene     — tools/chip_hygiene.py reports processes holding
#                         accelerator devices/lockfiles (informational:
#                         a lingering holder from a dead run is why a
#                         backend init finds the chip taken — it fails
#                         at once, no retry).
#   5. serial suite     — python -m pytest tests/ -q on the virtual
#                         8-device CPU mesh (conftest pins it). This
#                         INCLUDES the 2-OS-process distributed pass: the
#                         reference re-runs its whole suite under
#                         `mpirun -n 2`; here the multi-process rendezvous
#                         is exercised by tests/test_multiprocess.py, which
#                         spawns 2 python processes with a shared
#                         coordinator itself (TPU-native launch shape —
#                         jax.distributed, not MPI).
#   6. partitioner      — unified-Partitioner gate (docs/PARALLELISM.md):
#      smoke               (a) graftlint rule HG002 — no module outside
#                         hydragnn_tpu/parallel/ may construct a
#                         jax.sharding.Mesh directly (train/serve/bench
#                         obtain meshes exclusively through Partitioner);
#                         (b) forced 8-device CPU host mesh, one tiny
#                         train run with Parallel.fsdp=2 — the flight
#                         manifest must carry the parallel block with
#                         sharded param/opt leaves and a per-device byte
#                         drop, and the loss history must equal the
#                         fsdp=1 data-parallel run's.
#   7. telemetry smoke  — one tiny training through api.run_training,
#                         then the emitted flight record is schema-
#                         validated (tools/obs_report.py --validate
#                         --require-complete) and pretty-printed: the
#                         committed proof that a default run leaves a
#                         parseable evidence artifact
#                         (docs/OBSERVABILITY.md).
#   8. fault-injection  — a tiny run is SIGTERM-killed mid-epoch via
#      smoke               HYDRAGNN_INJECT_SIGTERM_STEP, the restart
#                         supervisor (tools/supervise.py) resumes it to
#                         completion, and the merged flight record must
#                         validate with exactly one preempted run_end +
#                         one resumed event (docs/RESILIENCE.md). The run
#                         shares a persistent executable cache
#                         (HYDRAGNN_EXEC_CACHE survives the restart), so
#                         the resumed segment must reach first-step-ready
#                         as a cache HIT with 0 new compiles.
#   9. serve-chaos      — a tiny trained run is served; a poison request
#      smoke               is injected (raise-in-forward), then the
#                         checkpoint is HOT-reloaded into the running
#                         server; the server must answer identically
#                         afterwards, the serve flight record must
#                         validate (quarantine/reload event kinds), and
#                         tools/serve_probe.py must exit 0 on the
#                         exported Prometheus textfile
#                         (docs/RESILIENCE.md "Serving resilience").
#                         Then the lock-order witness smoke: the same
#                         serve is re-run with HYDRAGNN_LOCK_DEBUG=1
#                         and an injected lock-order inversion
#                         (HYDRAGNN_INJECT_LOCK_ORDER) — the witness
#                         must convert it into a schema-valid
#                         `lock_order` flight event (thread stacks
#                         attached) while the server keeps answering
#                         and the probe still exits 0: the witness is
#                         observability, never an availability risk.
#  10. exec-cache smoke — persistent AOT executable cache (docs/PERF.md
#                         "r09 cold start"): train a tiny model once,
#                         start TWO servers (separate processes) against
#                         one cache dir — the second must perform 0 AOT
#                         compiles (every bucket a disk hit) — then
#                         corrupt one entry and require a LOUD
#                         single-entry eviction + recompile, not a crash.
#  11. perf gate        — tools/bench_gate.py: a tiny fixed-config bench
#                         measured with D2H-fenced segments and compared
#                         against the committed BENCH_CI_BASELINE.json
#                         (>15% graphs/sec regression fails; MFU too on
#                         TPU; >15% cost-model bytes/step INCREASE
#                         fails), then self-tests proving the gate fails
#                         on an injected slowdown and on injected
#                         cost-model traffic; plus the warm-start arm —
#                         a warm executable-cache start must cost <50%
#                         of the cold start and 0 compiles.
#  12. full matrix      — opt-in (CI_FULL=1): all 7 models x head configs
#                         trained to the reference accuracy thresholds
#                         (HYDRAGNN_FULL_MATRIX=1, ~15 min).
#  13. chip smoke       — opt-in (CI_TPU=1, needs a real TPU):
#                         python chip_smoke.py — the flagship train and
#                         serve path plus the on-chip kernel-vs-XLA
#                         checks, all in ONE process (a chip belongs to
#                         one process at a time).
#
# Usage: ./ci.sh            # stages 1-11 (the default CI gate)
#        CI_FULL=1 ./ci.sh  # + acceptance matrix
#        CI_TPU=1  ./ci.sh  # + chip smoke on the attached TPU
set -euo pipefail
cd "$(dirname "$0")"

echo "== format gate =="
if python -m black --version >/dev/null 2>&1; then
    python -m black --check .
elif command -v black >/dev/null 2>&1; then
    black --check .
else
    echo "black not installed in this image; running syntax gate (compileall)"
    python -m compileall -q hydragnn_tpu tests examples tools bench.py bench_scaling.py bench_serve.py __graft_entry__.py
fi

echo "== graftlint (AST invariant linter, docs/LINT.md) =="
# The --changed fast path first: this is the exact pre-commit loop a
# developer runs locally (working tree + index vs HEAD), so CI proves
# the fast path itself stays healthy. The full-tree scan below remains
# the authoritative gate — --changed narrows WHICH files, never WHICH
# rules.
python tools/graftlint.py --changed || {
    echo "FAIL: graftlint --changed (pre-commit fast path) found violations"
    exit 1
}
# Full tree, all rules, empty committed baseline. On failure the JSON
# findings artifact is left at /tmp/graftlint_findings.json for CI to
# collect.
python tools/graftlint.py --json /tmp/graftlint_findings.json || {
    echo "FAIL: graftlint found violations (JSON artifact: /tmp/graftlint_findings.json)"
    exit 1
}
# committed flight artifacts must validate against obs/flight.py's schema
python tools/graftlint.py --artifacts
# Self-test: the linter must FAIL on an injected violation of each
# statically-guarded invariant. HG002's fixture is specifically the
# aliased import the old grep gate could not see.
LINT_ST="$(mktemp -d)"
cat > "$LINT_ST/hg001_hot_sync.py" <<'EOF'
def make_train_step(model):
    def step(state, batch):
        return float(state.loss)

    return step
EOF
cat > "$LINT_ST/hg002_aliased_mesh.py" <<'EOF'
from jax.sharding import Mesh as M


def build(devices):
    return M(devices, ("data",))
EOF
cat > "$LINT_ST/hg005_unknown_kind.py" <<'EOF'
def emit(flight):
    flight.record("totally_unknown_kind", x=1)
EOF
cat > "$LINT_ST/hg006_rogue_knob.py" <<'EOF'
import os


def read():
    return os.environ.get("HYDRAGNN_NOT_A_KNOB")
EOF
for rule in HG001 HG002 HG005 HG006; do
    fixture="$(ls "$LINT_ST"/$(echo "$rule" | tr '[:upper:]' '[:lower:]')_*.py)"
    if python tools/graftlint.py --rule "$rule" --strict --no-baseline "$fixture" >/dev/null 2>&1; then
        echo "FAIL: graftlint self-test — $rule did not flag $fixture"
        exit 1
    fi
done
echo "graftlint self-test: HG001/HG002/HG005/HG006 each reject their injected violation"
rm -rf "$LINT_ST"

echo "== graftsync (thread-safety/lock-discipline analyzer, docs/LINT.md HS rules) =="
# Same shape as graftlint: the --changed pre-commit fast path first,
# then the authoritative full-tree scan against the EMPTY committed
# baseline (tools/graftsync_baseline.json — every finding in the
# shipped tree is a regression, not a grandfathered debt).
python tools/graftsync.py --changed || {
    echo "FAIL: graftsync --changed (pre-commit fast path) found violations"
    exit 1
}
python tools/graftsync.py --json /tmp/graftsync_findings.json || {
    echo "FAIL: graftsync found violations (JSON artifact: /tmp/graftsync_findings.json)"
    exit 1
}
# Self-test: each HS rule must individually FAIL on an injected
# violation of the invariant it guards. Fixtures live in a temp dir
# (tests/ and lint/fixtures are exempt from the HS path policy).
SYNC_ST="$(mktemp -d)"
cat > "$SYNC_ST/hs001_unguarded_state.py" <<'EOF'
import threading


class Box:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []

    def add(self, x):
        self._items.append(x)
EOF
cat > "$SYNC_ST/hs002_bare_acquire.py" <<'EOF'
import threading

_L = threading.Lock()


def f(work):
    _L.acquire()
    work()
    _L.release()
EOF
cat > "$SYNC_ST/hs003_sleep_under_lock.py" <<'EOF'
import threading
import time

_L = threading.Lock()


def f():
    with _L:
        time.sleep(0.1)
EOF
cat > "$SYNC_ST/hs004_unjoined_spawn.py" <<'EOF'
import threading


def work():
    pass


def main():
    t = threading.Thread(target=work)
    t.start()
EOF
cat > "$SYNC_ST/hs005_undeclared_root.py" <<'EOF'
import threading


def work():
    pass


def main():
    threading.Thread(target=work, daemon=True).start()
EOF
cat > "$SYNC_ST/hs006_lock_order_cycle.py" <<'EOF'
import threading


class A:
    def __init__(self):
        self._la = threading.Lock()
        self._lb = threading.Lock()

    def ab(self):
        with self._la:
            with self._lb:
                pass

    def ba(self):
        with self._lb:
            with self._la:
                pass
EOF
for rule in HS001 HS002 HS003 HS004 HS005 HS006; do
    fixture="$(ls "$SYNC_ST"/$(echo "$rule" | tr '[:upper:]' '[:lower:]')_*.py)"
    if python tools/graftsync.py --rule "$rule" --strict --no-baseline "$fixture" >/dev/null 2>&1; then
        echo "FAIL: graftsync self-test — $rule did not flag $fixture"
        exit 1
    fi
done
echo "graftsync self-test: HS001..HS006 each reject their injected violation"
rm -rf "$SYNC_ST"

echo "== graftcheck (compiled-IR contract checker, docs/LINT.md CC rules) =="
# Lowers the registered hot entry points (train step, scan-epoch body,
# eval/stats steps, serve bucket ladder) under BOTH CI layouts — pure-DP
# (data=8) and fsdp=2 (data=4, fsdp=2) — on the forced 8-device host
# mesh and proves the six compiled-IR contracts from the StableHLO /
# post-SPMD HLO text. Empty committed baseline
# (tools/graftcheck_baseline.json); JSON findings artifact published
# next to graftlint's for CI to collect.
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python tools/graftcheck.py --json /tmp/graftcheck_findings.json || {
    echo "FAIL: graftcheck found compiled-IR contract violations (JSON artifact: /tmp/graftcheck_findings.json)"
    exit 1
}
# Self-test: each contract must individually reject a REAL injected
# violation — the injection (HYDRAGNN_INJECT_GRAFTCHECK, docs/LINT.md
# "Self-test injections") perturbs the lowered program itself (a forced
# host callback, an f32 edge dot, a rogue collective, ...), not the
# checker, so a pass here proves the contract detects the defect class,
# not merely that a flag flips an exit code.
for cc in cc001 cc002 cc003 cc004 cc005 cc006; do
    CC="$(echo "$cc" | tr '[:lower:]' '[:upper:]')"
    if HYDRAGNN_INJECT_GRAFTCHECK="$cc" \
        XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python tools/graftcheck.py --layout dp --contract "$CC" --no-baseline \
        >/dev/null 2>&1; then
        echo "FAIL: graftcheck self-test — $CC did not reject its injected violation"
        exit 1
    fi
done
echo "graftcheck self-test: CC001..CC006 each reject their injected violation"

echo "== chip hygiene report =="
python tools/chip_hygiene.py || true

echo "== serial suite (virtual 8-device CPU mesh, incl. 2-process pass) =="
python -m pytest tests/ -q

echo "== partitioner smoke (HG002 mesh gate; fsdp=2 train == fsdp=1, flight parallel block) =="
# Train, serve, and bench obtain meshes/shardings exclusively through the
# Partitioner: no module outside hydragnn_tpu/parallel/ may construct a
# jax.sharding.Mesh directly. tests/ are exempt (they build adversarial
# meshes on purpose). AST-accurate gate (graftlint HG002, docs/LINT.md):
# unlike the old `grep -rn 'Mesh('`, it also catches aliased imports
# (`from jax.sharding import Mesh as M`) and `jax.sharding.Mesh(...)`.
python tools/graftlint.py --rule HG002 --strict \
    hydragnn_tpu bench.py bench_scaling.py bench_serve.py tools examples __graft_entry__.py
PART_DIR="$(mktemp -d)"
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python - "$PART_DIR" <<'EOF'
import glob
import sys

import numpy as np

import jax

jax.config.update("jax_platforms", "cpu")

from hydragnn_tpu.api import run_training
from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.flagship import flagship_config
from hydragnn_tpu.obs.flight import read_flight_record
from hydragnn_tpu.parallel import FSDP_AXIS

out = sys.argv[1]
assert jax.local_device_count() == 8, jax.devices()


def cfg(fsdp):
    c = flagship_config(hidden_dim=8, num_conv_layers=2, batch_size=8, num_epoch=2)
    c["NeuralNetwork"]["Parallel"] = {"fsdp": fsdp}
    return c


def data():
    return deterministic_graph_data(
        number_configurations=24,
        unit_cell_x_range=(2, 3),
        unit_cell_y_range=(2, 3),
        unit_cell_z_range=(2, 3),
        seed=0,
    )


_, _, hist_dp, _ = run_training(cfg(1), samples=data(), log_dir=out + "/dp/")
_, state, hist_f, _ = run_training(cfg(2), samples=data(), log_dir=out + "/fsdp/")

# the fsdp layout changes WHERE state bytes live, never what is computed
np.testing.assert_allclose(hist_f["train_loss"], hist_dp["train_loss"], rtol=1e-5)

# committed shardings, not inference: param leaves carry the fsdp axis
sharded = sum(
    any(
        e == FSDP_AXIS or (isinstance(e, tuple) and FSDP_AXIS in e)
        for e in leaf.sharding.spec
        if e is not None
    )
    for leaf in jax.tree_util.tree_leaves(state.params)
)
assert sharded > 0, "no fsdp-sharded parameter leaves"

# flight parallel block: mesh shape, fsdp factor, per-device byte drop
flight = glob.glob(out + "/fsdp/*/flight.jsonl")[0]
start = [e for e in read_flight_record(flight) if e["kind"] == "run_start"][0]
par = start["manifest"]["parallel"]
assert par["available"] and par["fsdp"] == 2, par
assert par["mesh"]["shape"] == {"data": 4, "fsdp": 2}, par["mesh"]
assert par["params"]["sharded"] == sharded, (par["params"], sharded)
assert par["params"]["bytes_per_device"] < par["params"]["bytes_global"]
assert par["opt"]["bytes_per_device"] < par["opt"]["bytes_global"]
print(
    f"partitioner smoke: OK (loss histories equal, {sharded} fsdp-sharded "
    f"param leaves, {par['params']['bytes_per_device']}/"
    f"{par['params']['bytes_global']} param bytes per device)"
)
EOF
PART_FLIGHT="$(ls "$PART_DIR"/fsdp/*/flight.jsonl)"
# --validate must surface the parallel block alongside the verdict
PART_OUT="$(python tools/obs_report.py --validate "$PART_FLIGHT")"
echo "$PART_OUT"
echo "$PART_OUT" | grep -q "parallel: mesh=" || {
    echo "FAIL: --validate did not surface the parallel block"; exit 1; }
rm -rf "$PART_DIR"

echo "== telemetry smoke (tiny 2-head training -> schema-valid v2 flight record with head diagnostics + MFU ledger) =="
SMOKE_DIR="$(mktemp -d)"
JAX_PLATFORMS=cpu python - "$SMOKE_DIR" <<'EOF'
import sys

from hydragnn_tpu.api import run_training
from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.flagship import flagship_config

# trimmed to TWO heads (graph energy + one node head): the introspection
# smoke must exercise a genuinely multi-head record without the full
# flagship's 4-head cost
cfg = flagship_config(hidden_dim=8, num_conv_layers=2, batch_size=5, num_epoch=2)
voi = cfg["NeuralNetwork"]["Variables_of_interest"]
voi["output_names"] = ["sum_x_x2_x3", "x"]
voi["output_index"] = [0, 0]
voi["type"] = ["graph", "node"]
cfg["NeuralNetwork"]["Architecture"]["task_weights"] = [1.0, 1.0]
samples = deterministic_graph_data(
    number_configurations=20,
    unit_cell_x_range=(2, 3),
    unit_cell_y_range=(2, 3),
    unit_cell_z_range=(2, 3),
    seed=0,
)
run_training(cfg, samples=samples, log_dir=sys.argv[1] + "/logs/")
EOF
FLIGHT="$(ls "$SMOKE_DIR"/logs/*/flight.jsonl)"
python tools/obs_report.py --validate --require-complete "$FLIGHT"
python tools/obs_report.py "$FLIGHT"
# the --heads view must render the diagnosis non-empty
HEADS_OUT="$(python tools/obs_report.py --heads "$FLIGHT")"
echo "$HEADS_OUT"
echo "$HEADS_OUT" | grep -q "task-conflict matrix" || {
    echo "FAIL: --heads view did not render the conflict matrix"; exit 1; }
python - "$FLIGHT" <<'EOF'
import sys

from hydragnn_tpu.obs.flight import read_flight_record

ev = read_flight_record(sys.argv[1])
eps = [e for e in ev if e.get("kind") == "epoch"]
assert eps and all(e.get("v") == 2 for e in eps), "epoch events must be schema v2"
names = ["sum_x_x2_x3", "x"]
for e in eps:
    heads, hw = e["heads"], e["hw"]
    assert heads["available"] and sorted(heads["grad_norm"]) == sorted(names)
    assert len(heads["cosine"]) == 2 and len(heads["cosine"][0]) == 2
    assert sorted(heads["mae"]) == sorted(names) and sorted(heads["rmse"]) == sorted(names)
    assert sorted(e["train_tasks"]) == sorted(names), "per-task losses must be name-keyed"
    # MFU ledger: achieved TFLOP/s + an MFU slot (None off-TPU) or an
    # explicit available:false; memory watermark always explicit
    assert "available" in hw and "available" in hw["memory"]
    if hw["available"]:
        assert hw["achieved_tflops"] > 0 and "mfu" in hw
assert eps[-1]["compiles"]["unexpected"] is False, "diagnostics caused a recompile"
print("introspection smoke: OK (v2 record, head diagnostics + MFU ledger present)")
EOF
rm -rf "$SMOKE_DIR"

echo "== fault-injection smoke (SIGTERM mid-epoch -> supervisor resume) =="
FAULT_DIR="$(mktemp -d)"
cat > "$FAULT_DIR/child.py" <<'EOF'
import sys

from hydragnn_tpu.resilience import run_guard
from hydragnn_tpu.api import run_training
from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.flagship import flagship_config

cfg = flagship_config(hidden_dim=8, num_conv_layers=2, batch_size=5, num_epoch=2)
cfg["NeuralNetwork"]["Training"]["checkpoint_every"] = 1
# pin per-step dispatch in BOTH segments: the injection env forces
# per_step in segment 1 but is stripped on restart, and the executable
# cache key includes the dispatch mode — the resumed segment must ask
# for the SAME program to warm-start from the cache
cfg["NeuralNetwork"]["Training"]["scan_epoch"] = False
samples = deterministic_graph_data(
    number_configurations=20,
    unit_cell_x_range=(2, 3),
    unit_cell_y_range=(2, 3),
    unit_cell_z_range=(2, 3),
    seed=0,
)
with run_guard():
    run_training(cfg, samples=samples, log_dir=sys.argv[1] + "/logs/")
EOF
# PYTHONPATH: the child script lives in the temp dir, so the repo must
# reach its sys.path through the environment
# HYDRAGNN_EXEC_CACHE is NOT an injection var, so it survives the
# supervisor's restart env-strip: the resumed segment finds the
# executable segment 1 stored and must not recompile it
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" HYDRAGNN_INJECT_SIGTERM_STEP=2 \
    HYDRAGNN_EXEC_CACHE="$FAULT_DIR/exec_cache" \
    python tools/supervise.py \
    --flight "$FAULT_DIR/supervisor.jsonl" -- \
    python "$FAULT_DIR/child.py" "$FAULT_DIR"
FAULT_FLIGHT="$(ls "$FAULT_DIR"/logs/*/flight.jsonl)"
python tools/obs_report.py --faults "$FAULT_FLIGHT"
python tools/obs_report.py --validate "$FAULT_FLIGHT" "$FAULT_DIR/supervisor.jsonl"
python - "$FAULT_FLIGHT" <<'EOF'
import sys

from hydragnn_tpu.obs.flight import read_flight_record

ev = read_flight_record(sys.argv[1])
ends = [e for e in ev if e.get("kind") == "run_end"]
assert [e["status"] for e in ends] == ["preempted", "completed"], ends
assert sum(1 for e in ev if e.get("kind") == "resumed") == 1, [
    e.get("kind") for e in ev
]
# warm auto-resume: segment 1 compiled+stored the train step (miss),
# segment 2 must reach first-step-ready as a cache HIT with 0 compiles
ready = [
    e
    for e in ev
    if e.get("kind") == "exec_cache" and e.get("event") == "train_ready"
]
assert len(ready) == 2, ready
assert ready[0]["hit"] is False, ready[0]
assert ready[1]["hit"] is True and ready[1]["compiles"] == 0, ready[1]
print(
    "fault-injection smoke: OK (one preempted + one resumed, run completed; "
    f"resume warm-started from the exec cache in {ready[1]['build_s']}s, 0 compiles)"
)
EOF
rm -rf "$FAULT_DIR"

echo "== serve-chaos smoke (poison request -> quarantine; hot reload from the saved checkpoint; health probe) =="
SERVE_DIR="$(mktemp -d)"
JAX_PLATFORMS=cpu python - "$SERVE_DIR" <<'EOF'
import glob
import os
import sys

import numpy as np

out = sys.argv[1]
# poison injection: the request admitted with sequence number 2 raises
# inside the forward; only ITS future may fail
os.environ["HYDRAGNN_INJECT_SERVE_RAISE"] = "2"

from hydragnn_tpu.api import prepare_loaders_and_config, run_training, serve_model
from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.flagship import flagship_config
from hydragnn_tpu.obs import FlightRecorder
from hydragnn_tpu.serve import RequestFailed, ServeConfig


def cfg():
    return flagship_config(hidden_dim=8, num_conv_layers=2, batch_size=5, num_epoch=1)


def data():
    return deterministic_graph_data(
        number_configurations=20,
        unit_cell_x_range=(2, 3),
        unit_cell_y_range=(2, 3),
        unit_cell_z_range=(2, 3),
        seed=0,
    )


run_training(cfg(), samples=data(), log_dir=out + "/logs/")
log_name = os.path.basename(os.path.dirname(glob.glob(out + "/logs/*/flight.jsonl")[0]))

flight = FlightRecorder(out + "/serve_flight.jsonl")
server = serve_model(
    cfg(),
    samples=data(),
    log_dir=out + "/logs/",
    serve_config=ServeConfig(max_batch=4, max_delay_ms=5.0),
    flight=flight,
)
_, _, test_loader, _ = prepare_loaders_and_config(cfg(), data())
# the tiny run's test split is small; cycle it so the poison request
# (admission seq 2) exists and is co-batched with innocents
test = (list(test_loader.all_samples) * 6)[:6]

futs = [server.submit(s) for s in test]
results, quarantined = {}, 0
for i, f in enumerate(futs):
    try:
        results[i] = f.result(timeout=120)
    except RequestFailed as exc:
        assert exc.seq == 2, exc
        quarantined += 1
assert quarantined == 1, f"expected exactly the poison request to fail, got {quarantined}"
assert len(results) == 5, "co-batched requests must survive the poison"

# hot reload from the freshly saved checkpoint (validating loader path);
# same weights -> answers must be bit-identical afterwards
os.environ.pop("HYDRAGNN_INJECT_SERVE_RAISE")
before = server.predict(test[0], timeout=120)
info = server.reload(log_name)
after = server.predict(test[0], timeout=120)
for k in before:
    np.testing.assert_allclose(after[k], before[k], rtol=0, atol=0)

health = server.health()
assert health["ready"] and health["live"], health
snap = server.metrics_snapshot()
assert snap["quarantined"] == 1 and snap["reloads"] == 1, snap
assert snap["compile_misses"] == 0, "chaos/reload recompiled on the serving path"
server.export_prometheus(out + "/serve.prom")
server.stop()
print(f"serve-chaos smoke: OK (quarantined=1, reload {info['swap_s']}s, answers identical)")
EOF
python tools/obs_report.py --validate "$SERVE_DIR/serve_flight.jsonl" | tee "$SERVE_DIR/validate.out"
if grep -q "WARNING" "$SERVE_DIR/validate.out"; then
    echo "FAIL: serve flight kinds not schema-known"; exit 1
fi
python tools/obs_report.py --faults "$SERVE_DIR/serve_flight.jsonl"
python tools/serve_probe.py --prom "$SERVE_DIR/serve.prom" --verbose
# lock-order witness smoke: serve the same checkpoint with the runtime
# witness ON (HYDRAGNN_LOCK_DEBUG=1) and a synthetic lock-order
# inversion injected between two real serve-path locks. The witness
# must convert the inversion into a `lock_order` flight event (thread
# stacks attached, record schema-valid) while the server answers
# normally and the health probe still exits 0 — an enabled witness is
# pure observability, never an availability risk.
JAX_PLATFORMS=cpu HYDRAGNN_LOCK_DEBUG=1 \
    HYDRAGNN_INJECT_LOCK_ORDER="batcher.MicroBatchQueue._cv,flight.FlightRecorder._lock" \
    python - "$SERVE_DIR" <<'EOF'
import sys

out = sys.argv[1]

from hydragnn_tpu.api import prepare_loaders_and_config, serve_model
from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.flagship import flagship_config
from hydragnn_tpu.obs import FlightRecorder
from hydragnn_tpu.obs.flight import read_flight_record, validate_flight_record
from hydragnn_tpu.serve import ServeConfig


def cfg():
    return flagship_config(hidden_dim=8, num_conv_layers=2, batch_size=5, num_epoch=1)


def data():
    return deterministic_graph_data(
        number_configurations=20,
        unit_cell_x_range=(2, 3),
        unit_cell_y_range=(2, 3),
        unit_cell_z_range=(2, 3),
        seed=0,
    )


flight = FlightRecorder(out + "/witness_flight.jsonl")
server = serve_model(
    cfg(),
    samples=data(),
    log_dir=out + "/logs/",  # the chaos smoke's checkpoint
    serve_config=ServeConfig(max_batch=4, max_delay_ms=5.0),
    flight=flight,
)
_, _, test_loader, _ = prepare_loaders_and_config(cfg(), data())
test = (list(test_loader.all_samples) * 4)[:4]
for s in test:
    server.predict(s, timeout=120)
health = server.health()
assert health["ready"] and health["live"], health
server.export_prometheus(out + "/witness.prom")
server.stop()

ev = read_flight_record(out + "/witness_flight.jsonl")
lock_events = [e for e in ev if e.get("kind") == "lock_order"]
assert len(lock_events) == 1, f"expected one injected lock_order event, got {lock_events}"
e = lock_events[0]
assert e["injected"] is True, e
assert set(e["locks"]) == {
    "batcher.MicroBatchQueue._cv",
    "flight.FlightRecorder._lock",
}, e["locks"]
assert e["stacks"], "lock_order event carried no thread stacks"
problems = validate_flight_record(ev)
assert not problems, problems
print(
    "lock-order witness smoke: OK (injected inversion -> one schema-valid "
    "lock_order event with thread stacks; server answered with the witness on)"
)
EOF
python tools/serve_probe.py --prom "$SERVE_DIR/witness.prom" --verbose
rm -rf "$SERVE_DIR"

echo "== fleet smoke (2-replica fleet from one checkpoint; replica-kill under traffic -> capacity restored warm; rolling reload bit-identical; merged flight validates) =="
FLEET_DIR="$(mktemp -d)"
JAX_PLATFORMS=cpu python - "$FLEET_DIR" <<'EOF'
import glob
import os
import sys
import threading

import numpy as np

out = sys.argv[1]

from hydragnn_tpu.api import prepare_loaders_and_config, run_training
from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.flagship import flagship_config
from hydragnn_tpu.fleet import ControllerConfig, Fleet, FleetController
from hydragnn_tpu.obs import FlightRecorder
from hydragnn_tpu.obs.flight import read_flight_record, validate_flight_record
from hydragnn_tpu.serve import ModelRegistry, Overloaded, ServeConfig, ServerClosed
from hydragnn_tpu.serve.server import RequestFailed


def cfg():
    return flagship_config(hidden_dim=8, num_conv_layers=2, batch_size=5, num_epoch=1)


def data():
    return deterministic_graph_data(
        number_configurations=20,
        unit_cell_x_range=(2, 3),
        unit_cell_y_range=(2, 3),
        unit_cell_z_range=(2, 3),
        seed=0,
    )


# ONE trained checkpoint feeds every replica in the fleet
run_training(cfg(), samples=data(), log_dir=out + "/logs/")
log_name = os.path.basename(os.path.dirname(glob.glob(out + "/logs/*/flight.jsonl")[0]))

train_loader, val_loader, test_loader, config = prepare_loaders_and_config(cfg(), data())
reference = (
    list(train_loader.all_samples)
    + list(val_loader.all_samples)
    + list(test_loader.all_samples)
)
served = ModelRegistry(out + "/logs/").load(
    log_name, config["NeuralNetwork"], example_graph=reference[0]
)

flight = FlightRecorder(out + "/fleet_flight.jsonl")
fleet = Fleet(exec_cache_dir=out + "/exec_cache", flight=flight)
reps = fleet.add_model(
    "flagship", served, reference,
    ServeConfig(max_batch=4, num_buckets=2, max_delay_ms=5.0), replicas=2,
)
# the second replica must warm-start ENTIRELY from the first's exec cache
snap = reps[1].server.metrics_snapshot()
assert snap["compile_warmup"] == 0, snap
assert snap["exec_cache_hits"] > 0, snap

# kill one replica while traffic flows through the router: the death
# retry absorbs in-flights — zero futures may fail untyped
test = (list(test_loader.all_samples) * 8)[:16]
victim = fleet.replicas()[0]
killer = threading.Timer(0.02, victim.kill)
killer.start()
futs = [fleet.submit(s) for s in test]
lost = 0
for f in futs:
    try:
        f.result(timeout=120)
    except (RequestFailed, Overloaded, ServerClosed):
        pass  # typed rejection is an answer; silence is the failure
    except BaseException:
        lost += 1
killer.join()
assert lost == 0, f"{lost} futures failed UNtyped after the replica kill"

# the controller reaps the dead replica and restores capacity; the
# replacement warm-starts from the shared cache with 0 compile misses
ctl = FleetController(
    fleet, registry=fleet.registry,
    config=ControllerConfig(min_replicas=1, max_replicas=3),
    flight=flight,
)
decisions = ctl.step()
assert [d["action"] for d in decisions] == ["replace"], decisions
assert fleet.replica_count() == 2 and not fleet.dead_replicas()
replacement = [r for r in fleet.replicas() if r.name not in {x.name for x in reps}]
assert len(replacement) == 1 and replacement[0].ready
assert replacement[0].server.metrics_snapshot()["compile_warmup"] == 0
for s in test[:4]:
    fleet.predict(s, timeout=120)
for r in fleet.replicas():
    m = r.server.metrics_snapshot()
    assert m["compile_misses"] == 0, (r.name, m)

# fleet-wide rolling reload from the SAME saved checkpoint: one replica
# at a time, and the answers must be bit-identical afterwards
before = fleet.predict(test[0], timeout=120)
outcomes = fleet.rolling_reload("flagship", log_name, log_dir=out + "/logs/")
assert len(outcomes) == 2 and all(o["ok"] for o in outcomes), outcomes
after = fleet.predict(test[0], timeout=120)
for k in before:
    np.testing.assert_allclose(after[k], before[k], rtol=0, atol=0)
health = fleet.health()
assert health["ready_count"] == 2 and health["live_count"] == 2, health

fleet.export_probes(out + "/probes")
fleet.stop()
flight.close()

# the MERGED flight (every replica's run_start, the scale decision, the
# reload outcomes) must be schema-valid as one timeline
ev = read_flight_record(out + "/fleet_flight.jsonl")
assert sum(1 for e in ev if e.get("kind") == "run_start") >= 3, "3 replica manifests"
scale = [e for e in ev if e.get("kind") == "fleet_scale"]
assert [e["action"] for e in scale] == ["replace"], scale
reloads = [e for e in ev if e.get("kind") == "fleet_reload"]
assert len(reloads) == 2 and all(e["ok"] for e in reloads), reloads
problems = validate_flight_record(ev)
assert not problems, problems
print(
    "fleet smoke: OK (replica-kill absorbed with 0 lost futures, replacement "
    "warm with 0 compile misses, rolling reload bit-identical, merged flight valid)"
)
EOF
python tools/obs_report.py --validate "$FLEET_DIR/fleet_flight.jsonl" | tee "$FLEET_DIR/validate.out"
if grep -q "WARNING" "$FLEET_DIR/validate.out"; then
    echo "FAIL: fleet flight kinds not schema-known"; exit 1
fi
python tools/serve_probe.py --fleet "$FLEET_DIR/probes" --verbose
rm -rf "$FLEET_DIR"

echo "== incident smoke (SLO triggers: clean control -> zero incidents; injected NaN train + wedged serve -> one validated bundle each) =="
INC_DIR="$(mktemp -d)"
# --- clean control: triggers armed + tracing on, nothing injected ->
#     ZERO incidents and sub-1% measured trigger/capture overhead; the
#     sampled step traces must land in the flight record and export as
#     Chrome/Perfetto JSON
JAX_PLATFORMS=cpu python - "$INC_DIR/clean" <<'EOF'
import glob
import json
import os
import sys

from hydragnn_tpu.api import run_training
from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.flagship import flagship_config
from hydragnn_tpu.obs import export_flight_chrome, read_flight_record

out = sys.argv[1]
cfg = flagship_config(hidden_dim=8, num_conv_layers=2, batch_size=5, num_epoch=2)
cfg["NeuralNetwork"]["Training"]["slo_triggers"] = True
cfg["NeuralNetwork"]["Training"]["scan_epoch"] = False  # the traced per-step path
samples = deterministic_graph_data(
    number_configurations=20,
    unit_cell_x_range=(2, 3),
    unit_cell_y_range=(2, 3),
    unit_cell_z_range=(2, 3),
    seed=0,
)
run_training(cfg, samples=samples, log_dir=out + "/logs/")
flight = glob.glob(out + "/logs/*/flight.jsonl")[0]
inc_root = os.path.join(os.path.dirname(flight), "incidents")
bundles = sorted(os.listdir(inc_root)) if os.path.isdir(inc_root) else []
assert bundles == [], f"clean control produced incidents: {bundles}"
ev = read_flight_record(flight)
trig = [e for e in ev if e.get("kind") == "run_end"][-1].get("triggers")
assert trig is not None and trig["fired"] == 0 and trig["incidents"] == [], trig
assert trig["overhead_frac"] < 0.01, f"trigger overhead over 1%: {trig}"
assert any(e.get("kind") == "trace_capture" for e in ev), "no sampled step traces"
export_flight_chrome(flight, out + "/trace.json")
with open(out + "/trace.json") as f:
    assert json.load(f)["traceEvents"], "empty chrome trace export"
print(
    "incident smoke (clean control): OK (0 incidents, "
    f"overhead_frac={trig['overhead_frac']})"
)
EOF
# --- injected NaN batch: the nonfinite sentry skips it and the
#     train_nonfinite_burst rule turns the skip counter's delta into
#     exactly ONE incident bundle, captured over the next epoch's steps
JAX_PLATFORMS=cpu HYDRAGNN_INJECT_NAN_STEP=2 HYDRAGNN_INCIDENT_PROFILE_STEPS=2 \
    python - "$INC_DIR/nan" <<'EOF'
import glob
import json
import os
import sys

from hydragnn_tpu.api import run_training
from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.flagship import flagship_config
from hydragnn_tpu.obs import read_flight_record
from hydragnn_tpu.obs.triggers import list_incidents, validate_incident_bundle

out = sys.argv[1]
cfg = flagship_config(hidden_dim=8, num_conv_layers=2, batch_size=5, num_epoch=2)
cfg["NeuralNetwork"]["Training"]["slo_triggers"] = True
samples = deterministic_graph_data(
    number_configurations=20,
    unit_cell_x_range=(2, 3),
    unit_cell_y_range=(2, 3),
    unit_cell_z_range=(2, 3),
    seed=0,
)
run_training(cfg, samples=samples, log_dir=out + "/logs/")
flight = glob.glob(out + "/logs/*/flight.jsonl")[0]
bundles = list_incidents(os.path.join(os.path.dirname(flight), "incidents"))
assert len(bundles) == 1, f"expected exactly one train incident, got {bundles}"
problems = validate_incident_bundle(bundles[0])
assert not problems, problems
with open(os.path.join(bundles[0], "incident_manifest.json")) as f:
    man = json.load(f)
assert man["rule"] == "train_nonfinite_burst", man
assert man["trigger"]["kind"] == "nonfinite_burst", man["trigger"]
assert man["profile"]["nonempty"], "train incident captured an empty profiler trace"
ev = read_flight_record(flight)
assert sum(1 for e in ev if e.get("kind") == "incident") == 1
trig = [e for e in ev if e.get("kind") == "run_end"][-1].get("triggers")
assert trig["incidents"] == ["train_nonfinite_burst"], trig
print(f"incident smoke (NaN train): OK (one bundle at {bundles[0]})")
EOF
# --- injected dispatch wedge: serve p99 blows through the SLO, the
#     serve_p99 rule opens ONE incident, post-wedge traffic drives the
#     bounded capture; request traces land in the serve flight record
JAX_PLATFORMS=cpu python - "$INC_DIR" "$INC_DIR/clean" <<'EOF'
import json
import os
import sys

out, ckpt = sys.argv[1], sys.argv[2]
# wedge: dispatch sleeps 1 s inside the forward for request seq 2
os.environ["HYDRAGNN_INJECT_SERVE_WEDGE"] = "2:1"
os.environ["HYDRAGNN_INCIDENT_PROFILE_STEPS"] = "2"

from hydragnn_tpu.api import prepare_loaders_and_config, serve_model
from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.flagship import flagship_config
from hydragnn_tpu.obs import FlightRecorder, read_flight_record
from hydragnn_tpu.obs.triggers import list_incidents, validate_incident_bundle
from hydragnn_tpu.serve import ServeConfig


def cfg():
    # num_epoch=2 matches the clean control's run name (the checkpoint dir)
    return flagship_config(hidden_dim=8, num_conv_layers=2, batch_size=5, num_epoch=2)


def data():
    return deterministic_graph_data(
        number_configurations=20,
        unit_cell_x_range=(2, 3),
        unit_cell_y_range=(2, 3),
        unit_cell_z_range=(2, 3),
        seed=0,
    )


flight = FlightRecorder(out + "/serve_flight.jsonl")
server = serve_model(
    cfg(),
    samples=data(),
    log_dir=ckpt + "/logs/",  # the clean control's checkpoint
    serve_config=ServeConfig(
        max_batch=4,
        max_delay_ms=5.0,
        slo_p99_ms=200.0,
        trigger_eval_every_s=0.05,
        incident_dir=out + "/serve_incidents",
    ),
    flight=flight,
)
_, _, test_loader, _ = prepare_loaders_and_config(cfg(), data())
test = (list(test_loader.all_samples) * 8)[:8]
for s in test:  # sequential: the wedged batch, then post-wedge traffic
    server.predict(s, timeout=120)
server.export_trace(out + "/serve_trace.json")
server.stop()
with open(out + "/serve_trace.json") as f:
    assert json.load(f)["traceEvents"], "serve trace export empty"
bundles = list_incidents(out + "/serve_incidents")
assert len(bundles) == 1, f"expected exactly one serve incident, got {bundles}"
problems = validate_incident_bundle(bundles[0])
assert not problems, problems
with open(os.path.join(bundles[0], "incident_manifest.json")) as f:
    man = json.load(f)
assert man["rule"] == "serve_p99" and man["trigger"]["kind"] == "latency_p99", man
assert man["profile"]["nonempty"], "serve incident captured an empty profiler trace"
ev = read_flight_record(out + "/serve_flight.jsonl")
assert sum(1 for e in ev if e.get("kind") == "incident") == 1
assert any(e.get("kind") == "trace_capture" for e in ev), "no request traces sampled"
print(f"incident smoke (serve wedge): OK (one bundle at {bundles[0]})")
EOF
# the bundles pass the lint artifact gate and the reporter renders them
python tools/graftlint.py --artifacts \
    "$INC_DIR"/nan/logs/*/incidents/*/incident_manifest.json \
    "$INC_DIR"/serve_incidents/*/incident_manifest.json
python tools/incident_report.py --validate \
    "$INC_DIR"/nan/logs/*/incidents "$INC_DIR/serve_incidents"
python tools/incident_report.py \
    "$INC_DIR"/nan/logs/*/incidents "$INC_DIR/serve_incidents" \
    | tee "$INC_DIR/report.out"
grep -q "== incident" "$INC_DIR/report.out" || {
    echo "FAIL: incident_report.py rendered nothing"; exit 1; }
# the incident appears in the fault timeline (and the record validates)
python tools/obs_report.py --faults "$(ls "$INC_DIR"/nan/logs/*/flight.jsonl)"
rm -rf "$INC_DIR"

echo "== podview smoke (simulated 2-host pod: per-host shards merge into one timeline with per-host Chrome tracks; injected straggler -> one step_skew bundle naming host 1) =="
POD_DIR="$(mktemp -d)"
cat > "$POD_DIR/host_run.py" <<'EOF'
"""One simulated host's tiny training run into a shared run dir. The
podview smoke runs this once per host — host 1 first, then host 0,
whose rank-0 SkewMonitor reads the completed peer shard; the
host_epoch summaries carry durations, so wall-clock overlap between
the simulated hosts is not required (docs/OBSERVABILITY.md "Pod
visibility")."""
import sys

from hydragnn_tpu.api import run_training
from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.flagship import flagship_config

out, triggers = sys.argv[1], sys.argv[2] == "1"
cfg = flagship_config(hidden_dim=8, num_conv_layers=2, batch_size=5, num_epoch=2)
cfg["NeuralNetwork"]["Training"]["slo_triggers"] = triggers
# per-step path: the straggler injection lives in StepSpans.step
cfg["NeuralNetwork"]["Training"]["scan_epoch"] = False
samples = deterministic_graph_data(
    number_configurations=20,
    unit_cell_x_range=(2, 3),
    unit_cell_y_range=(2, 3),
    unit_cell_z_range=(2, 3),
    seed=0,
)
run_training(cfg, samples=samples, log_dir=out + "/logs/")
EOF
# --- clean pass: the same tiny config once per simulated host into ONE
#     run dir; triggers stay off here (two sequential CPU runs carry
#     real compile-time noise — the straggler pass below proves the
#     trigger loop with an unambiguous signal)
JAX_PLATFORMS=cpu HYDRAGNN_PODVIEW_HOSTS=2 HYDRAGNN_PODVIEW_RUN_ID=podsmoke \
    HYDRAGNN_PODVIEW_HOST=1 PYTHONPATH="$PWD" python "$POD_DIR/host_run.py" "$POD_DIR/clean" 0
JAX_PLATFORMS=cpu HYDRAGNN_PODVIEW_HOSTS=2 HYDRAGNN_PODVIEW_RUN_ID=podsmoke \
    HYDRAGNN_PODVIEW_HOST=0 PYTHONPATH="$PWD" python "$POD_DIR/host_run.py" "$POD_DIR/clean" 0
JAX_PLATFORMS=cpu python - "$POD_DIR/clean" <<'EOF'
import glob
import os
import sys

from hydragnn_tpu.obs import (
    export_flight_chrome,
    flight_to_chrome,
    host_epoch_table,
    merge_host_flights,
    read_flight_record,
)

out = sys.argv[1]
flight = glob.glob(out + "/logs/*/flight.jsonl")[0]
run_dir = os.path.dirname(flight)
assert os.path.exists(os.path.join(run_dir, "flight.host1.jsonl")), \
    "host 1 wrote no shard"
merged = merge_host_flights(run_dir)
assert merged.hosts == [0, 1], merged.hosts
assert merged.problems == [], merged.problems
table = host_epoch_table(merged.events, run_id="podsmoke")
assert sorted(table) == [0, 1] and all(
    sorted(v) == [0, 1] for v in table.values()
), table
# rank 0's monitor saw the peer shard: skew verdicts in the record
assert any(e.get("kind") == "podview" for e in merged.events), \
    "no podview skew verdicts in the canonical shard"
# the plane's cost is stamped into run_end and <1% on the clean path
end = [e for e in read_flight_record(flight) if e.get("kind") == "run_end"][-1]
pv = end.get("podview")
assert pv and pv["enabled"] and pv["hosts"] == 2, pv
assert pv["overhead_frac"] < 0.01, f"podview overhead over 1%: {pv}"
# one Chrome track per host
chrome = flight_to_chrome(merged.events)["traceEvents"]
tids = {
    e["tid"] for e in chrome
    if e.get("ph") == "X" and str(e.get("name", "")).startswith("host")
}
assert tids == {0, 1}, tids
export_flight_chrome(run_dir, out + "/pod_trace.json")
print(
    "podview smoke (clean pod): OK (2 shards merged, "
    f"overhead_frac={pv['overhead_frac']})"
)
EOF
# the shard directory passes the reporter's validate gate (torn or
# missing hosts would be warnings, not failures), the --hosts view
# renders, and each shard passes the lint artifact gate
POD_RUN_DIR="$(dirname "$(ls "$POD_DIR"/clean/logs/*/flight.jsonl)")"
python tools/obs_report.py --validate "$POD_RUN_DIR"
python tools/obs_report.py --hosts "$POD_RUN_DIR" | tee "$POD_DIR/hosts.out"
grep -q "slowest" "$POD_DIR/hosts.out" || {
    echo "FAIL: obs_report --hosts rendered no per-host table"; exit 1; }
python tools/graftlint.py --artifacts \
    "$POD_RUN_DIR/flight.jsonl" "$POD_RUN_DIR/flight.host1.jsonl"
# --- straggler pass: host 1 sleeps 200 ms per step; host 0's monitor
#     must turn the cross-host skew into exactly ONE step_skew incident
#     whose podview_report.json names the injected host
JAX_PLATFORMS=cpu HYDRAGNN_PODVIEW_HOSTS=2 HYDRAGNN_PODVIEW_RUN_ID=podstrag \
    HYDRAGNN_PODVIEW_HOST=1 HYDRAGNN_INJECT_STRAGGLER=1:200 \
    PYTHONPATH="$PWD" python "$POD_DIR/host_run.py" "$POD_DIR/strag" 0
JAX_PLATFORMS=cpu HYDRAGNN_PODVIEW_HOSTS=2 HYDRAGNN_PODVIEW_RUN_ID=podstrag \
    HYDRAGNN_PODVIEW_HOST=0 HYDRAGNN_INCIDENT_PROFILE_STEPS=2 \
    PYTHONPATH="$PWD" python "$POD_DIR/host_run.py" "$POD_DIR/strag" 1
JAX_PLATFORMS=cpu python - "$POD_DIR/strag" <<'EOF'
import glob
import json
import os
import sys

from hydragnn_tpu.obs import validate_podview_report
from hydragnn_tpu.obs.triggers import list_incidents, validate_incident_bundle

out = sys.argv[1]
flight = glob.glob(out + "/logs/*/flight.jsonl")[0]
bundles = list_incidents(os.path.join(os.path.dirname(flight), "incidents"))
assert len(bundles) == 1, \
    f"expected exactly one step_skew incident, got {bundles}"
problems = validate_incident_bundle(bundles[0])
assert not problems, problems
with open(os.path.join(bundles[0], "incident_manifest.json")) as f:
    man = json.load(f)
assert man["rule"] == "podview_step_skew" and man["kind"] == "step_skew", man
assert man["trigger"]["detail"]["slowest_host"] == 1, man["trigger"]
with open(os.path.join(bundles[0], "podview_report.json")) as f:
    report = json.load(f)
assert validate_podview_report(report) == [], report
assert report["slowest_host"] == 1, report  # names the injected host
assert report["history"], "podview report carries no skew history"
# per-host evidence: the straggler's own shard tail rides in the bundle
assert os.path.exists(os.path.join(bundles[0], "flight_tail.host1.jsonl")), \
    "bundle missing the peer shard's tail"
print(
    "podview smoke (straggler): OK (one step_skew bundle naming host 1 "
    f"at {bundles[0]})"
)
EOF
# the new sidecar passes the lint artifact gate by name
python tools/graftlint.py --artifacts \
    "$POD_DIR"/strag/logs/*/incidents/*/podview_report.json
rm -rf "$POD_DIR"

echo "== pod-recovery smoke (concurrent 2-host pod under supervise.py --pod: SIGKILL host 1 mid-checkpoint -> host_lost restart from the last COMMIT, losses bit-match the uninterrupted reference; elastic leg re-shards 2->1) =="
PODREC_DIR="$(mktemp -d)"
cat > "$PODREC_DIR/child.py" <<'EOF'
"""One pod host's training run. tools/supervise.py --pod N launches N
of these CONCURRENTLY (HYDRAGNN_PODVIEW_HOST=k/_HOSTS=N per child);
run_guard maps TrainingPreempted/PodHostLost onto the supervisor's
exit-code contract (docs/RESILIENCE.md 'Pod recovery')."""
import sys

from hydragnn_tpu.resilience import run_guard
from hydragnn_tpu.api import run_training
from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.flagship import flagship_config

cfg = flagship_config(hidden_dim=8, num_conv_layers=2, batch_size=5, num_epoch=3)
cfg["NeuralNetwork"]["Training"]["checkpoint_every"] = 1
# Pin one dispatch mode for every run in this smoke: armed HYDRAGNN_INJECT_*
# vars force the per-step path (scan auto-eligibility), but the supervisor
# strips them for restarted attempts and the uninterrupted reference never
# has them — without the pin, the legs would compare scan-epoch losses
# against per-step losses and the bit-match below would be meaningless.
cfg["NeuralNetwork"]["Training"]["scan_epoch"] = False
samples = deterministic_graph_data(
    number_configurations=20,
    unit_cell_x_range=(2, 3),
    unit_cell_y_range=(2, 3),
    unit_cell_z_range=(2, 3),
    seed=0,
)
with run_guard():
    run_training(cfg, samples=samples, log_dir=sys.argv[1] + "/logs/")
EOF
cat > "$PODREC_DIR/check_leg.py" <<'EOF'
"""One recovery leg's evidence chain: supervisor flight (host_lost ->
prompt restart), host 0's merged training flight (preempted segment +
pod_resume lineage), the on-disk commit protocol, and the bit-match
against the uninterrupted reference."""
import glob
import os
import sys

from hydragnn_tpu.obs.flight import read_flight_record
from hydragnn_tpu.resilience.podckpt import latest_commit_info
from hydragnn_tpu.utils.checkpoint import load_train_meta

base, leg = sys.argv[1], sys.argv[2]
want_width, want_gen = int(sys.argv[3]), int(sys.argv[4])

# supervisor flight: exactly ONE host_lost (host 1, signal-dead) and
# one host_lost-class restart — prompt (no backoff) at the expected
# pod width (2 fixed, 1 elastic)
sup = read_flight_record(os.path.join(base, f"sup{leg}.jsonl"))
lost = [e for e in sup if e.get("kind") == "host_lost"]
assert len(lost) == 1 and lost[0]["host"] == 1, lost
assert int(lost[0]["exit_code"]) < 0, lost[0]
restarts = [e for e in sup if e.get("kind") == "restart"]
assert len(restarts) == 1 and restarts[0]["cause"] == "host_lost", restarts
assert restarts[0]["delay_s"] == 0, restarts[0]
assert int(restarts[0]["hosts"]) == want_width, restarts[0]
assert [e["status"] for e in sup if e.get("kind") == "run_end"] == ["completed"]

# host 0's merged training flight: the survivor cut its boundary and
# exited preempted inside the grace window; the restarted segment rose
# from committed gen 1 (gen 2's manifest never landed) and completed
flight_path = glob.glob(
    os.path.join(base, f"pod{leg}", "logs", "*", "flight.jsonl")
)[0]
run_dir = os.path.dirname(flight_path)
ev = read_flight_record(flight_path)
ends = [e["status"] for e in ev if e.get("kind") == "run_end"]
assert ends == ["preempted", "completed"], ends
assert sum(1 for e in ev if e.get("kind") == "resumed") == 1
pre = [e for e in ev if e.get("kind") == "preempt"]
assert pre and pre[0]["signal"] == 15, pre
fails = [
    e
    for e in ev
    if e.get("kind") == "error" and e.get("error_type") == "PodCommitFailed"
]
assert fails, "the torn generation left no PodCommitFailed evidence"
resumes = [e for e in ev if e.get("kind") == "pod_resume"]
assert len(resumes) == 1, resumes
assert resumes[0]["gen"] == 1 and resumes[0]["prior_hosts"] == 2, resumes[0]
assert not resumes[0].get("fallbacks"), resumes[0]
starts = [e for e in ev if e.get("kind") == "run_start"]
lineage = (starts[-1].get("manifest") or {}).get("pod_resume")
assert lineage and lineage["resumed_from_gen"] == 1, lineage
assert lineage["prior_hosts"] == 2, lineage

# on-disk protocol ground truth: the newest COMMIT marker names the
# expected generation (3 after a full-width recovery; still 1 after
# the elastic leg, whose single-host continuation leaves pod cutting
# off) and the meta sidecar describes the completed run
commit = latest_commit_info(run_dir)
assert commit is not None and int(commit["gen"]) == want_gen, commit
assert int(commit["hosts"]) == 2, commit
meta = load_train_meta(os.path.basename(run_dir), os.path.dirname(run_dir))
assert meta is not None and int(meta["epoch"]) == 3, meta
assert int(meta.get("format_version", 1)) == 2, meta

# recovery correctness: every epoch's final losses equal the
# uninterrupted single-process reference's EXACTLY (the restored
# generation is byte-identical state, the replayed epochs deterministic)
ref_flight = glob.glob(os.path.join(base, "ref", "logs", "*", "flight.jsonl"))[0]
ref = {
    e["epoch"]: e
    for e in read_flight_record(ref_flight)
    if e.get("kind") == "epoch"
}
got = {e["epoch"]: e for e in ev if e.get("kind") == "epoch"}
assert sorted(got) == sorted(ref) == [0, 1, 2], (sorted(got), sorted(ref))
for ep in sorted(ref):
    for k in ("train_loss", "val_loss", "test_loss"):
        assert got[ep][k] == ref[ep][k], (ep, k, got[ep][k], ref[ep][k])
print(
    f"pod-recovery leg {leg}: OK (host_lost -> prompt restart at width "
    f"{want_width}, resumed from committed gen 1, last commit gen "
    f"{want_gen}, losses bit-match the reference)"
)
EOF
# the uninterrupted reference: same config, single process, no pod.
# Also warms the shared exec cache so every pod host below starts
# compile-free — the bounded commit waits then measure the protocol,
# not cross-host compile skew.
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
    HYDRAGNN_EXEC_CACHE="$PODREC_DIR/exec_cache" \
    python "$PODREC_DIR/child.py" "$PODREC_DIR/ref"
# --- fixed-width leg: host 1 is SIGKILLed inside its gen-2 shard write
#     (shard bytes land, the manifest never does -> gen 2 can never
#     commit). The supervisor classifies the signal death host_lost,
#     SIGTERMs the survivor (it cuts its boundary and exits 75 inside
#     the grace window), and restarts the full pod promptly with the
#     injection stripped; both hosts resume from committed gen 1.
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
    HYDRAGNN_EXEC_CACHE="$PODREC_DIR/exec_cache" \
    HYDRAGNN_INJECT_POD_KILL_HOST=1:2 \
    HYDRAGNN_POD_COMMIT_TIMEOUT_S=10 \
    python tools/supervise.py --pod 2 --pod-grace 90 --run-id podrecA \
    --flight "$PODREC_DIR/supA.jsonl" -- \
    python "$PODREC_DIR/child.py" "$PODREC_DIR/podA"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
    python "$PODREC_DIR/check_leg.py" "$PODREC_DIR" A 2 3
PODREC_RUN_A="$(dirname "$(ls "$PODREC_DIR"/podA/logs/*/flight.jsonl)")"
# the reporter surfaces the protocol state and the resume lineage, the
# fault timeline narrates the loss and the rise, and every flight
# artifact (host shards + the supervisor's) passes the lint gate
python tools/obs_report.py --validate "$PODREC_RUN_A" \
    | tee "$PODREC_DIR/validateA.out"
grep -q "podckpt: last committed gen 3" "$PODREC_DIR/validateA.out" || {
    echo "FAIL: --validate did not surface the committed generation"; exit 1; }
grep -q "pod_resume: from gen 1 (prior_hosts=2" "$PODREC_DIR/validateA.out" || {
    echo "FAIL: --validate did not surface the pod resume lineage"; exit 1; }
python tools/obs_report.py --faults "$PODREC_DIR/supA.jsonl" \
    | tee "$PODREC_DIR/faultsA.out"
grep -q "host 1 declared lost" "$PODREC_DIR/faultsA.out" || {
    echo "FAIL: --faults did not narrate the lost host"; exit 1; }
python tools/obs_report.py --faults "$PODREC_RUN_A/flight.jsonl" \
    | tee "$PODREC_DIR/faultsA_train.out"
grep -q "resumed from committed gen 1" "$PODREC_DIR/faultsA_train.out" || {
    echo "FAIL: --faults did not narrate the pod resume"; exit 1; }
python tools/graftlint.py --artifacts \
    "$PODREC_RUN_A/flight.jsonl" "$PODREC_RUN_A/flight.host1.jsonl" \
    "$PODREC_DIR/supA.jsonl"
# --- elastic leg: same loss, --pod-elastic restarts the pod at width 1;
#     the single-host continuation restores the 2-host generation
#     re-sharded onto itself and completes with the same losses
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
    HYDRAGNN_EXEC_CACHE="$PODREC_DIR/exec_cache" \
    HYDRAGNN_INJECT_POD_KILL_HOST=1:2 \
    HYDRAGNN_POD_COMMIT_TIMEOUT_S=10 \
    python tools/supervise.py --pod 2 --pod-elastic --pod-grace 90 \
    --run-id podrecB --flight "$PODREC_DIR/supB.jsonl" -- \
    python "$PODREC_DIR/child.py" "$PODREC_DIR/podB"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
    python "$PODREC_DIR/check_leg.py" "$PODREC_DIR" B 1 1
PODREC_RUN_B="$(dirname "$(ls "$PODREC_DIR"/podB/logs/*/flight.jsonl)")"
python tools/obs_report.py --validate "$PODREC_RUN_B" \
    | tee "$PODREC_DIR/validateB.out"
grep -q "podckpt: last committed gen 1" "$PODREC_DIR/validateB.out" || {
    echo "FAIL: --validate did not surface the elastic leg's commit"; exit 1; }
python tools/graftlint.py --artifacts \
    "$PODREC_RUN_B/flight.jsonl" "$PODREC_DIR/supB.jsonl"
rm -rf "$PODREC_DIR"

echo "== exec-cache smoke (train once; two server starts vs one cache dir; corrupt entry -> loud eviction) =="
EXEC_DIR="$(mktemp -d)"
cat > "$EXEC_DIR/serve_once.py" <<'EOF'
import sys

out = sys.argv[1]
expect = sys.argv[2]  # cold | warm | corrupt

from hydragnn_tpu.api import run_training, serve_model
from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.flagship import flagship_config
from hydragnn_tpu.serve import ServeConfig


def cfg():
    return flagship_config(hidden_dim=8, num_conv_layers=2, batch_size=5, num_epoch=1)


def data():
    return deterministic_graph_data(
        number_configurations=20,
        unit_cell_x_range=(2, 3),
        unit_cell_y_range=(2, 3),
        unit_cell_z_range=(2, 3),
        seed=0,
    )


if expect == "cold":
    run_training(cfg(), samples=data(), log_dir=out + "/logs/")

server = serve_model(
    cfg(),
    samples=data(),
    log_dir=out + "/logs/",
    serve_config=ServeConfig(
        max_batch=4, max_delay_ms=5.0, exec_cache_dir=out + "/exec_cache"
    ),
)
snap = server.metrics_snapshot()
n = len(server.buckets)
server.stop()
print(
    f"{expect} start: buckets={n} warmup_compiles={snap['compile_warmup']} "
    f"cache_hits={snap['exec_cache_hits']} "
    f"miss_reasons={snap['exec_cache_miss_reasons']}"
)
if expect == "cold":
    assert snap["compile_warmup"] == n and snap["exec_cache_misses"] == n, snap
elif expect == "warm":
    # the second-replica criterion: 0 AOT compiles, every bucket from disk
    assert snap["compile_warmup"] == 0, f"warm start recompiled: {snap}"
    assert snap["exec_cache_hits"] == n, snap
else:  # corrupt: ONE loud eviction + recompile of that bucket, rest hit
    assert snap["exec_cache_miss_reasons"] == {"corrupt": 1}, snap
    assert snap["compile_warmup"] == 1 and snap["exec_cache_hits"] == n - 1, snap
EOF
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" python "$EXEC_DIR/serve_once.py" "$EXEC_DIR" cold
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" python "$EXEC_DIR/serve_once.py" "$EXEC_DIR" warm
# flip bytes inside one entry: the next start must evict LOUDLY (stderr
# names the entry), recompile just that bucket, and serve normally
python - "$EXEC_DIR/exec_cache" <<'EOF'
import glob
import sys

path = sorted(glob.glob(sys.argv[1] + "/*.bin"))[0]
with open(path, "r+b") as f:
    f.seek(30)
    f.write(b"\xde\xad\xbe\xef")
EOF
if ! JAX_PLATFORMS=cpu PYTHONPATH="$PWD" python "$EXEC_DIR/serve_once.py" "$EXEC_DIR" corrupt \
        2>"$EXEC_DIR/corrupt.err"; then
    echo "FAIL: server start over a corrupt cache entry crashed"
    cat "$EXEC_DIR/corrupt.err"
    exit 1
fi
grep -q "exec_cache: evicted entry" "$EXEC_DIR/corrupt.err" || {
    echo "FAIL: corruption eviction was not loud on stderr"
    cat "$EXEC_DIR/corrupt.err"
    exit 1
}
rm -rf "$EXEC_DIR"

echo "== drift smoke (request spool + drift plane: clean traffic -> zero incidents + bounded spool overhead; injected covariate shift -> one validated feature_drift bundle) =="
DRIFT_DIR="$(mktemp -d)"
# --- train the reference: run_training stamps the per-channel stats
#     block (moments, quantiles, histogram fractions) into its flight
#     manifest — that flight IS the drift_ref a server arms against
JAX_PLATFORMS=cpu python - "$DRIFT_DIR/train" <<'EOF'
import glob
import sys

from hydragnn_tpu.api import run_training
from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.flagship import flagship_config
from hydragnn_tpu.obs.drift import load_reference

out = sys.argv[1]
cfg = flagship_config(hidden_dim=8, num_conv_layers=2, batch_size=5, num_epoch=2)
samples = deterministic_graph_data(
    number_configurations=24,
    unit_cell_x_range=(2, 3),
    unit_cell_y_range=(2, 3),
    unit_cell_z_range=(2, 3),
    seed=0,
)
run_training(cfg, samples=samples, log_dir=out + "/logs/")
flight = glob.glob(out + "/logs/*/flight.jsonl")[0]
ref = load_reference(flight)  # raises if the stats block is absent/invalid
assert ref["num_rows"] > 0 and ref["feature"]["channels"], ref.keys()
print(f"drift smoke (train ref): OK ({ref['num_rows']} reference rows)")
EOF
DRIFT_REF="$(ls "$DRIFT_DIR"/train/logs/*/flight.jsonl)"
# --- clean serve: spool + drift armed against the training reference.
#     In-distribution traffic must produce ZERO incidents, a run_end
#     spool block with its measured overhead fraction, and shards that
#     reload bit-compatibly through the training batcher (the retrain
#     contract). The smoke's wall time is ~1 s, so the overhead gate is
#     a sanity bound, not a production SLO.
JAX_PLATFORMS=cpu python - "$DRIFT_DIR" "$DRIFT_DIR/train" "$DRIFT_REF" <<'EOF'
import os
import sys

import numpy as np

out, ckpt, ref_path = sys.argv[1], sys.argv[2], sys.argv[3]

from hydragnn_tpu.api import prepare_loaders_and_config, serve_model
from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.flagship import flagship_config
from hydragnn_tpu.graph.batch import batch_graphs
from hydragnn_tpu.obs import FlightRecorder, read_flight_record
from hydragnn_tpu.obs.spool import list_shards, read_shard_manifest, read_spool
from hydragnn_tpu.obs.triggers import list_incidents
from hydragnn_tpu.serve import ServeConfig
from hydragnn_tpu.serve.server import request_to_dict


def cfg():
    return flagship_config(hidden_dim=8, num_conv_layers=2, batch_size=5, num_epoch=2)


def data():
    return deterministic_graph_data(
        number_configurations=24,
        unit_cell_x_range=(2, 3),
        unit_cell_y_range=(2, 3),
        unit_cell_z_range=(2, 3),
        seed=0,
    )


flight = FlightRecorder(out + "/clean_flight.jsonl")
server = serve_model(
    cfg(),
    samples=data(),
    log_dir=ckpt + "/logs/",
    serve_config=ServeConfig(
        max_batch=4,
        max_delay_ms=5.0,
        incident_dir=out + "/clean_incidents",
        spool=True,
        spool_sample=2,
        spool_shard_mb=0.05,
        spool_dir=out + "/spool",
        drift_ref=ref_path,
        drift_min_count=16,
    ),
    flight=flight,
)
train_loader, _, _, _ = prepare_loaders_and_config(cfg(), data())
reqs = list(train_loader.all_samples) * 2  # in-distribution traffic
for s in reqs:
    server.predict(s, timeout=120)
server.stop()
assert list_incidents(out + "/clean_incidents") == [], "clean traffic drifted?"
ev = read_flight_record(out + "/clean_flight.jsonl")
start = next(e for e in ev if e.get("kind") == "run_start")
man = start["manifest"]
assert man["spool"]["enabled"] and man["drift"]["armed"], man
end = [e for e in ev if e.get("kind") == "run_end"][-1]
sp, dr = end["spool"], end["drift"]
assert sp["spooled"] >= len(reqs) // 2, sp
assert 0.0 <= sp["overhead_frac"] < 0.05, f"spool overhead over 5%: {sp}"
assert dr["feature_rows"] > 0 and dr["feature_psi_max"] < 0.25, dr
# the spooled window reloads through the training batcher: same node
# payload (f32) and identical edge_occupancy as the original requests
shards = list_shards(out + "/spool")
assert shards, "clean serve spooled nothing"
mans = [read_shard_manifest(s) for s in shards]
assert sum(m["num_samples"] for m in mans) == sp["spooled"], (mans, sp)
back = sorted(read_spool(out + "/spool"), key=lambda s: s.meta["spool"]["seq"])
seqs = [s.meta["spool"]["seq"] for s in back]
orig = [reqs[i] for i in seqs]
want = batch_graphs([request_to_dict(s) for s in orig])
got = batch_graphs([request_to_dict(s) for s in back])
assert int(want.edge_occupancy) == int(got.edge_occupancy)
np.testing.assert_array_equal(
    np.asarray(want.nodes), np.asarray(got.nodes)
)
print(
    f"drift smoke (clean serve): OK (0 incidents, {sp['spooled']} spooled, "
    f"overhead_frac={sp['overhead_frac']}, feature_psi_max={dr['feature_psi_max']})"
)
EOF
# --- injected covariate shift: every admitted request's node features
#     move by +5.0; the feature_drift rule must open exactly ONE
#     incident whose bundle carries a schema-valid drift_report.json
#     and the spool window holding the offending traffic
JAX_PLATFORMS=cpu HYDRAGNN_INJECT_DRIFT=5.0 \
    python - "$DRIFT_DIR" "$DRIFT_DIR/train" "$DRIFT_REF" <<'EOF'
import json
import os
import sys

out, ckpt, ref_path = sys.argv[1], sys.argv[2], sys.argv[3]

from hydragnn_tpu.api import prepare_loaders_and_config, serve_model
from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.flagship import flagship_config
from hydragnn_tpu.obs import FlightRecorder, read_flight_record
from hydragnn_tpu.obs.drift import validate_drift_report
from hydragnn_tpu.obs.triggers import list_incidents, validate_incident_bundle
from hydragnn_tpu.serve import ServeConfig


def cfg():
    return flagship_config(hidden_dim=8, num_conv_layers=2, batch_size=5, num_epoch=2)


def data():
    return deterministic_graph_data(
        number_configurations=24,
        unit_cell_x_range=(2, 3),
        unit_cell_y_range=(2, 3),
        unit_cell_z_range=(2, 3),
        seed=0,
    )


flight = FlightRecorder(out + "/shift_flight.jsonl")
server = serve_model(
    cfg(),
    samples=data(),
    log_dir=ckpt + "/logs/",
    serve_config=ServeConfig(
        max_batch=4,
        max_delay_ms=5.0,
        incident_dir=out + "/shift_incidents",
        spool=True,
        spool_sample=2,
        spool_shard_mb=0.05,
        spool_dir=out + "/shift_spool",
        drift_ref=ref_path,
        drift_min_count=16,
        trigger_eval_every_s=0.05,
    ),
    flight=flight,
)
train_loader, _, _, _ = prepare_loaders_and_config(cfg(), data())
for s in list(train_loader.all_samples) * 2:
    server.predict(s, timeout=120)
server.stop()
bundles = list_incidents(out + "/shift_incidents")
assert len(bundles) == 1, f"expected exactly one drift incident, got {bundles}"
problems = validate_incident_bundle(bundles[0])
assert not problems, problems
with open(os.path.join(bundles[0], "incident_manifest.json")) as f:
    man = json.load(f)
assert man["rule"] == "serve_feature_drift", man
assert man["trigger"]["kind"] == "feature_drift", man["trigger"]
report_path = os.path.join(bundles[0], "drift_report.json")
with open(report_path) as f:
    report = json.load(f)
assert validate_drift_report(report) == [], validate_drift_report(report)
assert report["feature"]["psi_max"] > 0.25, report["feature"]
assert (report.get("spool_window") or {}).get("dir"), report.get("spool_window")
ev = read_flight_record(out + "/shift_flight.jsonl")
drift_ev = [e for e in ev if e.get("kind") == "drift"]
assert len(drift_ev) == 1 and drift_ev[0]["rule_kind"] == "feature_drift", drift_ev
print(
    "drift smoke (injected shift): OK (one bundle, "
    f"observed psi={drift_ev[0]['observed']:.3f} > {drift_ev[0]['threshold']})"
)
EOF
# the artifacts pass the lint gate and every reader renders/validates them
python tools/graftlint.py --artifacts \
    "$DRIFT_DIR"/shift_incidents/*/incident_manifest.json \
    "$DRIFT_DIR"/shift_incidents/*/drift_report.json \
    "$DRIFT_DIR"/spool/*/spool_manifest.json
python tools/incident_report.py --validate "$DRIFT_DIR/shift_incidents"
python tools/drift_report.py --validate \
    "$DRIFT_REF" "$DRIFT_DIR/clean_flight.jsonl" "$DRIFT_DIR/spool" \
    "$DRIFT_DIR"/shift_incidents/*/drift_report.json
python tools/drift_report.py --no-trend \
    "$DRIFT_DIR/shift_flight.jsonl" "$DRIFT_DIR/spool" \
    "$DRIFT_DIR"/shift_incidents/*/drift_report.json \
    | tee "$DRIFT_DIR/report.out"
grep -q "breaches: 1" "$DRIFT_DIR/report.out" || {
    echo "FAIL: drift_report.py did not render the breach"; exit 1; }
# the breach appears in the fault timeline (and the record validates)
python tools/obs_report.py --faults "$DRIFT_DIR/shift_flight.jsonl"
rm -rf "$DRIFT_DIR"

echo "== closed-loop smoke (retrain pilot: drift incident -> fine-tune from pinned spool -> two-slice canary -> hot reload; injected train crash absorbed, injected regression rejected, torn candidate rolled back) =="
PILOT_DIR="$(mktemp -d)"
# --- train once (the same tiny flagship the drift smoke uses); each
#     scenario then gets its own COPY of the checkpoint tree — the
#     pilot journal and the candidate run live NEXT TO the serving run,
#     so sharing one tree would leak pilot state (and candidates) from
#     one scenario into the next
JAX_PLATFORMS=cpu python - "$PILOT_DIR/train" <<'EOF'
import glob
import sys

from hydragnn_tpu.api import run_training
from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.flagship import flagship_config
from hydragnn_tpu.obs.drift import load_reference

out = sys.argv[1]
cfg = flagship_config(hidden_dim=8, num_conv_layers=2, batch_size=5, num_epoch=2)
samples = deterministic_graph_data(
    number_configurations=24,
    unit_cell_x_range=(2, 3),
    unit_cell_y_range=(2, 3),
    unit_cell_z_range=(2, 3),
    seed=0,
)
run_training(cfg, samples=samples, log_dir=out + "/logs/")
flight = glob.glob(out + "/logs/*/flight.jsonl")[0]
ref = load_reference(flight)
assert ref["num_rows"] > 0, ref.keys()
print(f"closed-loop smoke (train ref): OK ({ref['num_rows']} reference rows)")
EOF
# one driver, three scenarios: serve with HYDRAGNN_INJECT_DRIFT shifted
# traffic and a REAL attached RetrainPilot (real supervised child
# fine-tune, real canary, real hot reload), then assert the journal,
# the flight narration, and the serving weights per scenario.
# CANARY_TOL=10.0 keeps CI deterministic: the smoke proves the LOOP's
# mechanics (a 1-epoch fine-tune on 1x-CPU pseudo-label data is not a
# model-quality statement); the regression scenario still rejects
# because its injected inflation dwarfs any tolerance.
cat > "$PILOT_DIR/driver.py" <<'EOF'
"""Closed-loop smoke driver: serve a drifting model with a retrain
pilot attached and assert one full cycle per scenario (ok / canary /
torn)."""

import glob
import json
import os
import sys
import time

import numpy as np

out, ckpt, ref_path, scenario = sys.argv[1:5]

from hydragnn_tpu.api import prepare_loaders_and_config, serve_model
from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.flagship import flagship_config
from hydragnn_tpu.obs import FlightRecorder, read_flight_record
from hydragnn_tpu.obs.triggers import list_incidents
from hydragnn_tpu.pilot import RetrainPilot
from hydragnn_tpu.serve import ServeConfig


def cfg():
    return flagship_config(
        hidden_dim=8, num_conv_layers=2, batch_size=5, num_epoch=2
    )


def data():
    return deterministic_graph_data(
        number_configurations=24,
        unit_cell_x_range=(2, 3),
        unit_cell_y_range=(2, 3),
        unit_cell_z_range=(2, 3),
        seed=0,
    )


flight_path = f"{out}/{scenario}_flight.jsonl"
flight = FlightRecorder(flight_path)
server = serve_model(
    cfg(),
    samples=data(),
    log_dir=ckpt + "/logs/",
    serve_config=ServeConfig(
        max_batch=4,
        max_delay_ms=5.0,
        incident_dir=f"{out}/{scenario}_incidents",
        spool=True,
        spool_sample=2,
        spool_shard_mb=0.05,
        spool_dir=f"{out}/{scenario}_spool",
        drift_ref=ref_path,
        # node rows, not requests: fire the rule mid-traffic, once the
        # spool holds a trainable window (~24 requests in)
        drift_min_count=400,
        trigger_eval_every_s=0.05,
    ),
    flight=flight,
)
run_name = os.path.basename(
    os.path.dirname(glob.glob(ckpt + "/logs/*/flight.jsonl")[0])
)
train_loader, _, _, _ = prepare_loaders_and_config(cfg(), data())
refs = list(train_loader.all_samples)
pilot = RetrainPilot(server, run_name, reference_samples=refs, flight=flight)
server.attach_pilot(pilot)

baseline = server.predict(refs[0], timeout=120)
for s in refs * 2:
    server.predict(s, timeout=120)
# the drift verdict fires on the trigger thread; wait for the cycle
deadline = time.time() + 600
while time.time() < deadline and pilot.status()["cycle"] == 0:
    time.sleep(0.2)
assert pilot.status()["cycle"] == 1, f"no retrain cycle flew: {pilot.status()}"
pilot.join(timeout=600)
st = pilot.status()
assert st["state"] == "cooldown", st
assert st["pinned_shards"] == [], st  # the cycle released its pins
after = server.predict(refs[0], timeout=120)  # serving path alive post-cycle
server.export_prometheus(f"{out}/{scenario}.prom")
server.stop()

candidate = f"{run_name}-pilot-c1"
cand_ckpt = os.path.join(ckpt, "logs", candidate, f"{candidate}.mp")
states = [e["state"] for e in pilot.journal.entries()]
tail = pilot.journal.last()["detail"]
ev = read_flight_record(flight_path)
reloads = [e for e in ev if e.get("kind") == "reload"]
reload_fails = [e for e in ev if e.get("kind") == "reload_failed"]
pilot_ev = [e for e in ev if e.get("kind") == "pilot"]
assert pilot_ev, "pilot cycle left no flight narration"

if scenario == "ok":
    # full success: the injected train crash was absorbed by the
    # supervisor's restart (stripped injection), the candidate passed
    # both canary slices, and the reload swapped weights
    assert st["last_cycle_ok"] is True and st["failed_cycles"] == 0, st
    assert states == [
        "idle", "drift_confirmed", "fine_tuning", "canary",
        "reloading", "cooldown",
    ], states
    assert tail["reason"] == "reloaded", tail
    assert tail["reference"]["passed"] and tail["window"]["passed"], tail
    assert os.path.exists(cand_ckpt), cand_ckpt
    assert os.path.exists(
        os.path.join(ckpt, "logs", candidate, "config.json")
    ), "candidate config missing"
    assert len(reloads) == 1 and not reload_fails, (reloads, reload_fails)
    # the fine-tune manifest names its lineage (spool window + parent)
    cand_flight = glob.glob(
        os.path.join(ckpt, "logs", candidate, "flight.jsonl")
    )
    if cand_flight:
        cev = read_flight_record(cand_flight[0])
        man = next(e for e in cev if e.get("kind") == "run_start")["manifest"]
        assert man["fine_tune"]["from_run"] == run_name, man["fine_tune"]
        assert man["fine_tune"]["shards"], man["fine_tune"]
    # the drift incident bundle pinned its evidence: per-shard spool
    # manifests copied INTO the bundle
    (bundle,) = list_incidents(f"{out}/{scenario}_incidents")
    copies = glob.glob(os.path.join(bundle, "spool_manifests", "*.json"))
    assert copies, f"no spool manifest copies in {bundle}"
    with open(os.path.join(bundle, "drift_report.json")) as f:
        report = json.load(f)
    assert report["pinned_shards"], report.get("pinned_shards")
    print(
        f"closed-loop smoke (ok): OK (cycle 1 reloaded the candidate "
        f"despite an injected train crash; canary ref_mae="
        f"{tail['reference']['candidate_mae']}, "
        f"{len(copies)} pinned manifests in bundle)"
    )
elif scenario == "canary":
    # the candidate trained fine but the injected regression must be
    # rejected at the canary gate: no reload, old weights serve on
    # (the hung-tune wall-clock kill path is unit-tested in
    # tests/test_pilot.py — a real fine-tune here would need a wall
    # clock too generous to also prove the kill cheaply)
    assert st["last_cycle_ok"] is False and st["failed_cycles"] == 1, st
    assert states[-1] == "cooldown" and "reloading" not in states, states
    assert tail["reason"] == "canary_regression", tail
    assert not reloads and not reload_fails, (reloads, reload_fails)
    for k in baseline:
        np.testing.assert_array_equal(
            np.asarray(baseline[k]), np.asarray(after[k])
        )
    print(
        "closed-loop smoke (canary): OK (regressed candidate rejected "
        "at the canary gate, old weights bit-identical)"
    )
elif scenario == "torn":
    # the pilot canary passed but the checkpoint was torn before the
    # swap: the RELOAD path's validating loader must reject it and the
    # old weights keep serving
    assert st["last_cycle_ok"] is False and st["failed_cycles"] == 1, st
    assert states[-2:] == ["reloading", "cooldown"], states
    assert tail["reason"] == "reload_failed", tail
    assert reload_fails and not reloads, (reloads, reload_fails)
    for k in baseline:
        np.testing.assert_array_equal(
            np.asarray(baseline[k]), np.asarray(after[k])
        )
    print(
        "closed-loop smoke (torn): OK (torn candidate rejected by the "
        "reload canary, old weights bit-identical)"
    )
else:
    raise SystemExit(f"unknown scenario {scenario!r}")
EOF
for SCEN in ok canary torn; do
    cp -r "$PILOT_DIR/train" "$PILOT_DIR/train_$SCEN"
done
PILOT_ENV=(env PYTHONPATH="$PWD" JAX_PLATFORMS=cpu HYDRAGNN_INJECT_DRIFT=5.0
    HYDRAGNN_PILOT_CANARY_TOL=10.0 HYDRAGNN_PILOT_COOLDOWN_S=120
    HYDRAGNN_PILOT_TUNE_EPOCHS=1 HYDRAGNN_PILOT_TUNE_BACKOFF_S=0.1)
"${PILOT_ENV[@]}" HYDRAGNN_INJECT_PILOT_TRAIN_CRASH=1 \
    python "$PILOT_DIR/driver.py" "$PILOT_DIR" "$PILOT_DIR/train_ok" \
    "$(ls "$PILOT_DIR"/train_ok/logs/*/flight.jsonl)" ok
"${PILOT_ENV[@]}" HYDRAGNN_INJECT_PILOT_CANARY_REGRESS=1 \
    python "$PILOT_DIR/driver.py" "$PILOT_DIR" "$PILOT_DIR/train_canary" \
    "$(ls "$PILOT_DIR"/train_canary/logs/*/flight.jsonl)" canary
"${PILOT_ENV[@]}" HYDRAGNN_INJECT_PILOT_TORN_RELOAD=1 \
    python "$PILOT_DIR/driver.py" "$PILOT_DIR" "$PILOT_DIR/train_torn" \
    "$(ls "$PILOT_DIR"/train_torn/logs/*/flight.jsonl)" torn
# the pilot gauges round-trip through the prom textfile to the probe:
# healthy after the reloaded cycle, degraded (rc 1) after a failed one
for SCEN in ok canary torn; do
    rc=0
    python tools/serve_probe.py --prom "$PILOT_DIR/$SCEN.prom" \
        --pilot --max-age 3600 --verbose || rc=$?
    case "$SCEN" in ok) want=0 ;; *) want=1 ;; esac
    if [ "$rc" -ne "$want" ]; then
        echo "FAIL: serve_probe --pilot rc=$rc want=$want ($SCEN)"; exit 1
    fi
done
# the fault timeline narrates the cycle (pilot events + the reload)
python tools/obs_report.py --faults "$PILOT_DIR/ok_flight.jsonl" \
    | tee "$PILOT_DIR/report.out"
grep -q "pilot_cycles=1" "$PILOT_DIR/report.out" || {
    echo "FAIL: obs_report.py did not count the pilot cycle"; exit 1; }
rm -rf "$PILOT_DIR"

echo "== perf gate (tiny fixed-config bench vs committed baseline) =="
# fails on a >15% graphs/sec regression (and MFU regression on TPU)
# against BENCH_CI_BASELINE.json, keyed per backend:device so every CI
# machine gates against its own recorded number (tools/bench_gate.py)
JAX_PLATFORMS=cpu python tools/bench_gate.py
# the gate must DEMONSTRABLY fail on a slow build: inject a genuine
# per-step slowdown into the timed loop and require a nonzero exit
if JAX_PLATFORMS=cpu python tools/bench_gate.py --inject-slowdown-ms 40 >/tmp/_gate_inject.log 2>&1; then
    echo "FAIL: bench gate did not catch an injected 40 ms/step slowdown"
    cat /tmp/_gate_inject.log
    exit 1
else
    echo "bench gate self-test: injected slowdown correctly rejected"
fi
# same for the traffic arm: price a real ballast executable's
# cost-model bytes into the step and require a nonzero exit
if JAX_PLATFORMS=cpu python tools/bench_gate.py --inject-traffic-mb 64 >/tmp/_gate_traffic.log 2>&1; then
    echo "FAIL: bench gate did not catch 64 MiB of injected step traffic"
    cat /tmp/_gate_traffic.log
    exit 1
else
    echo "bench gate self-test: injected traffic correctly rejected"
fi
# warm-start arm: same executable through a fresh cache — the warm start
# must cost <50% of the cold compile and perform 0 XLA compiles
JAX_PLATFORMS=cpu python tools/bench_gate.py --warm-start-arm

if [ "${CI_FULL:-0}" = "1" ]; then
    echo "== full acceptance matrix (reference thresholds) =="
    HYDRAGNN_FULL_MATRIX=1 python -m pytest tests/test_train_matrix.py -q
else
    echo "== full acceptance matrix: skipped (set CI_FULL=1) =="
fi

if [ "${CI_TPU:-0}" = "1" ]; then
    echo "== chip smoke (one process holds the chip) =="
    python chip_smoke.py
else
    echo "== chip smoke: skipped (set CI_TPU=1, needs a TPU) =="
fi

echo "CI protocol complete."
