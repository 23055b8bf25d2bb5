"""Sweep kernel tile sizes (HYDRAGNN_BN x HYDRAGNN_CE x
HYDRAGNN_BCAST_CE — the gather kernel's chunk reads only the latter)
on the flagship step, traced device time per setting (subprocess per
setting — the constants bake at import).

Usage: python tools/tune_tiles.py [--save] [BNxCE[xBCE] ...]
(BCE defaults to the package default when omitted)

``--save`` persists the sweep's best setting (minimum traced device
ms) into the committed ``TUNE_TILES.json`` at the repo root, keyed
``(shape_tag, device_kind)`` — shape_tag is ``TUNE_CONFIG`` (default
"flagship"), device_kind is what the child measured on.
``hydragnn_tpu/ops/segment_pallas.py`` (and through it
``ops/fused_conv.py``, which imports BN/CE from there) reads its
import-time tile defaults from that table via ``HYDRAGNN_TILE_SHAPE``
/ ``HYDRAGNN_DEVICE_KIND``; the explicit HYDRAGNN_BN/CE/BCAST_CE env
knobs always win. Commit the updated JSON."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = r"""
import glob, os, shutil, sys, time
sys.path.insert(0, %(here)r)
import jax, jax.numpy as jnp, numpy as np
from hydragnn_tpu.flagship import build_flagship
from hydragnn_tpu.train import create_train_state, make_train_step, select_optimizer

import os as _os
if _os.environ.get("TUNE_CONFIG") == "large":
    config, model, variables, loader = build_flagship(
        n_samples=48, hidden_dim=128, num_conv_layers=6, batch_size=32,
        unit_cells=(6, 8),
    )
else:
    config, model, variables, loader = build_flagship(
        n_samples=1280, hidden_dim=128, num_conv_layers=6, batch_size=1024,
        unit_cells=(2, 4),
    )
tx = select_optimizer(config["NeuralNetwork"]["Training"])
state = create_train_state(variables, tx)
step = make_train_step(model, tx, compute_dtype=jnp.bfloat16)
batch = next(iter(loader))
compiled = step.lower(state, batch).compile()
state, loss, _ = compiled(state, batch)
np.asarray(loss)
tdir = "/tmp/tune_trace"
shutil.rmtree(tdir, ignore_errors=True)
with jax.profiler.trace(tdir):
    for _ in range(3):
        state, loss, _ = compiled(state, batch)
    np.asarray(loss)
planes = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
from xprof.convert import raw_to_tool_data as rd
import json as _json
data, _ = rd.xspace_to_tool_data(planes, "hlo_stats", {"tqx": "out:csv;"})
tab = _json.loads(data.decode() if isinstance(data, bytes) else data)
cols = [c["id"] for c in tab["cols"]]
i_t = cols.index("total_self_time")
i_c = cols.index("category")
tot = pall = 0.0
for r in tab["rows"]:
    t = float((r["c"][i_t] or {}).get("v") or 0)
    tot += t
    if (r["c"][i_c] or {}).get("v") == "custom-call":
        pall += t
kind = getattr(jax.devices()[0], "device_kind", "unknown").replace(" ", "_")
print(f"RESULT device={tot/3e3:.2f} pallas={pall/3e3:.2f} loss={float(loss):.5f} kind={kind}")
"""


def run(bn, ce, bce=None):
    env = dict(os.environ, HYDRAGNN_BN=str(bn), HYDRAGNN_CE=str(ce))
    if bce is not None:
        env["HYDRAGNN_BCAST_CE"] = str(bce)
    tag = f"BN={bn} CE={ce}" + (f" BCE={bce}" if bce is not None else "")
    out = subprocess.run(
        [sys.executable, "-c", CHILD % {"here": HERE}],
        env=env, capture_output=True, text=True, timeout=560,
    )
    for line in out.stdout.splitlines():
        if line.startswith("RESULT"):
            print(f"{tag}: {line[7:]}", flush=True)
            try:
                fields = dict(p.split("=", 1) for p in line[7:].split())
                return {
                    "BN": bn,
                    "CE": ce,
                    "BCAST_CE": bce,
                    "device_ms": float(fields["device"]),
                    "kind": fields.get("kind", "unknown"),
                }
            except (KeyError, ValueError):
                return None
    print(f"{tag}: FAILED\n{out.stderr[-500:]}", flush=True)
    return None


def save_best(results) -> None:
    """Merge the sweep's best (min traced device ms) setting into the
    committed TUNE_TILES.json under (shape_tag, device_kind)."""
    best = min(results, key=lambda r: r["device_ms"])
    shape_tag = os.environ.get("TUNE_CONFIG") or "flagship"
    path = os.path.join(HERE, "TUNE_TILES.json")
    table = {}
    if os.path.exists(path):
        with open(path) as f:
            table = json.load(f)
    entry = {
        "BN": best["BN"],
        "CE": best["CE"],
        "device_ms": best["device_ms"],
    }
    if best["BCAST_CE"] is not None:
        entry["BCAST_CE"] = best["BCAST_CE"]
    table.setdefault(shape_tag, {})[best["kind"]] = entry
    with open(path, "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
        f.write("\n")
    print(
        f"saved best setting BN={best['BN']} CE={best['CE']} "
        f"BCE={best['BCAST_CE']} ({best['device_ms']} ms) -> {path} "
        f"[{shape_tag}:{best['kind']}] — commit it; consumers select it "
        f"via HYDRAGNN_TILE_SHAPE={shape_tag} "
        f"HYDRAGNN_DEVICE_KIND={best['kind']}"
    )


if __name__ == "__main__":
    argv = [a for a in sys.argv[1:] if a != "--save"]
    save = len(argv) != len(sys.argv) - 1
    # r05-measured gather-chunk sweep included: 512/1024/2048 traced
    # 77.8 / 75.9 / 79.7 ms on the flagship (docs/PERF.md)
    settings = [
        (128, 512, None),
        (256, 512, None),
        (128, 512, 512),
        (128, 512, 2048),
        (128, 1024, None),
    ]
    if argv:
        settings = []
        for s in argv:
            parts = list(map(int, s.split("x")))
            settings.append(tuple(parts) if len(parts) == 3 else (*parts, None))
    results = [r for r in (run(bn, ce, bce) for bn, ce, bce in settings) if r]
    if save:
        if not results:
            print("no successful settings — nothing to save")
            sys.exit(1)
        save_best(results)
