"""The program's own spans, read beside ``trace_reduce.py``.

    python3 benchmark/program_spans.py <file.xplane.pb>

``hydragnn_tpu/obs/spans.py:span`` puts every phase of the program's
set-up and of an epoch under a ``jax.profiler.TraceAnnotation`` and keeps
its host seconds in the flight record (``setup`` event ``phases``; ``epoch``
events ``phases``, and ``phases_late`` for the spans that closed after
their epoch's event was written: ``epoch.record``, ``epoch.checkpoint``,
``epoch`` itself; ``run_end`` carries the last ones). This file reads both.

What one v5e trace of the program looks like (PR 23, looked at by hand):

  * the spans are events of the host plane's ``python3`` line, named as
    the program names them (``epoch.train``, ``train.dispatch``); an
    attribute (``epoch=4``, ``step=2``) is a stat of the event, not part of
    its name. A span that was open when the capture began or ended is
    not in the trace: of the first traced epoch, ``epoch`` itself;
  * a Pallas kernel's ``name=`` (``ops/segment_pallas.py``,
    ``ops/fused_conv.py``) IS the instruction's name on the ``XLA Ops``
    line (``%gather_stats.7 = ... custom-call(...)``): ``name=`` alone
    reaches it on this runtime (``pallas_call`` opens the
    ``jax.named_scope`` itself), so ``trace_reduce.kernel_stem`` gives the
    kernel and the ledger's ``device_ops`` split by kernel;
  * the ``XLA Modules`` line has one event per executed program, named
    ``jit_<function>(<fingerprint>)``: ``jit_train_scan_epoch_guarded``,
    ``jit_diagnostics_step``, ``jit_eval_scan``, ``jit_eval_step_outputs``.

Idle time: inside the traced window (``bench_trace_begin`` to
``bench_trace_end``), the fullest chip's idle intervals are cut at the
spans' boundaries and every piece goes to the DEEPEST span that covers it
(``idle_self_s``); ``idle_s`` of a span is the idle time anywhere under
it. What lies under no child of ``epoch`` is ``unattributed``. So the
children of ``epoch`` and ``unattributed`` add up to the window's idle
time exactly. ``idle_in_program_s`` is the part of a span's idle time
that lies INSIDE an ``XLA Modules`` event: no host gap, but the device
waiting inside a running program, and what is left of its real containers
(a ``while``'s own bookkeeping between two bodies). Until PR 25 most of it
was real work that ``trace_reduce.leaves`` dropped (an operation that one
of XLA's zero-length custom calls shares its start with: 0.185 s of
0.905 s idle in PR 23's PNA trace); since the repair it reads a few
milliseconds an epoch. The idle time here is ``trace_reduce``'s own
(``leaves``, ``union``), so the two agree to the nanosecond.

Every function returns ``None``, and raises nothing, where the spans are
not there: a rehearsal (no trace), an older program (no span, no
``phases``).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys
import traceback
from typing import Any, Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import trace_reduce as tr  # noqa: E402

# the program's naming scheme: ``epoch``, and ``<phase>.<part>``
SPAN_NAME = re.compile(r"^(epoch|(setup|epoch|train|validate|test)\.[a-z_0-9]+)$")
ROOT = "epoch"
MODULES_LINE = "XLA Modules"
PROGRAM = re.compile(r"^(.*?)\(\d+\)$")  # jit_eval_scan(13988079081748679534)
DIAGNOSTICS = "jit_diagnostics_step"
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
NS = 1e-9

Interval = Tuple[int, int]


class _Cover:
    """Sorted disjoint intervals, with the covered time of any [a, b)."""

    def __init__(self, intervals: List[Interval]):
        self.iv = intervals
        self.starts = [s for s, _ in intervals]
        self.before = [0]
        for s, e in intervals:
            self.before.append(self.before[-1] + e - s)

    def inside(self, a: int, b: int) -> int:
        if b <= a or not self.iv:
            return 0
        i = bisect.bisect_right(self.starts, a) - 1
        j = bisect.bisect_left(self.starts, b)
        acc = self.before[j] - self.before[max(i, 0)]
        if i >= 0:
            s, e = self.iv[i]
            acc -= min(max(a - s, 0), e - s)
        if j - 1 >= 0:
            s, e = self.iv[j - 1]
            acc -= max(e - max(b, s), 0)
        return acc

    @property
    def total(self) -> int:
        return self.before[-1]


def _intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Of two sorted disjoint lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def span_events(pd) -> List[Tuple[int, int, str, str]]:
    """(start, end, name, thread) of every host event named as a program
    span."""
    out = []
    for plane in pd.planes:
        if tr.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if SPAN_NAME.match(e.name):
                    s = int(e.start_ns)
                    out.append((s, s + int(e.duration_ns), e.name, f"{plane.name}/{line.name}"))
    return out


def parents_from_trace(events: List[Tuple[int, int, str, str]]) -> Dict[str, Optional[str]]:
    """Each span's parent as the trace shows it: the innermost span of the
    same thread around it, the commonest one over its instances (an
    instance whose parent began before the capture has none)."""
    votes: Dict[str, Dict[str, int]] = {}
    by_thread: Dict[str, list] = {}
    for ev in events:
        by_thread.setdefault(ev[3], []).append(ev)
    for evs in by_thread.values():
        stack: List[Tuple[int, int, str, str]] = []
        for ev in sorted(evs, key=lambda x: (x[0], -x[1])):
            while stack and stack[-1][1] <= ev[0]:
                stack.pop()
            votes.setdefault(ev[2], {})
            if stack and stack[-1][2] != ev[2]:
                votes[ev[2]][stack[-1][2]] = votes[ev[2]].get(stack[-1][2], 0) + 1
            stack.append(ev)
    return {name: (max(v, key=v.get) if v else None) for name, v in votes.items()}


def modules(pd, chip: int) -> List[Tuple[int, int, str]]:
    """(start, end, program) of the chip's ``XLA Modules`` events."""
    out = []
    for plane in pd.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) != chip:
            continue
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for e in line.events:
                s = int(e.start_ns)
                named = PROGRAM.match(e.name)
                out.append((s, s + int(e.duration_ns), named.group(1) if named else e.name))
    return out


def table(path: str, parents: Optional[Dict[str, Optional[str]]] = None) -> Optional[Dict[str, Any]]:
    """The reduction of one trace (module docstring). ``parents`` is the
    program's own span table ``{name: parent}`` where the flight record
    gives it; else the nesting the trace shows. ``None`` where the trace
    has no device operation or no program span."""
    pd = tr.load(path)
    dev = {chip: tr.leaves(ev) for chip, ev in tr.device_events(pd).items()}
    dev = {chip: ev for chip, ev in dev.items() if ev}
    spans = span_events(pd)
    if not dev or not spans:
        return None
    marks = tr.host_spans(pd)
    lo = marks[tr.MARK_BEGIN][0][1] if marks[tr.MARK_BEGIN] else min(e[0] for ev in dev.values() for e in ev)
    hi = marks[tr.MARK_END][-1][0] if marks[tr.MARK_END] else max(e[1] for ev in dev.values() for e in ev)
    busy_of = {chip: tr.union(tr.clip([(s, e) for s, e, _, _ in ev], lo, hi)) for chip, ev in dev.items()}
    chip = max(busy_of, key=lambda c: tr.total(busy_of[c]))
    busy = _Cover(busy_of[chip])
    idle = _Cover(tr.complement(busy_of[chip], lo, hi))

    programs: Dict[str, Dict[str, Any]] = {}
    unnamed = busy.total
    running = []  # when some program is on the chip
    for s, e, name in modules(pd, chip):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        running.append((s, e))
        p = programs.setdefault(name, {"device_s": 0.0, "n": 0})
        inside = busy.inside(s, e)
        p["device_s"] += inside * NS
        p["n"] += 1
        unnamed -= inside
    # idle INSIDE a running program is no host gap: a device stall, or an
    # operation the reduction does not count (module docstring)
    in_program = _Cover(_intersect(tr.union(running), idle.iv))

    if parents is None:
        parents = parents_from_trace(spans)
    inside = [(max(s, lo), min(e, hi), name) for s, e, name, _ in spans if min(e, hi) > max(s, lo)]
    if not inside:
        return None
    rows: Dict[str, Dict[str, Any]] = {}
    for s, e, name in inside:
        row = rows.setdefault(name, {"parent": parents.get(name), "s": 0.0, "n": 0, "idle_s": 0.0,
                                     "idle_self_s": 0.0, "idle_in_program_s": 0.0})
        # the children that lie inside THIS instance (of the first traced
        # epoch the ``epoch`` span itself is missing, its children are not)
        below = sum(idle.inside(a, b) for a, b, child in inside if parents.get(child) == name and a >= s and b <= e)
        row["s"] += (e - s) * NS
        row["n"] += 1
        row["idle_s"] += idle.inside(s, e) * NS
        row["idle_self_s"] += (idle.inside(s, e) - below) * NS
        row["idle_in_program_s"] += in_program.inside(s, e) * NS
    attributed = sum(r["idle_s"] for r in rows.values() if r["parent"] == ROOT)

    kernels: Dict[str, Dict[str, Any]] = {}
    for s, e, name, cat in dev[chip]:
        # XLA's own custom calls (ConcatBitcast, AllocateBuffer: no time) are no kernels
        if e <= lo or s >= hi or tr.category(name, cat) != "pallas" or PALLAS_TARGET not in name:
            continue
        k = kernels.setdefault(tr.kernel_stem(name), {"device_s": 0.0, "n": 0})
        k["device_s"] += (min(e, hi) - max(s, lo)) * NS
        k["n"] += 1
    return {
        "chip": chip,
        "window_s": (hi - lo) * NS,
        "busy_s": busy.total * NS,
        "idle_s": idle.total * NS,
        "idle_in_program_s": in_program.total * NS,
        "unattributed_idle_s": (idle.total * NS) - attributed,
        "spans": rows,
        "programs": programs,
        "device_s_outside_any_program": unnamed * NS,
        "kernels": kernels,
    }


# -- the flight record -------------------------------------------------------


def setup_phases(flight: List[Dict[str, Any]]) -> Optional[Dict[str, Dict[str, Any]]]:
    for ev in flight or []:
        if ev.get("kind") == "setup" and isinstance(ev.get("phases"), dict):
            return ev["phases"]
    return None


def epoch_phases(flight: List[Dict[str, Any]]) -> Dict[int, Dict[str, Dict[str, Any]]]:
    """``{epoch: {name: {"s", "n", "parent"}}}``: each epoch event's
    ``phases`` and whatever a later event carries for it as
    ``phases_late``."""
    out: Dict[int, Dict[str, Dict[str, Any]]] = {}
    for ev in flight or []:
        if ev.get("kind") == "epoch" and isinstance(ev.get("phases"), dict):
            out.setdefault(ev["epoch"], {}).update(ev["phases"])
        for late in ev.get("phases_late") or []:
            if late.get("epoch") is not None and isinstance(late.get("phases"), dict):
                out.setdefault(late["epoch"], {}).update(late["phases"])
    return out


def parents_from_flight(flight: List[Dict[str, Any]]) -> Optional[Dict[str, Optional[str]]]:
    parents: Dict[str, Optional[str]] = {}
    for phases in epoch_phases(flight).values():
        for name, row in phases.items():
            parents.setdefault(name, row.get("parent"))
    return parents or None


# -- for the metric readers --------------------------------------------------


def of(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """This run's ``table``, made once and kept in ``ctx``."""
    if "program_spans" not in ctx:
        ctx["program_spans"] = None
        trace_dir = getattr(ctx.get("taps"), "trace_dir", None)
        pb = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)) if trace_dir else []
        parents = parents_from_flight(ctx.get("flight"))
        if pb and parents:
            try:
                ctx["program_spans"] = table(pb[-1], parents)
            except Exception:  # a reader must not take the run's result down
                traceback.print_exc()
    return ctx["program_spans"]


def idle_under_ms(ctx: Dict[str, Any], names: Tuple[str, ...], per: str = "epoch") -> Optional[float]:
    """Milliseconds the fullest chip ran nothing under the spans ``names``,
    per traced epoch, or per instance of the first of them."""
    t = of(ctx)
    if not t or names[0] not in t["spans"]:
        return None
    n = ctx.get("traced_epochs") if per == "epoch" else t["spans"][names[0]]["n"]
    if not n:
        return None
    return 1e3 * sum(t["spans"][k]["idle_s"] for k in names if k in t["spans"]) / n


def main(path: str) -> None:
    t = table(path)
    if t is None:
        print("no program span, or no device operation, in this trace")
        return
    print(f"chip {t['chip']}: window {t['window_s']:.4f} s, busy {t['busy_s']:.4f} s, idle {t['idle_s']:.4f} s, "
          f"of it under no child of `{ROOT}` {t['unattributed_idle_s']:.4f} s, inside a running program "
          f"{t['idle_in_program_s']:.4f} s")

    rows = t["spans"]

    def walk(name: str, depth: int) -> List[Tuple[str, int]]:
        below = sorted((k for k, r in rows.items() if r["parent"] == name), key=lambda k: -rows[k]["s"])
        return [(name, depth)] + [x for k in below for x in walk(k, depth + 1)]

    roots = sorted((k for k, r in rows.items() if r["parent"] not in rows), key=lambda k: -rows[k]["s"])
    print(f"{'span':34s} {'seconds':>9s} {'n':>4s} {'idle under':>11s} {'idle self':>10s} {'in program':>11s}")
    for name, depth in (x for k in roots for x in walk(k, 0)):
        r = rows[name]
        print(f"{'  ' * depth + name:34s} {r['s']:9.4f} {r['n']:4d} {r['idle_s']:11.4f} {r['idle_self_s']:10.4f} "
              f"{r['idle_in_program_s']:11.4f}")
    print(f"{'program':34s} {'device s':>9s} {'n':>4s}")
    for name, p in sorted(t["programs"].items(), key=lambda kv: -kv[1]["device_s"]):
        print(f"{name:34s} {p['device_s']:9.4f} {p['n']:4d}")
    print(f"{'(outside any program)':34s} {t['device_s_outside_any_program']:9.4f}")
    print(f"{'kernel':34s} {'device s':>9s} {'n':>4s}")
    for name, k in sorted(t["kernels"].items(), key=lambda kv: -kv[1]["device_s"]):
        print(f"{name:34s} {k['device_s']:9.4f} {k['n']:4d}")


if __name__ == "__main__":
    main(sys.argv[1])
