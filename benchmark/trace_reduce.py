"""From the profiler's ``.xplane.pb`` to busy and idle time, time per
category, Pallas kernel time, exposed collective time and named idle gaps.

Read with ``jax.profiler.ProfileData`` alone (no converter, no
tensorboard). What one real v5e trace of this program looks like (PR 22,
looked at by hand; ``describe`` prints the same for any trace):

  * one plane per chip, ``/device:TPU:<n>``; its line ``XLA Ops`` holds one
    event per executed HLO instruction, named by the instruction's whole
    text (``%conv_1.67 = bf16[1724416,128]{...} custom-call(...),
    custom_call_target="tpu_custom_call"``, ``%fusion.1388 = ...``,
    ``%while.44 = ...``); the flax module's name survives in it
    (``conv_1``). No ``hlo_category`` stat on this runtime. Control
    flow (``while``, ``conditional``, ``call``) appears as an event that
    CONTAINS its body's events: only leaves are counted, or a scanned
    epoch would read as one solid block;
  * ``XLA Modules`` holds one event per executed program, ``Steps`` the
    profiler's own step markers; neither is counted;
  * the host's threads are lines of the plane ``/host:CPU``; the
    program's own spans (``hydragnn_tpu/obs/spans.py``: ``epoch.train``,
    ``epoch.validate``, ``epoch.test``, ``epoch.checkpoint`` and the other
    children of ``epoch``) and the benchmark's two window marks
    (``taps.py``: ``bench_trace_begin`` / ``bench_trace_end``) are events
    there, on the same clock as the device events. The benchmark opens no
    span of its own (it did until PR 25: ``bench_train`` and three more).

Kernel names: a Pallas kernel is a ``custom-call`` instruction on the
device line. Its instruction name is whatever scope it was traced under
(``conv_1.67`` inside a conv layer, ``segment_sum_local_pallas.3`` in a
backward pass): enough to group by, not a stable name. The kernels' time
is the summed time of all ``custom-call`` leaves. Other instructions'
TEXT mentions their operands (``fusion(... %custom-call.126)``), so
everything here goes by the opcode, never by a substring of the text.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
CONTAINERS = re.compile(r"^(while|conditional|call)([.\d]*)$")
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
MARK_BEGIN, MARK_END = "bench_trace_begin", "bench_trace_end"
TRAIN_SPAN = "epoch.train"  # the train dispatch(es) of one epoch, diagnostics sample included
# the children of the program's ``epoch`` span (they follow one another on one thread): idle time is named by the
# one it lies under
HOST_SPANS = (TRAIN_SPAN, "epoch.validate", "epoch.test", "epoch.head_quality", "epoch.diag_snapshot",
              "epoch.record", "epoch.checkpoint")


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _stats(event) -> Dict[str, Any]:
    try:
        return {k: v for k, v in event.stats}
    except Exception:
        return {}


def category(name: str, hlo_category: Optional[str] = None) -> str:
    """By the instruction's opcode, and for a fusion by its kind, as the
    v5e compiler uses them: ``custom-call`` is a Pallas kernel, a
    ``kOutput`` fusion is rooted in a matrix multiplication, a ``kCustom``
    fusion is XLA's scatter (segment max/min), ``kLoop`` / ``kInput``
    fusions are elementwise and reduction loops."""
    m = SHORT.match(name)
    op = m.group(2) if m else name.lower()
    if op == "custom-call":
        return "pallas"
    if COLLECTIVE.search(op):
        return "collective"
    if op in ("convolution", "dot"):
        return "matmul"
    if op == "fusion":
        kind = re.search(r"kind=(k\w+)", name)
        return {"kOutput": "matmul", "kCustom": "scatter", "kInput": "fused_reduce"}.get(
            kind.group(1) if kind else "", "fused_elementwise")
    if op in ("scatter", "select-and-scatter", "sort"):
        return "scatter"
    if op.startswith(("copy", "async", "slice", "dynamic-", "transpose", "bitcast", "reshape",
                      "concatenate", "pad", "broadcast", "gather")):
        return "copy"
    return "other"


def kernel_stem(name: str) -> str:
    """``%conv_1.67 = ... custom-call(...)`` -> ``conv``: which Pallas
    call this is, as far as the instruction's name says."""
    m = SHORT.match(name)
    return re.sub(r"[_.\d]+$", "", m.group(1)) if m else "?"


SHORT = re.compile(r"^%?([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")


def short_name(name: str) -> str:
    """``%conv_1.67 = bf16[...] custom-call(...)...`` -> ``conv_1.67:custom-call``."""
    m = SHORT.match(name)
    return f"{m.group(1)}:{m.group(2)}" if m else name[:60]


def leaves(events: List[Tuple[int, int, str, Optional[str]]]) -> List[Tuple[int, int, str, Optional[str]]]:
    """Drop every event that contains another one (control flow). An event
    is a container only if what it contains has a length: XLA's zero-length
    custom calls (``ConcatBitcast``, ``AllocateBuffer``) begin at the same
    nanosecond as the operation that follows them and make no container of
    it (until PR 25 they did: 11 operations of every PNA train step were
    dropped, 6 ms a step)."""
    ev = sorted(events, key=lambda e: (e[0], -(e[1])))
    # for each event, the next one in this order that has a length
    nxt: List[Optional[Tuple[int, int, str, Optional[str]]]] = [None] * len(ev)
    following = None
    for i in range(len(ev) - 1, -1, -1):
        nxt[i] = following
        if ev[i][1] > ev[i][0]:
            following = ev[i]
    out = []
    for e, c in zip(ev, nxt):
        has_child = c is not None and c[0] < e[1] and c[1] <= e[1] and (c[0] > e[0] or c[1] < e[1])
        if has_child or CONTAINERS.match(e[2]):
            continue
        out.append(e)
    return out


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def complement(merged: List[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    """What of [lo, hi) a sorted disjoint list inside it leaves uncovered."""
    out, cursor = [], lo
    for s, e in merged:
        if s > cursor:
            out.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        out.append((cursor, hi))
    return out


def clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def overlap(a, b) -> int:
    """Total overlap of two merged interval lists."""
    i = j = 0
    acc = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            acc += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return acc


def collective_times(ev) -> Tuple[int, int]:
    """(time in collectives, the part of it during which nothing else runs
    on that chip) for one chip's leaf events."""
    coll = union([(s, e) for s, e, n, c in ev if category(n, c) == "collective"])
    rest = union([(s, e) for s, e, n, c in ev if category(n, c) != "collective"])
    return total(coll), total(coll) - overlap(coll, rest)


def device_events(pd) -> Dict[int, List[Tuple[int, int, str, Optional[str]]]]:
    out: Dict[int, List] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = list(plane.lines)
        ops = [ln for ln in lines if ln.name == OPS_LINE]
        if not ops:
            continue
        ev = []
        for e in ops[0].events:
            start = int(e.start_ns)
            ev.append((start, start + int(e.duration_ns), e.name, None))
        out[int(m.group(1))] = ev
    return out


def host_spans(pd) -> Dict[str, List[Tuple[int, int]]]:
    want = set(HOST_SPANS) | {MARK_BEGIN, MARK_END}
    out: Dict[str, List[Tuple[int, int]]] = {k: [] for k in want}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in want:
                    s = int(e.start_ns)
                    out[e.name].append((s, s + int(e.duration_ns)))
    return out


def reduce(path: str) -> Dict[str, Any]:
    """See the module docstring. Seconds, averaged over the chips unless
    the key says otherwise."""
    pd = load(path)
    dev = {k: leaves(v) for k, v in device_events(pd).items()}
    if not dev or not any(dev.values()):
        raise RuntimeError("no device operation in the trace")
    spans = host_spans(pd)
    all_starts = [e[0] for v in dev.values() for e in v]
    all_ends = [e[1] for v in dev.values() for e in v]
    lo = spans[MARK_BEGIN][0][1] if spans[MARK_BEGIN] else min(all_starts)
    hi = spans[MARK_END][-1][0] if spans[MARK_END] else max(all_ends)
    train = union(clip(spans[TRAIN_SPAN], lo, hi))

    busy, train_busy, pallas, coll, exposed = [], [], [], [], []
    cats: Dict[str, float] = {}
    names: Dict[str, float] = {}
    per_chip_busy = {}
    for chip, ev in dev.items():
        ev = [(max(s, lo), min(e, hi), n, c) for s, e, n, c in ev if e > lo and s < hi]
        merged = union([(s, e) for s, e, _, _ in ev])
        per_chip_busy[chip] = merged
        busy.append(total(merged))
        train_busy.append(overlap(merged, train))
        pallas.append(sum(e - s for s, e, n, c in ev if category(n, c) == "pallas"))
        c_all, c_exposed = collective_times(ev)
        coll.append(c_all)
        exposed.append(c_exposed)
        for s, e, n, c in ev:
            cat = category(n, c)
            if cat == "pallas":
                cat = f"pallas:{kernel_stem(n)}"
            cats[cat] = cats.get(cat, 0.0) + (e - s)
            names[short_name(n)] = names.get(short_name(n), 0.0) + (e - s)
    chips = len(dev)
    ns = 1e-9
    fullest = max(per_chip_busy, key=lambda k: total(per_chip_busy[k]))
    # idle time of the fullest chip, cut at the program's spans: each piece
    # goes to the child of ``epoch`` that covers it, the rest to host_other
    idle = complement(per_chip_busy[fullest], lo, hi)
    gaps: Dict[str, float] = {}
    for name in HOST_SPANS:
        under = overlap(idle, union(clip(spans[name], lo, hi)))
        if under:
            gaps[name] = float(under)
    rest = total(idle) - sum(gaps.values())
    if rest > 0:
        gaps["host_other"] = float(rest)
    top_ops = sorted(names.items(), key=lambda kv: -kv[1])[:6]
    by_cat = sorted(cats.items(), key=lambda kv: -kv[1])
    breakdown_ops = [[f"category:{k}", v * ns / chips] for k, v in by_cat][:7] + [[k, v * ns / chips] for k, v in top_ops][:3]
    return {
        "chips": chips,
        "window_s": (hi - lo) * ns,
        "busy_s": sum(busy) * ns / chips,
        "busy_max_s": max(busy) * ns,
        "train_busy_s": max(train_busy) * ns,
        "pallas_s": sum(pallas) * ns / chips,
        "collective_s": sum(coll) * ns / chips,
        "collective_exposed_s": sum(exposed) * ns / chips,
        "by_category_s": {k: v * ns / chips for k, v in by_cat},
        "breakdown": {
            "device_ops": breakdown_ops[:10],
            "idle_gaps": [[k, v * ns] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])][:10],
        },
    }


def describe(path: str, top: int = 12) -> Dict[str, Any]:
    """For the look by hand: planes, lines, event counts, the stats keys
    seen and the names that take most time."""
    pd = load(path)
    out = []
    for plane in pd.planes:
        p = {"plane": plane.name, "lines": []}
        for line in plane.lines:
            names: Dict[str, float] = {}
            keys = set()
            n = 0
            first = last = None
            for e in line.events:
                n += 1
                names[e.name] = names.get(e.name, 0.0) + float(e.duration_ns)
                if n <= 50:
                    keys.update(_stats(e).keys())
                s = int(e.start_ns)
                first = s if first is None else min(first, s)
                last = s + int(e.duration_ns) if last is None else max(last, s + int(e.duration_ns))
            p["lines"].append({
                "line": line.name, "events": n, "stat_keys": sorted(keys)[:20],
                "first_ns": first, "last_ns": last,
                "top": [[k, v * 1e-9] for k, v in sorted(names.items(), key=lambda kv: -kv[1])[:top]],
            })
        out.append(p)
    return {"planes": out}


if __name__ == "__main__":
    import json
    import sys

    print(json.dumps(describe(sys.argv[1]), indent=1))
