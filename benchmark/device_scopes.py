"""A train step's device time by pass, from the program's own scopes.

    python3 benchmark/device_scopes.py <file.xplane.pb>

Every instruction of a compiled program carries the name stack it was
traced under (``metadata.op_name``). JAX's automatic differentiation and
flax mark the passes of a train step in it, and the program adds two
scopes of its own (``hydragnn_tpu/obs/spans.py:scope``):

  * ``.../jvp(HydraModel)/conv_3/...``: the forward;
  * ``.../transpose(jvp(HydraModel))/...``: the backward. A remat'd layer's
    backward reads ``transpose(jvp(..))/jvp(..)/checkpoint/layer_0/...``:
    the transposed layer is named under its forward frame, and the rule
    still calls it backward;
  * ``.../checkpoint/rematted_computation/layer_0/...``: the forward
    computed again inside the backward (``nn.remat``, ``jax.checkpoint``);
  * ``.../optimizer/...``: the update and its landing (``train/state.py``);
  * ``.../diagnostics/...``: the per-head pulls and their arithmetic
    (``obs/introspect.py``), but not the pull that makes the update.

What the trace holds (a v5e trace, looked at by hand): the device's ``XLA Ops``
line names each executed instruction by its text (``%fusion.1443 = ...``)
and carries no ``op_name``; the ``XLA Modules`` line names each executed
program ``jit_<name>(<id>)``; and the plane ``/host:metadata`` has one
event-metadata entry per program the process holds, under the same name,
whose stat ``Hlo Proto`` is the program's optimized HLO. So an event is
joined to its program by the ``XLA Modules`` event around it, and to its
instruction by name in that program's HLO. The name with its id is the
key: one function compiled twice (the scan with and without its first
step) is two programs.

Regions, the first rule that matches an instruction's ``op_name`` (a
fusion takes its own instruction's, which XLA gives from its root):
``diagnostics``, ``optimizer`` (a path component of that name),
``recompute`` (``rematted_computation``), ``backward`` (a component
``transpose(...)``), ``forward`` (any other name: the forward's frame and
the step's plumbing beside it, the batch cut from the stack and the
random split), ``unscoped`` (no ``op_name``, or an event that joins no
instruction). An instruction whose name stack names no pass (XLA made it:
a layout copy, or the ``ragged-dot-none`` custom calls it makes of
``jax.lax.ragged_dot``, named after nothing) takes the name of the nearest
instruction that calls its computation and names one (the layer's
``while``). A fusion whose fused instructions fall in more than one
region is ``mixed``: XLA fuses across a scope's edge, and the fusion's time
goes to its root's region all the same.

The events read are those ``step_device_ms`` reads: the fullest chip's
leaf operations inside ``epoch.train`` and inside the window marks.

Every reader returns ``None``, and raises nothing, where the trace has no
metadata plane, the parser (tensorflow's generated protobuf modules) is
missing, or the program carries no ``optimizer`` scope (a program from
before the scopes): the split cannot be told there.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys
import traceback
from typing import Any, Dict, Iterable, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import program_spans as ps  # noqa: E402
import trace_reduce as tr  # noqa: E402

REGIONS = ("diagnostics", "optimizer", "recompute", "backward", "forward", "unscoped")
SCOPES = ("diagnostics", "optimizer")  # the program's (obs/spans.py:SCOPES), in rule order
RECOMPUTE = "rematted_computation"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
# frames of the name stack that are no module: the passes' (differentiation, remat) and control flow's
_PASS = re.compile(r"^((jvp|transpose|vmap)\(.*\)|checkpoint|rematted_computation|remat\d*)$")
_FLOW = re.compile(r"^((jit|pjit)\(.*\)|while|body|cond|closed_call|branch_\d+_fun|custom_jvp_call|custom_vjp_call)$")
_INDEXED = re.compile(r"_\d+$")
# what XLA fuses in beside the work and names after nothing
_PLUMBING = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}
NS = 1e-9


def region(op_name: str) -> str:
    """The pass an instruction belongs to, by its ``op_name`` (module
    docstring)."""
    if not op_name:
        return "unscoped"
    parts = op_name.split("/")
    for name in SCOPES:
        if name in parts:
            return name
    if RECOMPUTE in parts:
        return "recompute"
    if any(p.startswith("transpose(") for p in parts):
        return "backward"
    return "forward"


def component(op_name: str) -> str:
    """The model's part an instruction belongs to: after the last frame of
    a pass in the name stack (``jvp(..)``, ``checkpoint``, a scope) and
    without the primitive's own name, the first flax module that carries an
    index (``conv_3``, ``layer_2``, ``mtp_1``, ``MaskedBatchNorm_0``), else
    the first module (``graph_shared``, ``tokens``), else ``(root)``; a
    method's scope (``HydraModel._apply_conv``) and control flow inside a
    module (``while/body``) are skipped."""
    if not op_name:
        return "(none)"
    parts = op_name.split(";")[0].split("/")[:-1]
    last = max((i for i, p in enumerate(parts) if _PASS.match(p) or p in SCOPES), default=-1)
    modules = [p for p in parts[last + 1:] if not _FLOW.match(p) and "." not in p and "(" not in p]
    for p in modules:
        if _INDEXED.search(p):
            return p
    return modules[0] if modules else "(root)"


class Instr:
    """One instruction of a program's HLO: its region, component, opcode,
    and for a fusion the regions of what it fuses."""

    __slots__ = ("region", "component", "opcode", "fused")

    def __init__(self, op_name: str, opcode: str, fused: Tuple[str, ...] = ()):
        self.region = region(op_name)
        self.component = component(op_name)
        self.opcode = opcode
        self.fused = fused

    @property
    def mixed(self) -> bool:
        return len(set(self.fused)) > 1


def _in_a_pass(op_name: str) -> bool:
    """Whether a name stack says which pass made the instruction."""
    return any(_PASS.match(p) or p in SCOPES for p in op_name.split("/")[:-1])


def instructions(module) -> Dict[str, Instr]:
    """``{instruction name: Instr}`` of an ``HloModuleProto`` (every
    computation: a while body's instructions run as events of their own).
    An instruction whose ``op_name`` names no pass (empty, or a name XLA
    gave what it made: the grouped products' ``ragged-dot-none`` custom
    calls inside a layer's loop) takes the ``op_name`` of the nearest caller
    of its computation that names one (the layer's ``while``); where no
    caller does, it keeps its own (the scan's plumbing is ``forward``, a
    layout copy ``unscoped``)."""
    comps = {c.id: c for c in module.computations}
    callers: Dict[int, Tuple[int, Any]] = {}  # computation -> (its caller's computation, the caller)
    for comp in module.computations:
        for ins in comp.instructions:
            for cid in ins.called_computation_ids:
                callers.setdefault(cid, (comp.id, ins))

    def op_name(cid: int, ins) -> str:
        name, seen = ins.metadata.op_name, set()
        while not _in_a_pass(name) and cid in callers and cid not in seen:
            seen.add(cid)
            cid, caller = callers[cid]
            if _in_a_pass(caller.metadata.op_name):
                return caller.metadata.op_name
        return name

    def fused_regions(cid: int, seen: set) -> List[str]:
        out: List[str] = []
        if cid in seen or cid not in comps:
            return out
        seen.add(cid)
        for ins in comps[cid].instructions:
            if ins.opcode in _PLUMBING:
                continue
            if ins.opcode == "fusion":
                for sub in ins.called_computation_ids:
                    out += fused_regions(sub, seen)
            elif ins.metadata.op_name:
                out.append(region(op_name(cid, ins)))
        return out

    table: Dict[str, Instr] = {}
    for comp in module.computations:
        for ins in comp.instructions:
            fused: Tuple[str, ...] = ()
            if ins.opcode == "fusion":
                fused = tuple(r for sub in ins.called_computation_ids for r in fused_regions(sub, set()))
            table[ins.name] = Instr(op_name(comp.id, ins), ins.opcode, fused)
    return table


def _parsers():
    """tensorflow's generated modules for ``XSpace`` and ``HloProto``, or
    ``None``. (Where tensorflow is not yet imported, the import takes
    seconds; a benchmark run has imported it by then for tensorboard.)"""
    try:
        from tensorflow.compiler.xla.service import hlo_pb2
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except Exception:
        return None
    return xplane_pb2, hlo_pb2


def programs(path: str) -> Optional[Dict[str, Dict[str, Instr]]]:
    """``{program name as the XLA Modules events give it: {instruction:
    Instr}}`` from the trace's metadata plane, or ``None``."""
    parsers = _parsers()
    if parsers is None:
        return None
    xplane_pb2, hlo_pb2 = parsers
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out: Dict[str, Dict[str, Instr]] = {}
    for plane in space.planes:
        if plane.name != METADATA_PLANE:
            continue
        stat = {k for k, v in plane.stat_metadata.items() if v.name == HLO_STAT}
        for em in plane.event_metadata.values():
            for s in em.stats:
                if s.metadata_id in stat and s.bytes_value:
                    proto = hlo_pb2.HloProto()
                    proto.ParseFromString(s.bytes_value)
                    out[em.name] = instructions(proto.hlo_module)
    return out or None


def module_events(pd, chip: int) -> List[Tuple[int, int, str]]:
    """(start, end, name) of the chip's ``XLA Modules`` events, the name
    WITH its id (``program_spans.modules`` keeps the function's name only,
    which cannot tell two programs of one name apart)."""
    out = []
    for plane in pd.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) != chip:
            continue
        for line in plane.lines:
            if line.name == ps.MODULES_LINE:
                for e in line.events:
                    s = int(e.start_ns)
                    out.append((s, s + int(e.duration_ns), e.name))
    return sorted(out)


def _instruction_name(event_name: str) -> Optional[str]:
    m = tr.SHORT.match(event_name)
    return m.group(1) if m else None


def split(path: str, train_only: bool = True) -> Optional[Dict[str, Any]]:
    """Device seconds of the traced train steps by region (module
    docstring), with ``by_category`` and ``by_component`` under each region,
    ``mixed_s`` (time in fusions that span regions), ``total_s``,
    ``unjoined_s`` (events no program's HLO names), ``scoped`` (the program
    carries the ``optimizer`` scope) and ``chip``. ``train_only=False``
    reads every event inside the window marks (a trace cut without
    ``epoch.train``). ``None`` where the trace holds no HLO."""
    progs = programs(path)
    if not progs:
        return None
    pd = tr.load(path)
    dev = {chip: tr.leaves(ev) for chip, ev in tr.device_events(pd).items()}
    dev = {chip: ev for chip, ev in dev.items() if ev}
    if not dev:
        return None
    spans = tr.host_spans(pd)
    starts = [e[0] for ev in dev.values() for e in ev]
    ends = [e[1] for ev in dev.values() for e in ev]
    lo = spans[tr.MARK_BEGIN][0][1] if spans[tr.MARK_BEGIN] else min(starts)
    hi = spans[tr.MARK_END][-1][0] if spans[tr.MARK_END] else max(ends)
    keep = tr.union(tr.clip(spans[tr.TRAIN_SPAN], lo, hi)) if train_only else [(lo, hi)]
    busy = {c: tr.total(tr.union(tr.clip([(s, e) for s, e, _, _ in ev], lo, hi))) for c, ev in dev.items()}
    chip = max(busy, key=busy.get)

    mods = module_events(pd, chip)
    mod_starts = [m[0] for m in mods]
    keep_starts = [k[0] for k in keep]
    out: Dict[str, Any] = {"chip": chip, "total_s": 0.0, "mixed_s": 0.0, "unjoined_s": 0.0,
                           "regions": {r: {"s": 0.0, "by_category": {}, "by_component": {}} for r in REGIONS}}
    used = set()
    for s, e, name, cat in dev[chip]:
        # the part of the event inside the kept intervals
        i = bisect.bisect_right(keep_starts, s) - 1
        inside = 0
        for a, b in keep[max(i, 0):]:
            if a >= e:
                break
            inside += max(0, min(e, b) - max(s, a))
        if inside <= 0:
            continue
        j = bisect.bisect_right(mod_starts, s) - 1  # the program running when the event began
        program = mods[j][2] if j >= 0 and mods[j][1] > s else None
        ins = progs.get(program, {}).get(_instruction_name(name)) if program else None
        if program in progs:
            used.add(program)
        t = inside * NS
        out["total_s"] += t
        if ins is None:
            out["unjoined_s"] += t
            reg, comp = "unscoped", "(unjoined)"
        else:
            reg, comp = ins.region, ins.component
            if ins.mixed:
                out["mixed_s"] += t
        row = out["regions"][reg]
        row["s"] += t
        category = tr.category(name, cat)
        row["by_category"][category] = row["by_category"].get(category, 0.0) + t
        row["by_component"][comp] = row["by_component"].get(comp, 0.0) + t
    out["programs"] = sorted(used)
    out["scoped"] = any(i.region == "optimizer" for p in used for i in progs[p].values())
    return out


# -- for the metric readers --------------------------------------------------


def of(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """This run's ``split``, made once and kept in ``ctx``; ``None`` where
    the program carries no scope."""
    if "device_scopes" not in ctx:
        ctx["device_scopes"] = None
        trace_dir = getattr(ctx.get("taps"), "trace_dir", None)
        pb = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)) if trace_dir else []
        if pb:
            try:
                got = split(pb[-1])
                ctx["device_scopes"] = got if got and got["scoped"] and got["total_s"] > 0 else None
            except Exception:  # a reader must not take the run's result down
                traceback.print_exc()
    return ctx["device_scopes"]


def per_step_ms(ctx: Dict[str, Any], name: str) -> Optional[float]:
    """Milliseconds of the region ``name`` per traced train step (the
    steps ``step_device_ms`` divides by)."""
    got = of(ctx)
    steps = ctx.get("traced_steps")
    if got is None or not steps:
        return None
    return 1e3 * got["regions"][name]["s"] / steps


def diagnosed_steps(ctx: Dict[str, Any]) -> Optional[int]:
    """Steps of the traced epochs whose update came from the program that
    diagnosed them (the ``epoch`` events' ``diagnosed_steps``)."""
    traced = getattr(ctx.get("taps"), "traced", None)
    epochs = ctx.get("epochs") or {}
    if not traced:
        return None
    return sum(int((epochs.get(i) or {}).get("diagnosed_steps") or 0) for i in range(*traced))


# -- the look by hand ---------------------------------------------------------


def _rows(d: Dict[str, float], top: int) -> Iterable[Tuple[str, float]]:
    return sorted(d.items(), key=lambda kv: -kv[1])[:top]


def main(path: str, top: int = 8) -> None:
    got = split(path)
    if got is not None and got["total_s"] == 0:  # a cut without epoch.train
        got = split(path, train_only=False)
    if got is None:
        print("no program HLO in this trace (no /host:metadata plane, or no parser)")
        return
    total = got["total_s"] or 1.0
    print(f"chip {got['chip']}: {got['total_s']:.4f} s of device time read; mixed fusions {got['mixed_s']:.4f} s "
          f"({100 * got['mixed_s'] / total:.2f}%); events joined to no instruction {got['unjoined_s']:.4f} s; "
          f"program scopes {'present' if got['scoped'] else 'ABSENT'}")
    print(f"programs: {', '.join(got['programs'])}")
    for r in REGIONS:
        row = got["regions"][r]
        if not row["s"]:
            continue
        print(f"{r:12s} {row['s']:9.4f} s {100 * row['s'] / total:6.2f}%")
        print("  by category:  " + ", ".join(f"{k} {v:.4f}" for k, v in _rows(row["by_category"], top)))
        print("  by component: " + ", ".join(f"{k} {v:.4f}" for k, v in _rows(row["by_component"], top)))


if __name__ == "__main__":
    main(sys.argv[1])
