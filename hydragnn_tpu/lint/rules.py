"""The graftlint rule set: HG001–HG008, one class per invariant.

Each rule encodes something a past PR paid to learn (docs/LINT.md has
the incident history). They are deliberately AST-shallow — no type
inference, no cross-module dataflow — tuned so that every finding on
this tree is a true positive and near-misses (the same call in a
legitimate position) stay silent. When a rule can't decide, it stays
quiet: the linter's contract is zero false positives on the shipped
tree, enforced by tests/test_graftlint.py's meta-test.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterator, List, Optional, Set, Tuple

from .core import (
    Finding,
    ParsedModule,
    Rule,
    dotted_name,
    load_flight_kinds,
    load_knob_registry,
    string_arg,
)

_KNOB_RE = re.compile(r"HYDRAGNN_[A-Z0-9_]*\Z")


def _functions_by_name(tree: ast.Module) -> Dict[str, ast.FunctionDef]:
    """Module-level and method-level function defs by bare name (last
    definition wins — fine for reachability)."""
    out: Dict[str, ast.FunctionDef] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out[node.name] = node
    return out


def _called_names(func: ast.AST) -> Set[str]:
    """Bare names referenced anywhere in a function body — call
    targets, plus functions passed by name (``jax.jit(step)``)."""
    names: Set[str] = set()
    for node in ast.walk(func):
        if isinstance(node, ast.Name):
            names.add(node.id)
    return names


def _nested_defs(func: ast.FunctionDef) -> List[ast.FunctionDef]:
    """Function defs nested (at any depth) inside ``func``."""
    out: List[ast.FunctionDef] = []

    def visit(node: ast.AST) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.append(child)
            visit(child)

    visit(func)
    return out


class HostSyncInHotPath(Rule):
    """HG001 — no host synchronization inside traced step/epoch bodies.

    The step builders (``make_train_step``/``make_scan_epoch``/... and
    their sharded/edge-sharded twins) return jitted functions whose
    nested bodies are traced once and dispatched thousands of times; a
    ``block_until_ready``/``device_get``/``np.asarray``/``float()``
    there either fails tracing or — worse — silently forces a D2H
    round-trip per step (the r06 regression the async-dispatch PR
    removed). Builder-level host ops run once at build time and are
    fine, so only *nested* function bodies are scanned. ``obs/spans.py``
    is allowlisted wholesale: its sampled sync window is the one place
    a deliberate device sync belongs.
    """

    id = "HG001"
    name = "host-sync-in-hot-path"
    description = (
        "host sync (block_until_ready / device_get / np.asarray / "
        "float()/int() / .item()) inside a traced body reachable from a "
        "step/epoch builder"
    )
    exclude = ("obs/spans.py", "tests/", "examples/", "lint/")

    HOT_ROOTS = (
        "make_train_step",
        "make_scan_epoch",
        "make_scan_eval",
        "make_stats_step",
        "make_eval_step",
        "make_diagnostics_step",
        "make_diagnosed_first_step",
        "make_sharded_train_step",
        "make_sharded_stats_step",
        "make_sharded_eval_step",
        "make_dp_edge_train_step",
        "make_dp_edge_eval_step",
        "make_dp_edge_stats_step",
    )
    _NP_ALIASES = ("np", "numpy", "onp")

    def _reachable(self, module: ParsedModule) -> List[ast.FunctionDef]:
        funcs = _functions_by_name(module.tree)
        todo = [n for n in self.HOT_ROOTS if n in funcs]
        seen: Set[str] = set()
        while todo:
            name = todo.pop()
            if name in seen:
                continue
            seen.add(name)
            for called in _called_names(funcs[name]):
                if called in funcs and called not in seen:
                    todo.append(called)
        return [funcs[n] for n in sorted(seen)]

    def _sync_call(self, call: ast.Call) -> Optional[str]:
        func = call.func
        if isinstance(func, ast.Attribute):
            if func.attr == "block_until_ready":
                return "block_until_ready()"
            if func.attr == "device_get":
                return f"{dotted_name(func) or 'device_get'}()"
            if func.attr == "item":
                return ".item()"
            if func.attr in ("asarray", "array"):
                base = func.value
                if isinstance(base, ast.Name) and base.id in self._NP_ALIASES:
                    return f"{base.id}.{func.attr}()"
        elif isinstance(func, ast.Name) and func.id in ("float", "int"):
            if call.args and not isinstance(call.args[0], ast.Constant):
                return f"{func.id}() on a runtime value"
        return None

    def check(self, module: ParsedModule) -> Iterator[Finding]:
        for builder in self._reachable(module):
            for body in _nested_defs(builder):
                for node in ast.walk(body):
                    if not isinstance(node, ast.Call):
                        continue
                    what = self._sync_call(node)
                    if what:
                        yield self.finding(
                            module,
                            node,
                            f"{what} inside traced body "
                            f"'{body.name}' of hot builder "
                            f"'{builder.name}' forces a per-step host "
                            "sync (docs/PERF.md sync discipline)",
                        )


class MeshOutsidePartitioner(Rule):
    """HG002 — ``Mesh`` is constructed in ``hydragnn_tpu/parallel/``
    and nowhere else.

    The AST-accurate replacement for the old ``grep -rn 'Mesh('`` gate
    in ci.sh's partitioner-smoke stage: it additionally sees ``jax.sharding.Mesh(...)``
    attribute calls, module aliases (``import jax.sharding as sh;
    sh.Mesh(...)``), and aliased imports (``from jax.sharding import
    Mesh as M``) that the grep missed. Every mesh must come from the
    Partitioner so train/serve/bench agree on axis layout.
    """

    id = "HG002"
    name = "mesh-outside-partitioner"
    description = (
        "jax.sharding.Mesh imported or constructed outside "
        "hydragnn_tpu/parallel/"
    )
    exclude = ("hydragnn_tpu/parallel/", "tests/", "lint/")

    _MESH_MODULES = ("jax.sharding", "jax.experimental.maps", "jax.interpreters.pxla")

    def check(self, module: ParsedModule) -> Iterator[Finding]:
        sharding_aliases: Set[str] = set()
        mesh_names: Set[str] = set()
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name in self._MESH_MODULES:
                        sharding_aliases.add(alias.asname or alias.name)
            elif isinstance(node, ast.ImportFrom):
                for alias in node.names:
                    if alias.name == "Mesh":
                        mesh_names.add(alias.asname or alias.name)
                        yield self.finding(
                            module,
                            node,
                            f"'Mesh' imported from {node.module or '.'}"
                            " — construct meshes via hydragnn_tpu.parallel"
                            " (Partitioner) only",
                        )
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            dn = dotted_name(node.func)
            if dn is None:
                continue
            if dn in mesh_names:
                yield self.finding(
                    module,
                    node,
                    f"mesh constructed via '{dn}(' outside "
                    "hydragnn_tpu/parallel/",
                )
            elif dn.endswith(".Mesh"):
                base = dn[: -len(".Mesh")]
                if base in sharding_aliases or base in self._MESH_MODULES:
                    yield self.finding(
                        module,
                        node,
                        f"mesh constructed via '{dn}(' outside "
                        "hydragnn_tpu/parallel/",
                    )


class DonationAfterDeserialize(Rule):
    """HG003 — deserialized executables only flow through the gated
    loader in ``utils/exec_cache.py``.

    On jax 0.4.x a deserialized executable with donated arguments is
    memory-unsafe unless the donation round-trip probe has passed
    (``exec_cache.donation_roundtrip_ok``). ``ExecCache.load`` wraps
    every ``deserialize_and_load`` with that gate plus digest and
    compat checks; a direct call anywhere else bypasses all three.
    """

    id = "HG003"
    name = "donation-after-deserialize"
    description = (
        "direct deserialize_and_load/deserialize_executable call outside "
        "utils/exec_cache.py bypasses the donation-probe gate"
    )
    exclude = ("utils/exec_cache.py", "tests/", "lint/")

    _LOADERS = ("deserialize_and_load", "deserialize_executable")

    def check(self, module: ParsedModule) -> Iterator[Finding]:
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            dn = dotted_name(node.func)
            if dn and dn.split(".")[-1] in self._LOADERS:
                yield self.finding(
                    module,
                    node,
                    f"'{dn}' called directly — use ExecCache.load, which "
                    "applies the digest, compat, and donation-probe gates "
                    "(utils/exec_cache.py)",
                )


class JitInLoop(Rule):
    """HG004 — no ``jax.jit``/``pjit`` construction inside a loop body.

    A jit wrapper built per iteration recompiles (or at best re-hashes)
    every pass — the classic silent 100x regression. Hoist the wrapper
    out of the loop or reuse a cached executable. Lexical check: any
    jit/pjit call (including via ``functools.partial``) whose nearest
    enclosing statement sits in a ``for``/``while`` body.

    Promoted warning -> error once the tree reached zero findings: a
    recompile-per-iteration hazard is never acceptable on the hot path,
    and the empty committed baseline keeps it that way.
    """

    id = "HG004"
    name = "jit-in-loop"
    severity = "error"
    description = "jax.jit/pjit called inside a for/while body (recompile hazard)"
    exclude = ("tests/", "examples/", "lint/")

    @staticmethod
    def _is_jit(call: ast.Call) -> bool:
        dn = dotted_name(call.func)
        if dn is None:
            return False
        leaf = dn.split(".")[-1]
        if leaf in ("jit", "pjit"):
            return True
        if leaf == "partial":
            for arg in call.args[:1]:
                adn = dotted_name(arg)
                if adn and adn.split(".")[-1] in ("jit", "pjit"):
                    return True
        return False

    def check(self, module: ParsedModule) -> Iterator[Finding]:
        loops: List[ast.AST] = [
            n for n in ast.walk(module.tree) if isinstance(n, (ast.For, ast.While))
        ]
        seen: Set[int] = set()
        for loop in loops:
            for node in ast.walk(loop):
                if (
                    isinstance(node, ast.Call)
                    and id(node) not in seen
                    and self._is_jit(node)
                ):
                    seen.add(id(node))
                    yield self.finding(
                        module,
                        node,
                        "jit construction inside a loop body recompiles "
                        "per iteration — hoist the wrapper or use "
                        "ExecCache.get_or_compile",
                    )


class UnregisteredFlightKind(Rule):
    """HG005 — every ``record(kind, ...)`` literal is a registered
    flight-event kind.

    ``obs/flight.py`` validates committed flight artifacts against its
    ``_REQUIRED``/``FAULT_KINDS`` tables; an event kind recorded but
    never registered passes at write time and then fails (or silently
    escapes) every downstream ``validate_flight_record`` gate — schema
    drift of exactly the sort the r08 serve-resilience work burned a
    day on. Register the kind (with its required payload fields) in
    ``_REQUIRED`` first.
    """

    id = "HG005"
    name = "unregistered-flight-kind"
    description = (
        "record(kind=...) string literal not present in obs/flight.py's "
        "_REQUIRED/FAULT_KINDS tables"
    )
    exclude = ("tests/", "examples/", "lint/")

    def __init__(self, repo_root: str):
        self._kinds = load_flight_kinds(repo_root)

    def check(self, module: ParsedModule) -> Iterator[Finding]:
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            is_record = (
                isinstance(func, ast.Attribute) and func.attr == "record"
            ) or (isinstance(func, ast.Name) and func.id == "record")
            if not is_record:
                continue
            kind = string_arg(node, 0, "kind")
            if kind is not None and kind not in self._kinds:
                yield self.finding(
                    module,
                    node,
                    f"flight kind '{kind}' is not registered in "
                    "obs/flight.py _REQUIRED/FAULT_KINDS — "
                    "validate_flight_record will reject or ignore it",
                )


class UndeclaredEnvKnob(Rule):
    """HG006 — every ``HYDRAGNN_*`` name in the tree is declared in
    ``utils/knobs.py``, and every declared knob is still referenced.

    The registry is the single source for docs/KNOBS.md and the typed
    accessors; a string literal that bypasses it is an undocumented
    knob (or a typo that silently reads the default forever). Checked
    on every string constant matching ``HYDRAGNN_[A-Z0-9_]*`` — a
    literal that is a *prefix* of registered names (e.g. the
    ``HYDRAGNN_INJECT_`` family scans) is allowed and marks the whole
    family as referenced. Test files are scanned for reference
    tracking but never flagged (fixtures are deliberately invalid).
    The stale-registry arm only fires on full-tree scans.
    """

    id = "HG006"
    name = "undeclared-env-knob"
    description = (
        "HYDRAGNN_* literal absent from the utils/knobs.py registry "
        "(or a registered knob no longer referenced anywhere)"
    )
    exclude = ("utils/knobs.py",)

    def __init__(self, repo_root: str):
        self._registry = load_knob_registry(repo_root)
        self._knobs_path = "hydragnn_tpu/utils/knobs.py"
        self._used: Set[str] = set()

    def check(self, module: ParsedModule) -> Iterator[Finding]:
        emit = "tests/" not in module.path and "lint/" not in module.path
        for node in ast.walk(module.tree):
            if not (isinstance(node, ast.Constant) and isinstance(node.value, str)):
                continue
            text = node.value
            if not _KNOB_RE.fullmatch(text):
                continue
            if text in self._registry:
                self._used.add(text)
                continue
            family = [k for k in self._registry if k.startswith(text)]
            if family:
                # prefix scan (e.g. "HYDRAGNN_INJECT_") references the family
                self._used.update(family)
                continue
            if emit:
                yield self.finding(
                    module,
                    node,
                    f"env knob '{text}' is not declared in "
                    "hydragnn_tpu/utils/knobs.py — register it (and its "
                    "type/default/doc line) so docs/KNOBS.md stays true",
                )

    def finalize(self) -> Iterator[Finding]:
        for name in sorted(set(self._registry) - self._used):
            yield Finding(
                rule=self.id,
                path=self._knobs_path,
                line=self._registry[name],
                col=1,
                message=(
                    f"knob '{name}' is declared in the registry but "
                    "referenced nowhere in the tree — delete the stale "
                    "entry or restore its consumer"
                ),
                severity=self.severity,
                snippet=name,
            )


class BareAssertContract(Rule):
    """HG007 — no ``assert`` for runtime contracts in library code.

    ``python -O`` strips asserts, so a contract expressed as ``assert``
    is a no-op in optimized deployments (the r05 #2 bug class: a
    shape-contract assert compiled away and the bad batch reached the
    kernel). Raise a typed exception instead; tests and examples keep
    their asserts.
    """

    id = "HG007"
    name = "bare-assert-contract"
    description = "assert statement in library code (stripped under python -O)"
    exclude = ("tests/", "examples/", "lint/")

    def check(self, module: ParsedModule) -> Iterator[Finding]:
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Assert):
                yield self.finding(
                    module,
                    node,
                    "bare assert in library code is stripped under "
                    "python -O — raise a typed exception "
                    "(e.g. ValueError / an AssertionError subclass)",
                )


class TracerLeak(Rule):
    """HG008 — no stores to ``self.``/globals inside jitted bodies.

    Assigning a traced value to an object attribute or module global
    from inside a jitted function leaks the tracer: the first call
    stores a tracer object that outlives the trace, and every later
    read raises ``TracerLeakError`` (or worse, silently holds stale
    constants after the first compile). Return the value instead.
    Checked inside functions that are jit-decorated or passed by name
    to ``jax.jit``/``pjit`` in the same module.
    """

    id = "HG008"
    name = "tracer-leak"
    description = (
        "assignment to self.*/global inside a jitted function body "
        "(tracer leak)"
    )
    exclude = ("tests/", "examples/", "lint/")

    @staticmethod
    def _is_jit_ref(node: ast.AST) -> bool:
        dn = dotted_name(node)
        return dn is not None and dn.split(".")[-1] in ("jit", "pjit")

    def _jitted_functions(self, tree: ast.Module) -> List[ast.FunctionDef]:
        funcs = _functions_by_name(tree)
        jitted: Dict[str, ast.FunctionDef] = {}
        for name, func in funcs.items():
            for dec in func.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                if self._is_jit_ref(target):
                    jitted[name] = func
                elif isinstance(dec, ast.Call) and any(
                    self._is_jit_ref(a) for a in dec.args[:1]
                ):
                    jitted[name] = func  # functools.partial(jax.jit, ...)
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and self._is_jit_ref(node.func):
                for arg in node.args[:1]:
                    if isinstance(arg, ast.Name) and arg.id in funcs:
                        jitted[arg.id] = funcs[arg.id]
        return list(jitted.values())

    def check(self, module: ParsedModule) -> Iterator[Finding]:
        for func in self._jitted_functions(module.tree):
            for node in ast.walk(func):
                if isinstance(node, ast.Global):
                    yield self.finding(
                        module,
                        node,
                        f"'global {', '.join(node.names)}' inside jitted "
                        f"'{func.name}' — a traced store to a global "
                        "leaks the tracer; return the value instead",
                    )
                elif isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                    targets = (
                        node.targets
                        if isinstance(node, ast.Assign)
                        else [node.target]
                    )
                    for tgt in targets:
                        if (
                            isinstance(tgt, ast.Attribute)
                            and isinstance(tgt.value, ast.Name)
                            and tgt.value.id == "self"
                        ):
                            yield self.finding(
                                module,
                                node,
                                f"store to 'self.{tgt.attr}' inside jitted "
                                f"'{func.name}' leaks the tracer — return "
                                "the value instead",
                            )


def all_rules(repo_root: str) -> List[Rule]:
    """The shipped rule set, in id order."""
    return [
        HostSyncInHotPath(),
        MeshOutsidePartitioner(),
        DonationAfterDeserialize(),
        JitInLoop(),
        UnregisteredFlightKind(repo_root),
        UndeclaredEnvKnob(repo_root),
        BareAssertContract(),
        TracerLeak(),
    ]
