"""graftlint: per-rule true-positive / near-miss fixtures, suppression
and baseline machinery, --changed plumbing, artifact validation, the
knob registry, and the meta-test that the shipped tree is lint-clean.

Fixtures are written to tmp_path (outside the repo) so per-rule path
policies (tests/ exemptions etc.) don't mask them, and every run_lint
call builds a fresh rule set — the HG005/HG006 rules carry per-run
state loaded from the real obs/flight.py and utils/knobs.py tables.
"""

import importlib.util
import json
import os
import subprocess
import textwrap

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_cli():
    path = os.path.join(REPO_ROOT, "tools", "graftlint.py")
    spec = importlib.util.spec_from_file_location("_graftlint_cli", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CLI = _load_cli()
CORE, RULES, ARTIFACTS = CLI._load_lint_pkg()

BASELINE = os.path.join(REPO_ROOT, "tools", "graftlint_baseline.json")


def lint(tmp_path, source, rule_ids=None, name="fixture.py"):
    """Write ``source`` to a tmp file and lint it with fresh rules."""
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    rules = RULES.all_rules(REPO_ROOT)
    if rule_ids:
        rules = [r for r in rules if r.id in set(rule_ids)]
    return CORE.run_lint(REPO_ROOT, rules, paths=[str(p)])


# ---------------------------------------------------------------- HG001


class TestHostSyncInHotPath:
    def test_flags_sync_in_traced_body(self, tmp_path):
        findings = lint(
            tmp_path,
            """
            def make_train_step(model):
                def step(state, batch):
                    return float(state.loss)

                return step
            """,
            ["HG001"],
        )
        assert [f.rule for f in findings] == ["HG001"]
        assert "make_train_step" in findings[0].message

    def test_flags_sync_reachable_via_helper(self, tmp_path):
        findings = lint(
            tmp_path,
            """
            def _build_body(model):
                def body(state):
                    state.loss.block_until_ready()
                    return state

                return body


            def make_scan_epoch(model):
                return _build_body(model)
            """,
            ["HG001"],
        )
        assert [f.rule for f in findings] == ["HG001"]

    def test_builder_level_sync_is_build_time(self, tmp_path):
        # host ops directly in the builder run once at build time: fine
        findings = lint(
            tmp_path,
            """
            def make_train_step(model):
                width = int(model.width)

                def step(state, batch):
                    return state

                return step
            """,
            ["HG001"],
        )
        assert findings == []

    def test_non_hot_builder_ignored(self, tmp_path):
        findings = lint(
            tmp_path,
            """
            def make_report(model):
                def fmt(state):
                    return float(state.loss)

                return fmt
            """,
            ["HG001"],
        )
        assert findings == []


# ---------------------------------------------------------------- HG002


class TestMeshOutsidePartitioner:
    def test_flags_aliased_import_and_call(self, tmp_path):
        # the exact case the old grep gate could not see
        findings = lint(
            tmp_path,
            """
            from jax.sharding import Mesh as M


            def build(devices):
                return M(devices, ("data",))
            """,
            ["HG002"],
        )
        assert len(findings) == 2  # the import and the construction
        assert all(f.rule == "HG002" for f in findings)

    def test_flags_module_alias_attribute_call(self, tmp_path):
        findings = lint(
            tmp_path,
            """
            import jax.sharding as sh


            def build(devices):
                return sh.Mesh(devices, ("data",))
            """,
            ["HG002"],
        )
        assert [f.rule for f in findings] == ["HG002"]

    def test_partitioner_usage_is_clean(self, tmp_path):
        findings = lint(
            tmp_path,
            """
            from hydragnn_tpu.parallel import Partitioner


            def build(devices):
                part = Partitioner(devices)
                return part.mesh, part.mesh_shape()
            """,
            ["HG002"],
        )
        assert findings == []


# ---------------------------------------------------------------- HG003


class TestDonationAfterDeserialize:
    def test_flags_direct_deserialize(self, tmp_path):
        findings = lint(
            tmp_path,
            """
            from jax import export


            def load(payload):
                return export.deserialize_and_load(payload)
            """,
            ["HG003"],
        )
        assert [f.rule for f in findings] == ["HG003"]
        assert "ExecCache.load" in findings[0].message

    def test_cache_api_is_clean(self, tmp_path):
        findings = lint(
            tmp_path,
            """
            def load(cache, key):
                return cache.load(key)  # the gated path


            def parse(blob):
                return deserialize_config(blob)  # not an executable loader
            """,
            ["HG003"],
        )
        assert findings == []


# ---------------------------------------------------------------- HG004


class TestJitInLoop:
    def test_flags_jit_under_loop(self, tmp_path):
        findings = lint(
            tmp_path,
            """
            import jax


            def run(fns, x):
                out = []
                for fn in fns:
                    out.append(jax.jit(fn)(x))
                return out
            """,
            ["HG004"],
        )
        assert [f.rule for f in findings] == ["HG004"]
        # promoted warning -> error (ISSUE 13): a recompile-per-iteration
        # hazard on the hot path fails CI outright
        assert findings[0].severity == "error"

    def test_hoisted_jit_is_clean(self, tmp_path):
        findings = lint(
            tmp_path,
            """
            import jax


            def run(fn, xs):
                compiled = jax.jit(fn)
                out = []
                for x in xs:
                    out.append(compiled(x))
                return out
            """,
            ["HG004"],
        )
        assert findings == []


# ---------------------------------------------------------------- HG005


class TestUnregisteredFlightKind:
    def test_flags_unknown_kind(self, tmp_path):
        findings = lint(
            tmp_path,
            """
            def emit(flight):
                flight.record("totally_bogus_kind", x=1)
            """,
            ["HG005"],
        )
        assert [f.rule for f in findings] == ["HG005"]
        assert "totally_bogus_kind" in findings[0].message

    def test_registered_and_dynamic_kinds_are_clean(self, tmp_path):
        kinds = CORE.load_flight_kinds(REPO_ROOT)
        assert "run_start" in kinds and "error" in kinds
        findings = lint(
            tmp_path,
            """
            def emit(flight, kind):
                flight.record("run_start", manifest={})
                flight.record("error", error="e", error_type="E")
                flight.record(kind, x=1)  # non-literal: can't judge, stay quiet
            """,
            ["HG005"],
        )
        assert findings == []


# ---------------------------------------------------------------- HG006


class TestUndeclaredEnvKnob:
    def test_flags_rogue_knob(self, tmp_path):
        findings = lint(
            tmp_path,
            """
            import os


            def read():
                return os.environ.get("HYDRAGNN_DEFINITELY_NOT_A_KNOB")
            """,
            ["HG006"],
        )
        assert [f.rule for f in findings] == ["HG006"]
        assert "HYDRAGNN_DEFINITELY_NOT_A_KNOB" in findings[0].message

    def test_registered_name_and_family_prefix_are_clean(self, tmp_path):
        registry = CORE.load_knob_registry(REPO_ROOT)
        assert "HYDRAGNN_TELEMETRY" in registry
        assert any(k.startswith("HYDRAGNN_INJECT_") for k in registry)
        findings = lint(
            tmp_path,
            """
            import os


            def read(env):
                a = os.environ.get("HYDRAGNN_TELEMETRY")
                fam = [k for k in env if k.startswith("HYDRAGNN_INJECT_")]
                return a, fam
            """,
            ["HG006"],
        )
        assert findings == []

    def test_stale_registry_arm_full_tree_only(self, tmp_path):
        rule = RULES.UndeclaredEnvKnob(REPO_ROOT)
        # nothing referenced: on a full-tree scan every knob looks stale
        stale = list(rule.finalize())
        assert stale and all(f.rule == "HG006" for f in stale)
        assert all(f.path.endswith("utils/knobs.py") for f in stale)
        # but run_lint only calls finalize on full-tree scans
        p = tmp_path / "empty.py"
        p.write_text("x = 1\n")
        findings = CORE.run_lint(
            REPO_ROOT, [RULES.UndeclaredEnvKnob(REPO_ROOT)], paths=[str(p)]
        )
        assert findings == []


# ---------------------------------------------------------------- HG007


class TestBareAssertContract:
    def test_flags_assert(self, tmp_path):
        findings = lint(
            tmp_path,
            """
            def check(batch):
                assert batch.n_node.ndim == 1
                return batch
            """,
            ["HG007"],
        )
        assert [f.rule for f in findings] == ["HG007"]

    def test_raise_is_clean(self, tmp_path):
        findings = lint(
            tmp_path,
            """
            def check(batch):
                if batch.n_node.ndim != 1:
                    raise ValueError("n_node must be 1-D")
                return batch
            """,
            ["HG007"],
        )
        assert findings == []


# ---------------------------------------------------------------- HG008


class TestTracerLeak:
    def test_flags_self_store_in_jitted_body(self, tmp_path):
        findings = lint(
            tmp_path,
            """
            import jax


            class Model:
                @jax.jit
                def forward(self, x):
                    self.last = x
                    return x
            """,
            ["HG008"],
        )
        assert [f.rule for f in findings] == ["HG008"]
        assert "self.last" in findings[0].message

    def test_flags_global_in_function_passed_to_jit(self, tmp_path):
        findings = lint(
            tmp_path,
            """
            import jax

            _COUNT = 0


            def step(x):
                global _COUNT
                _COUNT = _COUNT + 1
                return x


            compiled = jax.jit(step)
            """,
            ["HG008"],
        )
        assert [f.rule for f in findings] == ["HG008"]

    def test_unjitted_method_is_clean(self, tmp_path):
        findings = lint(
            tmp_path,
            """
            class Model:
                def remember(self, x):
                    self.last = x  # eager method: storing is fine
                    return x
            """,
            ["HG008"],
        )
        assert findings == []


# ------------------------------------------------------- suppressions


class TestSuppressions:
    SRC = """
    def check(batch):
        assert batch.ok{comment}
        return batch
    """

    def test_same_line_suppression(self, tmp_path):
        src = self.SRC.format(
            comment="  # graftlint: disable=HG007 -- test fixture"
        )
        assert lint(tmp_path, src, ["HG007"]) == []

    def test_line_above_suppression(self, tmp_path):
        src = (
            "def check(batch):\n"
            "    # graftlint: disable=HG007 -- test fixture\n"
            "    assert batch.ok\n"
            "    return batch\n"
        )
        assert lint(tmp_path, src, ["HG007"]) == []

    def test_file_suppression(self, tmp_path):
        src = (
            "# graftlint: disable-file=HG007\n"
            "def check(batch):\n"
            "    assert batch.ok\n"
            "    return batch\n"
        )
        assert lint(tmp_path, src, ["HG007"]) == []

    def test_wrong_rule_suppression_does_not_mask(self, tmp_path):
        src = self.SRC.format(comment="  # graftlint: disable=HG001")
        findings = lint(tmp_path, src, ["HG007"])
        assert [f.rule for f in findings] == ["HG007"]


# ------------------------------------------------------------ baseline


class TestBaseline:
    def test_round_trip_silences_grandfathered_findings(self, tmp_path):
        fixture = tmp_path / "legacy.py"
        fixture.write_text("def check(x):\n    assert x\n    return x\n")
        rules = [RULES.BareAssertContract()]
        findings = CORE.run_lint(REPO_ROOT, rules, paths=[str(fixture)])
        assert len(findings) == 1

        baseline = tmp_path / "baseline.json"
        CORE.write_baseline(str(baseline), findings)
        again = CORE.run_lint(
            REPO_ROOT,
            [RULES.BareAssertContract()],
            paths=[str(fixture)],
            baseline=str(baseline),
        )
        assert again == []

        # a NEW finding in the same file still surfaces
        fixture.write_text(
            "def check(x):\n    assert x\n    return x\n"
            "def other(y):\n    assert y != 0\n    return y\n"
        )
        fresh = CORE.run_lint(
            REPO_ROOT,
            [RULES.BareAssertContract()],
            paths=[str(fixture)],
            baseline=str(baseline),
        )
        assert len(fresh) == 1 and "y != 0" in fresh[0].snippet

    def test_fingerprint_survives_line_churn(self, tmp_path):
        fixture = tmp_path / "churn.py"
        fixture.write_text("def check(x):\n    assert x\n")
        (f1,) = CORE.run_lint(
            REPO_ROOT, [RULES.BareAssertContract()], paths=[str(fixture)]
        )
        fixture.write_text("import os\n\n\ndef check(x):\n    assert x\n")
        (f2,) = CORE.run_lint(
            REPO_ROOT, [RULES.BareAssertContract()], paths=[str(fixture)]
        )
        assert f1.line != f2.line
        assert f1.fingerprint() == f2.fingerprint()

    def test_committed_baseline_is_empty(self):
        with open(BASELINE) as f:
            data = json.load(f)
        assert data["findings"] == []


# ----------------------------------------------------------- --changed


class TestChangedMode:
    def _git(self, repo, *args):
        subprocess.run(
            ["git", "-c", "user.email=t@t", "-c", "user.name=t",
             "-C", str(repo)] + list(args),
            check=True,
            capture_output=True,
        )

    def test_changed_paths_tracks_modified_and_untracked(self, tmp_path):
        self._git(tmp_path, "init", "-q")
        mod = tmp_path / "mod.py"
        mod.write_text("def ok(x):\n    return x\n")
        self._git(tmp_path, "add", ".")
        self._git(tmp_path, "commit", "-q", "-m", "seed")
        assert CORE.changed_paths(str(tmp_path)) == []

        mod.write_text("def ok(x):\n    assert x\n    return x\n")
        (tmp_path / "new.py").write_text("def n(y):\n    assert y\n")
        changed = CORE.changed_paths(str(tmp_path))
        assert changed == ["mod.py", "new.py"]

        findings = CORE.run_lint(
            str(tmp_path), [RULES.BareAssertContract()], paths=changed
        )
        assert sorted(f.path for f in findings) == ["mod.py", "new.py"]


# ----------------------------------------------------------- artifacts


class TestArtifacts:
    def test_committed_artifacts_are_valid(self):
        assert ARTIFACTS.validate_artifacts(REPO_ROOT) == []

    def test_unregistered_kind_is_reported(self, tmp_path):
        art = tmp_path / "bogus.jsonl"
        art.write_text(
            json.dumps(
                {"v": 2, "kind": "totally_bogus_kind", "t": 0.0, "rank": 0}
            )
            + "\n"
        )
        findings = ARTIFACTS.validate_artifacts(REPO_ROOT, [str(art)])
        assert any("totally_bogus_kind" in f.message for f in findings)

    def test_missing_required_field_is_reported(self, tmp_path):
        art = tmp_path / "short.jsonl"
        art.write_text(
            json.dumps({"v": 2, "kind": "compile", "t": 0.0, "rank": 0})
            + "\n"
        )  # "compile" requires "count"
        findings = ARTIFACTS.validate_artifacts(REPO_ROOT, [str(art)])
        assert any(
            "compile" in f.message and "count" in f.message for f in findings
        )

    def test_missing_file_is_reported(self, tmp_path):
        findings = ARTIFACTS.validate_artifacts(
            REPO_ROOT, [str(tmp_path / "nope.jsonl")]
        )
        assert [f.message for f in findings] == ["flight artifact missing"]

    def test_fleet_record_requires_every_chaos_scenario(self, tmp_path):
        art = tmp_path / "BENCH_FLEET.json"
        record = {
            "metric": "fleet_sustained_qps", "value": 100.0,
            "unit": "graphs/sec", "replicas": 2, "qps_n1": 60.0,
            "qps_n2": 100.0, "scaleout_efficiency": 0.83,
            "warm_replica_aot_compiles": 0, "lost_futures": 0,
            "slo_p99_ms": 3000.0, "failures": [],
            "scenarios": {
                name: {"qps": 1.0}
                for name in ARTIFACTS._FLEET_SCENARIOS
            },
        }
        art.write_text(json.dumps(record))
        assert ARTIFACTS.validate_artifacts(REPO_ROOT, [str(art)]) == []
        del record["scenarios"]["replica_kill"]
        art.write_text(json.dumps(record))
        findings = ARTIFACTS.validate_artifacts(REPO_ROOT, [str(art)])
        assert any("replica_kill" in f.message for f in findings)


# ----------------------------------------------------------------- CLI


class TestCli:
    def test_strict_fixture_fails_with_json_artifact(self, tmp_path):
        fixture = tmp_path / "bad.py"
        fixture.write_text("def check(x):\n    assert x\n")
        out = tmp_path / "findings.json"
        rc = CLI.main(
            [str(fixture), "--rule", "HG007", "--strict", "--no-baseline",
             "--json", str(out)]
        )
        assert rc == 1
        payload = json.loads(out.read_text())
        assert payload["count"] == 1
        assert payload["findings"][0]["rule"] == "HG007"

    def test_unknown_rule_is_usage_error(self):
        assert CLI.main(["--rule", "HG999"]) == 2

    def test_list_rules(self, capsys):
        assert CLI.main(["--list-rules"]) == 0
        listed = capsys.readouterr().out
        for rid in ("HG001", "HG008"):
            assert rid in listed

    def test_promoted_hg004_fails_without_strict(self, tmp_path):
        # HG004 was promoted warning -> error (ISSUE 13): a jit built per
        # loop iteration now fails CI with or without --strict
        fixture = tmp_path / "warn.py"
        fixture.write_text(
            "import jax\n\n\ndef run(fns, x):\n"
            "    out = []\n"
            "    for f in fns:\n"
            "        out.append(jax.jit(f)(x))\n"
            "    return out\n"
        )
        rc = CLI.main([str(fixture), "--rule", "HG004", "--no-baseline"])
        assert rc == 1
        rc = CLI.main(
            [str(fixture), "--rule", "HG004", "--no-baseline", "--strict"]
        )
        assert rc == 1


# ------------------------------------------------------- knob registry


class TestKnobRegistry:
    def test_docs_match_registry(self):
        from hydragnn_tpu.utils import knobs

        with open(os.path.join(REPO_ROOT, "docs", "KNOBS.md")) as f:
            committed = f.read()
        assert committed == knobs.generate_docs(), (
            "docs/KNOBS.md is stale — regenerate with "
            "`python -m hydragnn_tpu.utils.knobs --write docs/KNOBS.md`"
        )

    def test_accessors_and_undeclared_error(self, monkeypatch):
        from hydragnn_tpu.utils import knobs

        monkeypatch.setenv("HYDRAGNN_RESIDENCY_VMEM_MB", "7.5")
        assert knobs.get_float("HYDRAGNN_RESIDENCY_VMEM_MB", 12.0) == 7.5
        monkeypatch.delenv("HYDRAGNN_RESIDENCY_VMEM_MB", raising=False)
        assert knobs.get_float("HYDRAGNN_RESIDENCY_VMEM_MB", 12.0) == 12.0
        monkeypatch.setenv("HYDRAGNN_TELEMETRY", "0")
        assert knobs.get_bool("HYDRAGNN_TELEMETRY", True) is False
        with pytest.raises(knobs.UndeclaredKnobError):
            knobs.raw("HYDRAGNN_DEFINITELY_NOT_A_KNOB")

    def test_active_injections_serve_filter(self, monkeypatch):
        from hydragnn_tpu.utils import knobs

        monkeypatch.setenv("HYDRAGNN_INJECT_NAN_STEP", "5")
        monkeypatch.setenv("HYDRAGNN_INJECT_SERVE_RAISE", "3")
        both = knobs.active_injections()
        assert "HYDRAGNN_INJECT_NAN_STEP" in both
        assert "HYDRAGNN_INJECT_SERVE_RAISE" in both
        train_only = knobs.active_injections(include_serve=False)
        assert "HYDRAGNN_INJECT_NAN_STEP" in train_only
        assert "HYDRAGNN_INJECT_SERVE_RAISE" not in train_only


# ------------------------------------------------------------ meta-test


class TestShippedTree:
    def test_tree_is_lint_clean_with_committed_baseline(self):
        findings = CORE.run_lint(
            REPO_ROOT, RULES.all_rules(REPO_ROOT), baseline=BASELINE
        )
        assert findings == [], "\n" + "\n".join(f.render() for f in findings)
