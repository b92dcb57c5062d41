"""parity-lint conformance: fixture snippets per rule (trigger + pass),
suppression and baseline behavior, CLI exit codes, and the meta-test that
keeps the live ``src/repro`` tree clean modulo the checked-in baseline.
"""
import json
import os
import textwrap
from pathlib import Path

import pytest

from repro.analysis import (ERROR, UNUSED_SUPPRESSION, WARNING,
                            default_rules, lint_paths, run_source)
from repro.analysis.baseline import Baseline, baseline_dict
from repro.analysis.report import to_json
from repro import cli

REPO = Path(__file__).resolve().parents[1]
BASELINE = REPO / "parity-lint-baseline.json"


def lint(src: str, path: str = "core/module.py"):
    return run_source(textwrap.dedent(src), path)


def rule_names(findings):
    return [f.rule for f in findings]


# ----------------------------------------------------------- rng discipline
class TestRngRules:
    def test_np_module_draw_triggers(self):
        out = lint("np.random.shuffle(order)\n")
        assert rule_names(out) == ["rng-module-draw"]
        assert out[0].severity == ERROR

    def test_py_module_draw_triggers(self):
        out = lint("x = random.randint(0, 7)\n")
        assert rule_names(out) == ["rng-module-draw"]

    def test_seeded_constructors_pass(self):
        assert lint("""
            rng = np.random.default_rng(seed)
            g = np.random.Generator(np.random.Philox(key=seed))
            r = random.Random(seed * 3 + 1)
            x = rng.random()
        """) == []

    def test_scope_outside_core_passes(self):
        assert lint("np.random.shuffle(order)\n",
                    path="training/optimizer.py") == []

    def test_time_seed_triggers_everywhere(self):
        out = lint("rng = random.Random(time.time())\n",
                   path="serving/engine.py")
        assert rule_names(out) == ["rng-time-seed"]

    def test_unseeded_constructor_triggers(self):
        out = lint("rng = np.random.default_rng()\n", path="hub/x.py")
        assert rule_names(out) == ["rng-time-seed"]

    def test_seed_method_from_clock_triggers(self):
        out = lint("rng.seed(int(time.time_ns()))\n", path="data/x.py")
        assert rule_names(out) == ["rng-time-seed"]

    def test_draw_in_set_loop_triggers(self):
        out = lint("""
            for key in set(pending):
                order.append(rng.random())
        """)
        # the ordering rule independently flags the set-ordered loop
        assert sorted(rule_names(out)) == ["ordering-set-iteration",
                                           "rng-set-iteration"]

    def test_draw_in_set_comprehension_triggers(self):
        out = lint("picks = [rng.choice(vals) for v in {1, 2, 3}]\n")
        assert sorted(rule_names(out)) == ["ordering-set-iteration",
                                           "rng-set-iteration"]

    def test_sorted_set_loop_passes(self):
        assert lint("""
            for key in sorted(set(pending)):
                order.append(rng.random())
        """) == []

    def test_draw_over_list_passes(self):
        assert lint("""
            for key in pending_list:
                order.append(rng.random())
        """) == []


# ------------------------------------------------------------ pickle safety
class TestPickleRules:
    def test_jax_memo_without_getstate_triggers(self):
        out = lint("""
            class Columns:
                def __init__(self):
                    self._jax = None
        """, path="serving/engine.py")
        assert rule_names(out) == ["pickle-device-cache"]

    def test_jax_memo_in_slots_triggers(self):
        out = lint("""
            class Columns:
                __slots__ = ("time_s", "_jax")
        """)
        assert rule_names(out) == ["pickle-device-cache"]

    def test_jax_memo_with_getstate_passes(self):
        assert lint("""
            class Columns:
                def __init__(self):
                    self._jax = None
                def __getstate__(self):
                    return {k: v for k, v in self.__dict__.items()
                            if k != "_jax"}
        """) == []

    def test_plain_attrs_pass(self):
        assert lint("""
            class Columns:
                def __init__(self):
                    self.time_s = []
        """) == []

    def test_state_device_attr_triggers(self):
        out = lint("""
            class _FastState(SearchState):
                def tell(self, observations):
                    self.pop = jnp.zeros((8, 4))
        """)
        assert rule_names(out) == ["pickle-state-device-attr"]

    def test_state_numpy_attr_passes(self):
        assert lint("""
            class _FastState(SearchState):
                def tell(self, observations):
                    self.pop = np.zeros((8, 4))
        """) == []

    def test_state_underscore_device_attr_passes(self):
        # underscore attrs are dropped by SearchState.__getstate__
        assert lint("""
            class _FastState(SearchState):
                def tell(self, observations):
                    self._scratch = jnp.zeros((8, 4))
        """) == []


# ------------------------------------------------------- f64 budget rules
class TestF64Rules:
    def test_cumsum_in_engine_triggers(self):
        out = lint("t = jnp.cumsum(charges)\n",
                   path="core/engine_jax/fast.py")
        assert rule_names(out) == ["f64-parallel-scan"]

    def test_np_cumsum_passes(self):
        # numpy's cumsum is the sequential host reference
        assert lint("t = np.cumsum(charges)\n",
                    path="core/engine_jax/fast.py") == []

    def test_cumsum_outside_engine_passes(self):
        assert lint("t = jnp.cumsum(charges)\n",
                    path="core/methodology.py") == []

    def test_sum_without_dtype_warns(self):
        out = lint("total = jnp.sum(spent)\n",
                   path="core/engine_jax/fast.py")
        assert rule_names(out) == ["f64-sum-dtype"]
        assert out[0].severity == WARNING

    def test_sum_with_dtype_passes(self):
        assert lint("total = jnp.sum(spent, dtype=jnp.float64)\n",
                    path="core/engine_jax/fast.py") == []

    def test_float32_literal_triggers(self):
        out = lint("""
            a = jnp.float32(0.0)
            b = charges.astype(jnp.float32)
            c = jnp.zeros(4, dtype="float32")
        """, path="core/engine_jax/tables2.py")
        assert rule_names(out) == ["f64-float32-literal"] * 3

    def test_float64_and_int32_pass(self):
        assert lint("""
            a = jnp.float64(0.0)
            b = rows.astype(jnp.int32)
        """, path="core/engine_jax/tables2.py") == []


# ---------------------------------------------------- device-sync rule
class TestDeviceSyncRule:
    PATH = "core/engine_jax/fast.py"

    def test_per_element_float_in_loop_triggers(self):
        out = lint("""
            def drain(rows, n):
                out = _replay_jit(rows)
                total = 0.0
                for i in range(n):
                    total += float(out[i])
                return total
        """, path=self.PATH)
        assert rule_names(out) == ["device-sync-in-loop"]
        assert out[0].severity == ERROR

    def test_asarray_per_iteration_triggers(self):
        out = lint("""
            def gather(rows):
                out = jnp.stack(rows)
                vals = []
                for o in out:
                    vals.append(np.asarray(o))
                return vals
        """, path=self.PATH)
        assert rule_names(out) == ["device-sync-in-loop"]

    def test_item_in_comprehension_triggers(self):
        out = lint("""
            def flatten(keys):
                out = jax.random.split(key, 8)
                return [v.item() for v in out]
        """, path=self.PATH)
        assert rule_names(out) == ["device-sync-in-loop"]

    def test_tolist_in_while_triggers(self):
        out = lint("""
            def drain(queue, work):
                mask = jnp.asarray(queue)
                while work:
                    work = submit(work, mask.tolist())
        """, path=self.PATH)
        assert rule_names(out) == ["device-sync-in-loop"]

    def test_convert_where_dispatched_passes(self):
        # the batched-output idiom of campaign._drive_group: dispatch and
        # the one bulk conversion live in the same loop iteration
        assert lint("""
            def drive(runs):
                while runs:
                    out = _replay_vjit(segment(runs))
                    accept = np.asarray(out[0])
                    runs = survivors(runs, accept)
        """, path=self.PATH) == []

    def test_conversion_result_is_host(self):
        # spent is a numpy array after np.asarray — indexing it in the
        # commit loop syncs nothing
        assert lint("""
            def commit(rows, runs):
                out = _replay_vjit(rows)
                spent = np.asarray(out[4])
                for i, run in enumerate(runs):
                    run.spent = float(spent[i])
        """, path=self.PATH) == []

    def test_view_of_conversion_is_host(self):
        # reinterpreting the converted bits (float64 columns travel as
        # int64 bit patterns) is a method of a host array
        assert lint("""
            def commit(rows, runs):
                out = _replay_vjit(rows)
                spent = np.asarray(out[4]).view(np.float64)
                for i, run in enumerate(runs):
                    run.spent = float(spent[i])
        """, path=self.PATH) == []

    @pytest.mark.parametrize("fetch", ["jax.device_get", "tables.device_get"])
    def test_batched_fetch_result_is_host(self, fetch):
        # one batched fetch of the outputs (campaign._drive_group): what it
        # returns is on the host, so the commit loop syncs nothing
        assert lint(f"""
            def commit(rows, runs, tables):
                out = _replay_vjit(rows)
                accept, spent = {fetch}((out[0], out[4]))
                for i, run in enumerate(runs):
                    run.spent = float(spent[i])
        """, path=self.PATH) == []

    @pytest.mark.parametrize("fetch", ["jax.device_get", "tables.device_get"])
    def test_batched_fetch_per_iteration_triggers(self, fetch):
        out = lint(f"""
            def gather(rows, tables):
                out = jnp.stack(rows)
                return [{fetch}(o) for o in out]
        """, path=self.PATH)
        assert rule_names(out) == ["device-sync-in-loop"]

    def test_bulk_conversion_outside_loop_passes(self):
        assert lint("""
            def once(rows):
                out = _replay_jit(rows)
                return np.asarray(out)
        """, path=self.PATH) == []

    def test_for_iterable_is_evaluated_once(self):
        # np.asarray in the iterable position runs once, not per iteration
        assert lint("""
            def walk(rows):
                out = _replay_jit(rows)
                for v in np.asarray(out):
                    consume(v)
        """, path=self.PATH) == []

    def test_numpy_values_pass(self):
        assert lint("""
            def commit(vals, n):
                acc = np.zeros(n)
                total = 0.0
                for i in range(n):
                    total += float(acc[i])
                return total
        """, path=self.PATH) == []

    def test_scope_outside_engine_passes(self):
        assert lint("""
            def drain(rows, n):
                out = _replay_jit(rows)
                return [float(out[i]) for i in range(n)]
        """, path="core/methodology.py") == []


# ------------------------------------------------------- protocol rules
class TestProtocolRules:
    def test_runner_call_in_strategy_triggers(self):
        out = lint("""
            def _optimize(self, space, runner, rng):
                return runner.run_batch(configs)
        """, path="core/strategies/fast_sa.py")
        assert rule_names(out) == ["protocol-runner-call"]

    def test_runner_call_outside_strategies_passes(self):
        assert lint("obs = self.runner.run_batch(configs)\n",
                    path="core/driver.py") == []

    def test_runner_attr_read_passes(self):
        assert lint("best = runner.best\n",
                    path="core/strategies/fast_sa.py") == []

    def test_state_retention_triggers(self):
        out = lint("""
            class _FastState(SearchState):
                def attach_runner(self, runner):
                    self.runner = runner
        """)
        assert rule_names(out) == ["protocol-state-retention"]

    def test_state_retention_underscore_passes(self):
        assert lint("""
            class _FastState(SearchState):
                def attach_runner(self, runner):
                    self._runner = runner
        """) == []

    def test_bind_and_init_pass(self):
        assert lint("""
            class _FastState(SearchState):
                def __init__(self, space, rng):
                    self.space = space
                def bind(self, space):
                    self.space = space
        """) == []


# -------------------------------------------------------- ordering rules
class TestOrderingRules:
    def test_unsorted_listdir_triggers(self):
        out = lint("""
            for name in os.listdir(root):
                shards.append(name)
        """, path="launch/serve.py")
        assert rule_names(out) == ["ordering-listdir"]

    def test_sorted_listdir_passes(self):
        assert lint("""
            for name in sorted(os.listdir(root)):
                shards.append(name)
        """, path="launch/serve.py") == []

    def test_unsorted_path_glob_triggers(self):
        out = lint("paths = list(root.glob('*.jsonl'))\n")
        assert rule_names(out) == ["ordering-listdir"]

    def test_set_loop_in_core_warns(self):
        out = lint("""
            for key in {"a", "b"}:
                journal.append(key)
        """)
        assert rule_names(out) == ["ordering-set-iteration"]
        assert out[0].severity == WARNING

    def test_set_loop_outside_core_passes(self):
        assert lint("""
            for key in {"a", "b"}:
                journal.append(key)
        """, path="models/mlp.py") == []

    def test_sorted_set_loop_passes(self):
        assert lint("""
            for key in sorted({"a", "b"}):
                journal.append(key)
        """) == []

    def test_import_time_environ_assign_triggers(self):
        out = lint("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        """, path="launch/dryrun.py")
        assert rule_names(out) == ["ordering-import-env-mutation"]
        assert out[0].severity == ERROR

    def test_import_time_environ_setdefault_triggers(self):
        out = lint("""
            import os
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
        """, path="models/mlp.py")
        assert rule_names(out) == ["ordering-import-env-mutation"]

    def test_env_mutation_inside_function_passes(self):
        assert lint("""
            import os
            def main():
                os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
                os.environ.setdefault("JAX_PLATFORMS", "cpu")
        """, path="launch/dryrun.py") == []

    def test_import_time_environ_read_passes(self):
        assert lint("""
            import os
            FAST = os.environ.get("REPRO_FAST") == "1"
        """) == []


# ------------------------------------------------ suppressions & baseline
class TestSuppression:
    def test_inline_disable_silences(self):
        out = lint("np.random.shuffle(x)"
                   "  # parity-lint: disable=rng-module-draw\n")
        assert out == []

    def test_disable_all_silences(self):
        out = lint("np.random.shuffle(x)  # parity-lint: disable=all\n")
        assert out == []

    def test_disable_other_rule_does_not_silence(self):
        out = lint("np.random.shuffle(x)"
                   "  # parity-lint: disable=ordering-listdir\n")
        assert sorted(rule_names(out)) == ["rng-module-draw",
                                           UNUSED_SUPPRESSION]

    def test_unused_suppression_flagged(self):
        out = lint("x = 1  # parity-lint: disable=rng-module-draw\n")
        assert rule_names(out) == [UNUSED_SUPPRESSION]
        assert out[0].severity == WARNING

    def test_unused_suppression_not_self_suppressible(self):
        out = lint("x = 1  # parity-lint: disable=unused-suppression\n")
        assert rule_names(out) == [UNUSED_SUPPRESSION]

    def test_syntax_error_is_a_finding(self):
        out = lint("def broken(:\n")
        assert rule_names(out) == ["syntax-error"]
        assert out[0].severity == ERROR


class TestBaseline:
    def _findings(self):
        return lint("np.random.shuffle(x)\nnp.random.shuffle(x)\n")

    def test_baseline_filters_matching_findings(self, tmp_path):
        tree = tmp_path / "core"
        tree.mkdir()
        (tree / "mod.py").write_text("np.random.shuffle(x)\n")
        res = lint_paths([str(tmp_path)])
        assert rule_names(res.findings) == ["rng-module-draw"]
        data = baseline_dict(res.findings,
                             lambda f: "np.random.shuffle(x)")
        bpath = tmp_path / "baseline.json"
        bpath.write_text(json.dumps(data))
        res2 = lint_paths([str(tmp_path)], baseline=str(bpath))
        assert res2.findings == [] and len(res2.baselined) == 1
        assert res2.stale_baseline == []

    def test_baseline_is_count_limited(self):
        findings = self._findings()
        assert len(findings) == 2
        bl = Baseline(baseline_dict(findings[:1],
                                    lambda f: "np.random.shuffle(x)")
                      ["entries"])
        survivors = [f for f in findings
                     if not bl.match(f, "np.random.shuffle(x)")]
        assert len(survivors) == 1  # the second duplicate still gates

    def test_stale_entries_reported(self, tmp_path):
        tree = tmp_path / "core"
        tree.mkdir()
        (tree / "mod.py").write_text("x = 1\n")
        bpath = tmp_path / "baseline.json"
        bpath.write_text(json.dumps(
            {"format": "parity-lint-baseline", "version": 1,
             "entries": [{"rule": "rng-module-draw", "path": "core/mod.py",
                          "context": "np.random.shuffle(x)"}]}))
        res = lint_paths([str(tmp_path)], baseline=str(bpath))
        assert res.findings == []
        assert len(res.stale_baseline) == 1

    def test_malformed_baseline_is_value_error(self, tmp_path):
        bad = tmp_path / "baseline.json"
        bad.write_text("{not json")
        with pytest.raises(ValueError):
            lint_paths([str(tmp_path)], baseline=str(bad))


# ------------------------------------------------------------ report shape
class TestReport:
    def test_json_report_shape(self, tmp_path):
        tree = tmp_path / "core"
        tree.mkdir()
        (tree / "mod.py").write_text("np.random.shuffle(x)\n")
        rules = default_rules()
        data = to_json(lint_paths([str(tmp_path)], rules=rules), rules)
        assert data["format"] == "parity-lint-report"
        assert data["ok"] is False and data["n_errors"] == 1
        assert data["findings"][0]["rule"] == "rng-module-draw"
        catalogued = {r["rule"] for r in data["rules"]}
        assert {"rng-module-draw", "pickle-device-cache",
                "f64-parallel-scan", "protocol-runner-call",
                "ordering-listdir"} <= catalogued
        json.dumps(data)  # round-trippable

    def test_at_least_five_rule_families(self):
        prefixes = {r.name.split("-")[0] for r in default_rules()}
        assert {"rng", "pickle", "f64", "protocol", "ordering"} <= prefixes


# ----------------------------------------------------------------- meta
class TestLiveTree:
    def test_live_tree_clean_modulo_baseline(self):
        res = lint_paths([str(REPO / "src" / "repro")],
                         baseline=str(BASELINE))
        assert res.findings == [], "\n".join(
            f.format() for f in res.findings)

    def test_baseline_has_no_stale_entries(self):
        res = lint_paths([str(REPO / "src" / "repro")],
                         baseline=str(BASELINE))
        assert res.stale_baseline == []
        # the grandfathered findings are exactly the deliberate ones:
        # the free-running tier (strategies.py) and the per-output bulk
        # conversions after a replay dispatch (replay.py / strategies.py)
        assert all(f.path in ("core/engine_jax/strategies.py",
                              "core/engine_jax/replay.py")
                   for f in res.baselined)

    def test_api_entry_point(self):
        from repro import api
        res = api.lint([str(REPO / "src" / "repro")],
                       baseline=str(BASELINE))
        assert res.ok and res.n_files > 50


# ------------------------------------------------------------------- CLI
class TestCli:
    def _tree(self, tmp_path, source="np.random.shuffle(x)\n"):
        tree = tmp_path / "core"
        tree.mkdir()
        (tree / "mod.py").write_text(source)
        return tmp_path

    def test_lint_clean_exit_zero(self, tmp_path, capsys):
        root = self._tree(tmp_path, "x = 1\n")
        assert cli.main(["lint", str(root), "--no-baseline"]) == 0
        assert "clean" in capsys.readouterr().out

    def test_lint_findings_exit_one(self, tmp_path, capsys):
        root = self._tree(tmp_path)
        assert cli.main(["lint", str(root), "--no-baseline"]) == 1
        assert "rng-module-draw" in capsys.readouterr().out

    def test_lint_missing_path_one_line_error(self):
        with pytest.raises(SystemExit) as exc:
            cli.main(["lint", "/no/such/tree"])
        assert "no such path" in str(exc.value.code)

    def test_lint_json_format(self, tmp_path, capsys):
        root = self._tree(tmp_path)
        assert cli.main(["lint", str(root), "--no-baseline",
                         "--format", "json"]) == 1
        data = json.loads(capsys.readouterr().out)
        assert data["n_errors"] == 1

    def test_lint_report_artifact(self, tmp_path):
        root = self._tree(tmp_path)
        report = tmp_path / "lint-report.json"
        cli.main(["lint", str(root), "--no-baseline",
                  "--report", str(report)])
        assert json.loads(report.read_text())["findings"]

    def test_lint_write_baseline_roundtrip(self, tmp_path, capsys):
        root = self._tree(tmp_path)
        bpath = tmp_path / "bl.json"
        assert cli.main(["lint", str(root), "--write-baseline",
                         "--baseline", str(bpath)]) == 0
        capsys.readouterr()
        assert cli.main(["lint", str(root),
                         "--baseline", str(bpath)]) == 0
        assert "1 baselined" in capsys.readouterr().out

    def test_report_missing_journal_one_line(self, tmp_path):
        with pytest.raises(SystemExit) as exc:
            cli.main(["report", str(tmp_path / "none.jsonl")])
        assert "no journal" in str(exc.value.code)

    def test_report_on_directory_one_line(self, tmp_path):
        with pytest.raises(SystemExit) as exc:
            cli.main(["report", str(tmp_path)])
        assert str(exc.value.code).startswith("error:")

    def test_report_malformed_journal_one_line(self, tmp_path):
        bad = tmp_path / "bad.jsonl"
        bad.write_bytes(b"\x00\x01 not a journal")
        with pytest.raises(SystemExit) as exc:
            cli.main(["report", str(bad)])
        assert str(exc.value.code).startswith("error:")

    def test_spaces_missing_cache_one_line(self, tmp_path):
        with pytest.raises(SystemExit) as exc:
            cli.main(["spaces", "--cache",
                      str(tmp_path / "missing.json")])
        assert str(exc.value.code).startswith("error:")

    def test_spaces_malformed_cache_one_line(self, tmp_path):
        bad = tmp_path / "bad.json"
        bad.write_text("definitely not a cache")
        with pytest.raises(SystemExit) as exc:
            cli.main(["spaces", "--cache", str(bad)])
        assert str(exc.value.code).startswith("error:")

    def test_lint_malformed_baseline_one_line(self, tmp_path):
        root = self._tree(tmp_path, "x = 1\n")
        bad = tmp_path / "bl.json"
        bad.write_text("{broken")
        with pytest.raises(SystemExit) as exc:
            cli.main(["lint", str(root), "--baseline", str(bad)])
        assert str(exc.value.code).startswith("error:")
