"""Parallel campaign execution: determinism, journal resume, CLI e2e."""
import json
import os

import numpy as np
import pytest

from repro.cli import main as cli_main
from repro.core import hypertuner
from repro.core.cache import CachedResult, CacheFile
from repro.core.hypertuner import (exhaustive_hypertune,
                                   hyperparam_searchspace, meta_hypertune)
from repro.core.methodology import evaluate_strategy, make_scorer
from repro.core.parallel import (CampaignExecutor, CampaignJournal,
                                 StrategyFactory, report_from_json,
                                 report_to_json)
from repro.core.searchspace import SearchSpace
from repro.core.tunable import tunables_from_dict


def _cache(seed=0):
    rng = np.random.default_rng(seed)
    space = SearchSpace(tunables_from_dict({
        "x": tuple(range(12)), "y": tuple(range(8))}), name="hp")
    results = {}
    for cfg in space.valid_configs:
        x, y = cfg
        v = 1e-3 * (1 + (x - 3) ** 2 + 2 * (y - 6) ** 2
                    + 0.3 * rng.random())
        results[space.config_id(cfg)] = CachedResult("ok", v, (v,) * 2, 0.05)
    return CacheFile("hp", "d", space, results)


def _assert_same_results(a, b):
    assert list(a.results) == list(b.results)
    for key in a.results:
        ra, rb = a.results[key], b.results[key]
        assert ra.score == rb.score  # bit-identical, not approx
        assert np.array_equal(ra.report.curve, rb.report.curve)


# ------------------------------------------------------------- determinism
@pytest.mark.parametrize("backend", ["thread", "process"])
def test_parallel_exhaustive_bit_identical_to_serial(backend):
    scorers = [make_scorer(_cache())]
    serial = exhaustive_hypertune("simulated_annealing", scorers,
                                  repeats=2, seed=0)
    with CampaignExecutor(workers=4, backend=backend) as ex:
        par = exhaustive_hypertune("simulated_annealing", scorers,
                                   repeats=2, seed=0, executor=ex)
    _assert_same_results(serial, par)


def test_parallel_evaluate_strategy_bit_identical():
    scorers = [make_scorer(_cache(0)), make_scorer(_cache(1))]
    scorers[1].cache.kernel = "hp2"  # distinct space names
    factory = StrategyFactory.create("greedy_ils", {"perturbation": 2})
    serial = evaluate_strategy(factory, scorers, repeats=3, seed=0)
    with CampaignExecutor(workers=3, backend="thread") as ex:
        par = evaluate_strategy(factory, scorers, repeats=3, seed=0,
                                executor=ex)
    assert serial.score == par.score
    assert np.array_equal(serial.curve, par.curve)
    assert serial.per_space_score == par.per_space_score


def test_jax_device_arrays_never_pickle():
    """The jax engine memoizes its device-array mirror on ``CacheColumns``
    (``_jax``); a process that unpickles it must re-materialize it against
    its own backend, never inherit device handles — so pickles drop it,
    even mid-campaign, and the compiled space carries none."""
    import pickle

    from repro.core.budget import Budget
    from repro.core.runner import SimulationRunner
    from repro.core.space import RowBatch

    cache = _cache(3)
    runner = SimulationRunner(cache, Budget(max_evals=30), engine="jax")
    # populate the device-table memos
    runner.run_batch(RowBatch(cache.space.compiled,
                              np.arange(20, dtype=np.int64)))
    cols, cs = cache.columns, cache.space.compiled
    assert cols._jax is not None
    assert pickle.loads(pickle.dumps(cols))._jax is None
    for payload in (pickle.dumps(cols), pickle.dumps(cs),
                    pickle.dumps(cache)):
        # no jax/jaxlib types smuggled in (the ``_jax`` attribute *name*
        # legitimately appears; module references must not)
        assert b"jaxlib" not in payload
        assert b"jax._src" not in payload
        assert b"ArrayImpl" not in payload


def test_parallel_jax_scorers_bit_identical_to_serial():
    """engine="jax" scorers handed a process pool keep their device work
    in this process (the pool stays idle), bit-identical to the serial
    run."""
    scorers = [make_scorer(_cache(), engine="jax")]
    factory = StrategyFactory.create("genetic_algorithm", {})
    serial = evaluate_strategy(factory, scorers, repeats=2, seed=0)
    with CampaignExecutor(workers=2, backend="process") as ex:
        par = evaluate_strategy(factory, scorers, repeats=2, seed=0,
                                executor=ex)
    assert serial.score == par.score
    assert np.array_equal(serial.curve, par.curve)


# ----------------------------------------------------------- journal resume
def test_interrupted_campaign_resumes_without_rescoring(tmp_path, monkeypatch):
    scorers = [make_scorer(_cache())]
    path = str(tmp_path / "campaign.jsonl")
    full = exhaustive_hypertune("greedy_ils", scorers, repeats=2, seed=0)
    grid = hyperparam_searchspace("greedy_ils").size

    class Interrupt(Exception):
        pass

    seen = []

    def interrupting_progress(msg):
        seen.append(msg)
        if len(seen) == 3:
            raise Interrupt

    with pytest.raises(Interrupt):
        exhaustive_hypertune("greedy_ils", scorers, repeats=2, seed=0,
                             journal=CampaignJournal(path),
                             progress=interrupting_progress)
    header, records = CampaignJournal(path).read()
    assert header["mode"] == "exhaustive" and len(records) == 3

    calls = []
    real_task = hypertuner.score_hyperconfig_task

    def counting_task(scorers, name, hp, repeats, seed):
        calls.append(hp)
        return real_task(scorers, name, hp, repeats, seed)

    monkeypatch.setattr(hypertuner, "score_hyperconfig_task", counting_task)
    resumed = exhaustive_hypertune("greedy_ils", scorers, repeats=2, seed=0,
                                   journal=CampaignJournal(path))
    assert len(calls) == grid - 3  # completed configs were not re-scored
    _assert_same_results(full, resumed)


def test_journal_tolerates_truncated_tail(tmp_path):
    scorers = [make_scorer(_cache())]
    path = str(tmp_path / "campaign.jsonl")
    exhaustive_hypertune("greedy_ils", scorers, repeats=1, seed=0,
                         journal=CampaignJournal(path))
    with open(path, "a") as f:
        f.write('{"hp_id": "half-written')  # kill -9 mid-append
    journal = CampaignJournal(path)
    header, records = journal.read()
    assert header is not None
    size = hyperparam_searchspace("greedy_ils").size
    assert len(records) == size
    # appending after the torn tail starts a fresh line: the new record is
    # not merged into the fragment, and nothing after it is lost
    journal.append({"hp_id": "post-crash", "score": 1.0,
                    "simulated_seconds": 0.0})
    journal.append({"hp_id": "post-crash-2", "score": 2.0,
                    "simulated_seconds": 0.0})
    _, records = journal.read()
    assert [r["hp_id"] for r in records[-2:]] == ["post-crash",
                                                  "post-crash-2"]
    assert len(records) == size + 2


def test_journal_rejects_mismatched_campaign(tmp_path):
    scorers = [make_scorer(_cache())]
    path = str(tmp_path / "campaign.jsonl")
    exhaustive_hypertune("greedy_ils", scorers, repeats=2, seed=0,
                         journal=CampaignJournal(path))
    with pytest.raises(ValueError, match="different campaign"):
        exhaustive_hypertune("greedy_ils", scorers, repeats=3, seed=0,
                             journal=CampaignJournal(path))


def test_meta_resume_replays_journal(tmp_path, monkeypatch):
    scorers = [make_scorer(_cache())]
    path = str(tmp_path / "meta.jsonl")
    first = meta_hypertune("greedy_ils", "random_search", scorers,
                           extended=False, max_hp_evals=5, repeats=2,
                           seed=0, journal=CampaignJournal(path))
    calls = []
    monkeypatch.setattr(
        hypertuner, "score_hyperconfig",
        lambda *a, **k: calls.append(a) or pytest.fail("re-scored"))
    again = meta_hypertune("greedy_ils", "random_search", scorers,
                           extended=False, max_hp_evals=5, repeats=2,
                           seed=0, journal=CampaignJournal(path))
    assert not calls
    assert again.best_hyperparams == first.best_hyperparams
    assert again.best_score == first.best_score
    assert again.evaluated == first.evaluated


def test_journal_records_wall_clock_bookkeeping(tmp_path):
    """The journal carries what ``repro report`` needs to show wall-clock
    behaviour: per-config worker compute and completion timestamps."""
    scorers = [make_scorer(_cache())]
    path = str(tmp_path / "campaign.jsonl")
    with CampaignExecutor(workers=2, backend="thread") as ex:
        exhaustive_hypertune("greedy_ils", scorers, repeats=2, seed=0,
                             executor=ex, journal=CampaignJournal(path))
    _, records = CampaignJournal(path).read()
    assert records, "journal has completed records"
    assert all(r["report"]["wall_seconds"] >= 0 for r in records)
    walls = [r["done_wall"] for r in records]
    assert walls == sorted(walls)  # appended in completion order
    assert walls[-1] > 0


def test_report_json_roundtrip():
    scorers = [make_scorer(_cache())]
    res = exhaustive_hypertune("greedy_ils", scorers, repeats=1, seed=0)
    rep = res.best.report
    back = report_from_json(json.loads(json.dumps(report_to_json(rep))))
    assert back.score == rep.score
    assert np.array_equal(back.curve, rep.curve)
    assert back.per_space_score == rep.per_space_score


# -------------------------------------------------------------------- CLI
@pytest.fixture
def cache_path(tmp_path):
    p = str(tmp_path / "tiny.t4.json.zst")  # exercises the gzip fallback too
    _cache().save(p)
    return p


def test_cli_simulate(cache_path, capsys):
    assert cli_main(["simulate", "--cache", cache_path, "--strategy", "pso",
                     "--repeats", "2"]) == 0
    out = capsys.readouterr().out
    assert "aggregate score" in out and "hp@d" in out


def test_cli_hypertune_and_report(cache_path, tmp_path, capsys):
    journal = str(tmp_path / "c.jsonl")
    assert cli_main(["hypertune", "--cache", cache_path, "--strategy",
                     "greedy_ils", "--repeats", "2", "--workers", "2",
                     "--journal", journal, "--quiet"]) == 0
    out = capsys.readouterr().out
    assert "optimal vs average config" in out
    # re-run: resumes fully from the journal (instant)
    assert cli_main(["hypertune", "--cache", cache_path, "--strategy",
                     "greedy_ils", "--repeats", "2", "--journal", journal,
                     "--quiet"]) == 0
    capsys.readouterr()
    assert cli_main(["report", journal]) == 0
    out = capsys.readouterr().out
    size = hyperparam_searchspace("greedy_ils").size
    assert f"progress: {size}/{size}" in out


def test_cli_meta(cache_path, tmp_path, capsys):
    journal = str(tmp_path / "m.jsonl")
    assert cli_main(["meta", "--cache", cache_path, "--strategy",
                     "greedy_ils", "--meta-strategy", "random_search",
                     "--max-hp-evals", "4", "--repeats", "2",
                     "--journal", journal, "--quiet"]) == 0
    out = capsys.readouterr().out
    assert "best hyperparameters" in out
    assert cli_main(["report", journal]) == 0
    assert "campaign: meta" in capsys.readouterr().out


# ------------------------------------------------ one process for the chip
@pytest.fixture
def no_worker_processes(monkeypatch):
    """Fail any attempt to start a worker process: a child of a process
    that holds the chip cannot open it."""
    from repro.core import parallel

    def refuse(*_a, **_kw):
        raise AssertionError("started a worker process")
    monkeypatch.setattr(parallel, "ProcessPoolExecutor", refuse)


@pytest.mark.parametrize("backend", ["auto", "process"])
def test_jax_engine_workers_stay_in_process(cache_path, no_worker_processes,
                                            capsys, backend):
    """``--workers 2 --engine jax``: simulate and hypertune grids run as
    fused device campaigns in this process, whatever the pool backend."""
    args = ["--cache", cache_path, "--strategy", "pso", "--engine", "jax",
            "--repeats", "2", "--workers", "2", "--backend", backend]
    assert cli_main(["simulate", *args]) == 0
    assert "drive: device" in capsys.readouterr().out
    assert cli_main(["hypertune", *args, "--quiet"]) == 0
    assert "drive: device" in capsys.readouterr().out


def test_live_record_workers_stay_in_process(tmp_path, no_worker_processes):
    out = str(tmp_path / "hs.json.gz")
    assert cli_main(["record", "--kernel", "hotspot", "--workers", "2",
                     "--max-evals", "2", "--repeats", "1",
                     "--out", out]) == 0
    assert CacheFile.load(out).device == "cpu_interpret"
    with pytest.raises(SystemExit, match="--backend thread"):
        cli_main(["record", "--kernel", "hotspot", "--workers", "2",
                  "--backend", "process", "--max-evals", "2",
                  "--out", str(tmp_path / "x.json.gz")])


def test_compiled_live_record_runs_shards_one_after_another(
        tmp_path, monkeypatch, no_worker_processes):
    """On a TPU each live evaluation times its kernel by the host clock, so
    ``--workers 3`` records its three shards one after another in this
    thread: no kernel ever waits behind another worker's."""
    import threading

    from repro.core import record
    from repro.kernels import gemm
    monkeypatch.setattr(record, "live_device", lambda: ("tpu_v5_lite", False))
    calls, active, peak = [], [0], [0]

    def make_live(_problem, interpret):
        def fn(_conf):
            active[0] += 1
            peak[0] = max(peak[0], active[0])
            calls.append((threading.get_ident(), interpret))
            active[0] -= 1
        return fn
    monkeypatch.setattr(gemm, "make_live", make_live)
    out = str(tmp_path / "g.json.gz")
    assert cli_main(["record", "--kernel", "gemm", "--workers", "3",
                     "--backend", "thread", "--max-evals", "2",
                     "--repeats", "1", "--out", out]) == 0
    assert len(calls) >= 3 and peak[0] == 1
    assert set(calls) == {(threading.get_ident(), False)}
    assert CacheFile.load(out).device == "tpu_v5_lite"
    assert all((tmp_path / f"g.shard-{w:02d}.jsonl").exists()
               for w in range(3))
