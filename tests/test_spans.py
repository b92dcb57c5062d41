"""Program spans (``core.spans``) on the JAX profiler's host plane.

Each test runs its work under a short ``jax.profiler`` session on the CPU
backend and reads the ``.xplane.pb`` it wrote with ``ProfileData``: the
spans land on the host plane, nest, and count the work of their layer
(one ``repro.replay.dispatch`` per replay dispatch, one ``repro.gc`` per
full collection). Outside a session a span is one shared null context,
and no span changes a score.
"""
import gc
import glob
import os
import random
import subprocess
import sys

import jax
import numpy as np
import pytest
from _synth import parity_cache, total_charge

import repro.core.engine_jax as engine_jax
from repro.core import spans
from repro.core.budget import Budget
from repro.core.driver import SearchDriver
from repro.core.engine_jax import campaign
from repro.core.engine_jax.tables import ReplayTables, replay_tables
from repro.core.methodology import evaluate_strategy, make_scorer
from repro.core.parallel import CampaignJournal
from repro.core.runner import LiveRunner, SimulationRunner
from repro.core.searchspace import SearchSpace
from repro.core.space import RowBatch
from repro.core.strategies import get_strategy
from repro.core.tunable import tunables_from_dict

pytestmark = pytest.mark.jax_engine

CACHE = parity_cache()
TOTAL = total_charge(CACHE)
GA = {"popsize": 20, "maxiter": 50, "method": "uniform",
      "mutation_chance": 10}


def _traced(tmp_path, work):
    """Run ``work()`` under a profiler session with automatic collections
    off; returns ``(work's result, the session's repro.* host spans as
    (name, start_ns, end_ns), sorted by start)``."""
    from jax.profiler import ProfileData
    was_enabled = gc.isenabled()
    gc.disable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = work()
    finally:
        jax.profiler.stop_trace()
        if was_enabled:
            gc.enable()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    found = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    assert plane.name == "/host:CPU", plane.name
                    found.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return out, sorted(found, key=lambda ev: ev[1])


def _count(found, name):
    return sum(ev[0] == name for ev in found)


def _ga_driver(seed):
    runner = SimulationRunner(CACHE, Budget(max_seconds=TOTAL * 0.4),
                              engine="jax")
    return SearchDriver(get_strategy("genetic_algorithm", **GA), CACHE.space,
                        runner, random.Random(seed))


def test_span_is_the_null_context_outside_a_session():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert spans.span(spans.SCORE) is spans.span(spans.CAMPAIGN_STEP)
    with spans.span(spans.SCORE):
        pass


def test_spans_leave_jax_unimported():
    """The modules that run without JAX import it neither themselves nor
    through the span helper, and a span there is the null context."""
    code = ("import sys\n"
            "import repro.core.methodology, repro.core.record\n"
            "from repro.core import spans\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n"
            "assert spans.span(spans.SCORE) is spans._NULL\n")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        p for p in sys.path if p))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


def test_named_spans_land_on_the_host_plane_and_nest(tmp_path):
    def work():
        with spans.span(spans.CAMPAIGN_STEP):
            with spans.span(spans.REPLAY_DISPATCH):
                pass
            with spans.span(spans.SCORE):
                pass

    _, found = _traced(tmp_path, work)
    assert [ev[0] for ev in found] == [spans.CAMPAIGN_STEP,
                                       spans.REPLAY_DISPATCH, spans.SCORE]
    outer = found[0]
    for _name, s, e in found[1:]:
        assert outer[1] <= s <= e <= outer[2]
    assert found[1][2] <= found[2][1]


def test_a_fused_campaign_writes_one_dispatch_span_per_replay_dispatch(
        tmp_path, monkeypatch):
    calls = []
    replay = campaign._replay_vjit

    def counted(*args):
        calls.append(1)
        return replay(*args)

    monkeypatch.setattr(campaign, "_replay_vjit", counted)
    # short segments: each run takes several dispatches
    monkeypatch.setattr(campaign, "SEGMENT_ROWS", 40)
    drivers = [_ga_driver(seed) for seed in range(3)]
    _, found = _traced(tmp_path,
                       lambda: engine_jax.drive_fused(drivers))
    assert len(calls) > 1
    assert _count(found, spans.REPLAY_DISPATCH) == len(calls)
    assert _count(found, spans.CAMPAIGN_STEP) >= len(calls)
    assert _count(found, spans.CAMPAIGN_BUILD) == 2  # drive_fused, the group
    # each dispatch follows the stepping that collected its segment
    steps = [ev for ev in found if ev[0] == spans.CAMPAIGN_STEP]
    for _name, s, _e in (ev for ev in found
                         if ev[0] == spans.REPLAY_DISPATCH):
        assert any(e0 <= s for _n, _s0, e0 in steps)


def _fetch_sizes(monkeypatch):
    """Record how many arrays each batched fetch brings back."""
    sizes = []
    fetch = ReplayTables.device_get

    def spy(self, outputs):
        sizes.append(len(outputs))
        return fetch(self, outputs)

    monkeypatch.setattr(ReplayTables, "device_get", spy)
    return sizes


def test_a_fused_dispatch_makes_one_put_and_one_fetch(monkeypatch):
    calls = []
    replay = campaign._replay_vjit

    def counted(*args):
        calls.append(1)
        return replay(*args)

    monkeypatch.setattr(campaign, "_replay_vjit", counted)
    monkeypatch.setattr(campaign, "SEGMENT_ROWS", 40)
    sizes = _fetch_sizes(monkeypatch)
    tables = replay_tables(CACHE.columns, CACHE.space.compiled)
    before = tables.transfers
    engine_jax.drive_fused([_ga_driver(seed) for seed in range(3)])
    assert len(calls) > 1
    assert tables.transfers - before == 2 * len(calls)
    # accept, t_after, spent, evals, exhausted: value and charge are the
    # host's own gathers, never fetched
    assert sizes == [5] * len(calls)


def test_a_per_ask_dispatch_makes_one_put_and_one_fetch(monkeypatch):
    sizes = _fetch_sizes(monkeypatch)
    runner = SimulationRunner(CACHE, Budget(max_seconds=1e9), engine="jax")
    tables = replay_tables(CACHE.columns, CACHE.space.compiled)
    before = tables.transfers
    for rows in ([0], [1, 2, 3], [0], [4, 5]):
        runner.run_batch(RowBatch(CACHE.space.compiled,
                                  np.asarray(rows, dtype=np.int64)))
    dispatches = runner._jax_engine().dispatches
    assert dispatches == 3  # the revisit of row 0 dispatches nothing
    assert tables.transfers - before == 2 * dispatches
    # the Observations need value and charge: all seven outputs
    assert sizes == [7] * dispatches


def test_a_per_ask_replay_writes_one_dispatch_span_per_dispatch(tmp_path):
    runner = SimulationRunner(CACHE, Budget(max_seconds=1e9), engine="jax")

    def work():
        for rows in ([0], [1, 2, 3], [0], [4, 5], [2, 6]):
            runner.run_batch(RowBatch(CACHE.space.compiled,
                                      np.asarray(rows, dtype=np.int64)))

    _, found = _traced(tmp_path, work)
    dispatches = runner._jax_engine().dispatches
    assert dispatches == 4  # the revisit of row 0 dispatches nothing
    assert _count(found, spans.REPLAY_DISPATCH) == dispatches


def test_a_full_collection_writes_one_gc_span(tmp_path):
    _, found = _traced(tmp_path, lambda: gc.collect(2))
    assert _count(found, spans.GC) == 1
    _, found = _traced(tmp_path / "young", lambda: gc.collect(0))
    assert _count(found, spans.GC) == 0


def test_the_gc_hook_is_installed_once():
    assert sum(getattr(cb, "__module__", None) == spans.__name__
               for cb in gc.callbacks) == 1


def test_the_device_drive_scores_the_same_under_a_session(tmp_path):
    scorer = make_scorer(CACHE, engine="jax")

    def score():
        return evaluate_strategy(
            lambda: get_strategy("genetic_algorithm", **GA), [scorer],
            repeats=3, seed=7, drive="device")

    plain = score()
    traced, found = _traced(tmp_path, score)
    assert traced.fuse == "device"
    assert traced.score == plain.score
    assert traced.per_space_score == plain.per_space_score
    assert traced.simulated_seconds == plain.simulated_seconds
    # sample times and baselines, the space's run scores, the aggregation
    assert _count(found, spans.SCORE) == 3
    # the space's drivers, drive_fused's runs, the group's value tables
    assert _count(found, spans.CAMPAIGN_BUILD) == 3


def test_live_evaluations_and_journal_appends_write_their_spans(tmp_path):
    space = SearchSpace(tunables_from_dict({"x": (1, 2, 3)}), name="live")
    runner = LiveRunner(space, lambda d: sum(range(d["x"])),
                        Budget(max_seconds=1e9), repeats=3)
    journal = CampaignJournal(str(tmp_path / "j.jsonl"))

    def work():
        for cfg in space.valid_configs:
            obs = runner.run(cfg)
            journal.append({"value": obs.value})

    _, found = _traced(tmp_path / "trace", work)
    n = len(space.valid_configs)
    assert _count(found, spans.LIVE_FIRST_CALL) == n
    assert _count(found, spans.LIVE_TIMED) == n
    assert _count(found, spans.JOURNAL_APPEND) == n
    with open(journal.path) as f:
        assert len(f.readlines()) == n
