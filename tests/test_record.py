"""Record → merge → replay pipeline: round-trip determinism, shard
merging (idempotence, conflicts, corruption tolerance), partial-cache
error handling, and the CLI end-to-end."""
import math
import os
import random

import pytest

from repro.cli import main as cli_main
from repro.core.budget import Budget
from repro.core.cache import CachedResult, CacheFile
from repro.core.record import (ObservationShard, RecordSpec, RecordingRunner,
                               bruteforce_shard_task, merge_shards,
                               record_shard_task, registry_space, shard_path)
from repro.core.runner import LiveRunner, SimulationRunner
from repro.core.strategies import get_strategy
from repro.kernels import get_kernel


def _record_costmodel(tmp_path, kernel="gemm", workers=2, max_evals=12,
                      strategy="random_search", seed=7):
    """Record a strategy-sampled cost-model run; returns (spec, prefix)."""
    spec = RecordSpec.create(kernel, runner="costmodel", device="tpu_v5e",
                             strategy=strategy, max_evals=max_evals,
                             seed=seed)
    prefix = str(tmp_path / kernel)
    for w in range(workers):
        record_shard_task(spec, w, workers, prefix)
    return spec, prefix


# ------------------------------------------------------ round-trip replay
def test_costmodel_roundtrip_bit_identical(tmp_path):
    """Record with the deterministic cost model, then replay the same seeded
    strategy against the recorded cache: the full trajectory — configs,
    objective values, cumulative simulated time — must match bit-for-bit."""
    kspec = get_kernel("gemm")
    space = kspec.space()
    spec = RecordSpec.create("gemm", runner="costmodel", device="tpu_v5e",
                             max_evals=20, seed=3)
    shard = ObservationShard(str(tmp_path / "g.jsonl"))
    shard.ensure_header(spec.shard_header(space, 0, 1))
    runner = spec.make_runner(space, Budget(max_evals=20))
    rec = RecordingRunner(runner, shard)
    get_strategy("simulated_annealing").run(space, rec, random.Random(11))

    cache = merge_shards([shard.path], space=space)
    sim = SimulationRunner(cache, Budget(max_evals=20))
    get_strategy("simulated_annealing").run(space, sim, random.Random(11))
    assert sim.trace == runner.trace
    assert sim.fresh_evals == runner.fresh_evals


def test_live_pallas_roundtrip_bit_identical(tmp_path):
    """The acceptance contract: live-record a registered Pallas kernel
    (interpret mode), replay through a disk round-trip of the cache, and
    get a bit-identical trajectory."""
    kspec = get_kernel("hotspot")  # smallest smoke space: cheap live evals
    space = kspec.space()
    shard = ObservationShard(str(tmp_path / "h.jsonl"))
    shard.ensure_header(ObservationShard.header(
        "hotspot", "cpu_interpret", space, runner="live", problem={},
        repeats=1))
    live = LiveRunner(space, kspec.make_live(), Budget(max_evals=4),
                      repeats=1)
    rec = RecordingRunner(live, shard)
    get_strategy("random_search").run(space, rec, random.Random(42))
    assert live.fresh_evals == 4

    path = str(tmp_path / "h.json.gz")
    merge_shards([shard.path], space=space).save(path)
    cache = CacheFile.load(path, space=space)
    sim = SimulationRunner(cache, Budget(max_evals=4))
    get_strategy("random_search").run(space, sim, random.Random(42))
    assert sim.trace == live.trace


def test_recording_failed_configs_replay_as_failures(tmp_path):
    """Live runtime failures (here: hotspot's divisibility asserts on
    configs outside the constrained space) are recorded with status 'error'
    and replay as failures with the same charge."""
    kspec = get_kernel("hotspot")
    space = kspec.space()
    bad = space.from_dict({"strip_h": 8, "block_w": 256, "io_dtype": "f32",
                           "t_block": 1, "acc_dtype": "f32",
                           "grid_order": "row"})  # block_w > W: assert fires
    assert not space.is_valid(bad)
    shard = ObservationShard(str(tmp_path / "h.jsonl"))
    shard.ensure_header(ObservationShard.header(
        "hotspot", "cpu_interpret", space, runner="live"))
    live = LiveRunner(space, kspec.make_live(), Budget(max_evals=2),
                      repeats=1)
    obs = RecordingRunner(live, shard).run(bad)
    assert obs.status == "error" and obs.value == math.inf
    cache = merge_shards([shard.path], space=space)
    replay = SimulationRunner(cache, Budget(max_evals=2)).run(bad)
    assert replay.status == "error" and replay.charge_s == obs.charge_s


# -------------------------------------------------------------- resuming
def test_record_resume_preloads_and_extends(tmp_path):
    """Re-running a recording against an existing shard must re-measure
    nothing (preloaded memo) and extend coverage with fresh configs."""
    spec, prefix = _record_costmodel(tmp_path, workers=1, max_evals=5)
    _, first = ObservationShard(shard_path(prefix, 0)).read()
    assert len(first) == 5
    summary = record_shard_task(spec, 0, 1, prefix)  # same seed: resumes
    assert summary["resumed"] == 5
    _, after = ObservationShard(shard_path(prefix, 0)).read()
    # the strategy revisits the 5 preloaded configs for free, then records
    # 5 more fresh ones before the per-run budget fires
    assert len(after) == 10
    assert {k: after[k] for k in first} == first  # originals untouched


# --------------------------------------------------------------- merging
def test_shard_merge_is_idempotent_and_order_independent(tmp_path):
    _, prefix = _record_costmodel(tmp_path, workers=2)
    paths = [shard_path(prefix, w) for w in range(2)]
    once = merge_shards(paths)
    twice = merge_shards(paths + paths)          # duplicates fold away
    reverse = merge_shards(list(reversed(paths)))
    assert once.results == twice.results == reverse.results
    assert once.kernel == "gemm" and once.device == "tpu_v5e"


def test_merge_rejects_conflicting_measurements(tmp_path):
    space = registry_space("gemm", None)
    cfg = space.valid_configs[0]
    cid = space.config_id(cfg)
    header = ObservationShard.header("gemm", "dev", space)
    a = ObservationShard(str(tmp_path / "a.jsonl"))
    b = ObservationShard(str(tmp_path / "b.jsonl"))
    a.ensure_header(header)
    b.ensure_header(header)
    a.append(cid, CachedResult("ok", 1.0, (1.0,), 0.1))
    b.append(cid, CachedResult("ok", 2.0, (2.0,), 0.1))
    with pytest.raises(ValueError, match="disagree"):
        merge_shards([a.path, b.path])


def test_merge_reconciles_live_duplicates_deterministically(tmp_path):
    """Independently-seeded live workers legitimately measure the same
    config with different timings; the merge keeps the lowest worker's
    observation, independent of shard order (idempotent merge)."""
    space = registry_space("gemm", None)
    cid = space.config_id(space.valid_configs[0])
    shards = []
    for w, t in ((0, 1.0), (1, 2.0)):
        s = ObservationShard(str(tmp_path / f"w{w}.jsonl"))
        s.ensure_header(ObservationShard.header(
            "gemm", "cpu_interpret", space, runner="live", problem={},
            repeats=1, worker=w))
        s.append(cid, CachedResult("ok", t, (t,), 0.1))
        shards.append(s.path)
    forward = merge_shards(shards)
    backward = merge_shards(list(reversed(shards)))
    assert forward.results == backward.results
    assert forward.results[cid].time_s == 1.0  # worker 0 wins
    # an equal copy of a shard must not perturb conflict resolution,
    # whichever position it is listed in (rank tracking stays minimal)
    copy = ObservationShard(str(tmp_path / "w1copy.jsonl"))
    copy.ensure_header(ObservationShard.header(
        "gemm", "cpu_interpret", space, runner="live", problem={},
        repeats=1, worker=1))
    copy.append(cid, CachedResult("ok", 2.0, (2.0,), 0.1))
    for order in ([copy.path, shards[1], shards[0]],
                  [shards[1], copy.path, shards[0]],
                  [shards[0], copy.path, shards[1]]):
        assert merge_shards(order).results[cid].time_s == 1.0


def test_merge_rejects_mismatched_problem_sizes(tmp_path):
    """gemm's tunables are problem-size independent, so only the header's
    problem field distinguishes a 128^3 recording from a 256^3 one — they
    must not merge into one cache."""
    space = registry_space("gemm", None)
    a = ObservationShard(str(tmp_path / "a.jsonl"))
    b = ObservationShard(str(tmp_path / "b.jsonl"))
    a.ensure_header(ObservationShard.header(
        "gemm", "cpu_interpret", space, runner="live", problem={"m": 128}))
    b.ensure_header(ObservationShard.header(
        "gemm", "cpu_interpret", space, runner="live", problem={"m": 256}))
    with pytest.raises(ValueError, match="different space or workload"):
        merge_shards([a.path, b.path])


def test_merge_rejects_mismatched_spaces(tmp_path):
    a = ObservationShard(str(tmp_path / "a.jsonl"))
    b = ObservationShard(str(tmp_path / "b.jsonl"))
    a.ensure_header(ObservationShard.header(
        "gemm", "dev", registry_space("gemm", None)))
    b.ensure_header(ObservationShard.header(
        "ssd", "dev", registry_space("ssd", None)))
    with pytest.raises(ValueError, match="different space"):
        merge_shards([a.path, b.path])


def test_corrupted_shard_lines_are_tolerated(tmp_path):
    """A shard torn mid-write (kill -9 during an append) keeps every intact
    record; only the torn line is dropped."""
    _, prefix = _record_costmodel(tmp_path, workers=1, max_evals=6)
    path = shard_path(prefix, 0)
    _, intact = ObservationShard(path).read()
    with open(path, "ab") as f:
        f.write(b'{"id": "torn-mid-wri')  # no newline: a torn append
    header, results = ObservationShard(path).read()
    assert header is not None
    assert results == intact
    assert len(merge_shards([path]).results) == 6
    # a later append lands on a fresh line; the torn fragment stays isolated
    ObservationShard(path).append("9,9,9,x,y",
                                  CachedResult("error", math.inf, (), 0.5))
    _, results = ObservationShard(path).read()
    assert len(results) == 7


def test_merge_rejects_foreign_files(tmp_path):
    foreign = tmp_path / "campaign.jsonl"
    foreign.write_text('{"format": "repro-campaign", "mode": "exhaustive"}\n')
    with pytest.raises(ValueError, match="repro-shard"):
        merge_shards([str(foreign)])
    binary = tmp_path / "noise.bin"
    binary.write_bytes(b"\x00\x01\x02 definitely not json\n")
    with pytest.raises(ValueError, match="repro-shard"):
        merge_shards([str(binary)])


# ------------------------------------------------------------ bruteforce
def test_bruteforce_partition_covers_space_exactly(tmp_path):
    spec = RecordSpec.create("ssd", runner="costmodel", device="tpu_v5e",
                             max_evals=None)
    prefix = str(tmp_path / "ssd")
    for w in range(3):
        bruteforce_shard_task(spec, w, 3, prefix)
    cache = merge_shards([shard_path(prefix, w) for w in range(3)])
    space = registry_space("ssd", None)
    assert len(cache.results) == space.size
    # one worker sequentially produces the identical cache (determinism)
    solo_prefix = str(tmp_path / "ssd_solo")
    bruteforce_shard_task(spec, 0, 1, solo_prefix)
    solo = merge_shards([shard_path(solo_prefix, 0)])
    assert solo.results == cache.results


# ------------------------------------------- partial/empty cache handling
def test_empty_and_all_error_caches_raise_clear_errors():
    space = registry_space("ssd", None)
    empty = CacheFile("ssd", "dev", space, {})
    with pytest.raises(ValueError, match="empty"):
        empty.mean_eval_charge()
    with pytest.raises(ValueError, match="no successful results"):
        empty.optimum
    cid = space.config_id(space.valid_configs[0])
    all_err = CacheFile("ssd", "dev", space,
                        {cid: CachedResult("error", math.inf, (), 0.5)})
    with pytest.raises(ValueError, match="no successful results"):
        all_err.optimum
    assert all_err.mean_eval_charge() == pytest.approx(0.5)
    # a lookup miss against an empty cache surfaces the clear error too
    runner = SimulationRunner(empty, Budget(max_seconds=10))
    with pytest.raises(ValueError, match="empty"):
        runner.run(space.valid_configs[1])


def test_cache_insert_guards_conflicts():
    space = registry_space("ssd", None)
    cache = CacheFile("ssd", "dev", space, {})
    cid = space.config_id(space.valid_configs[0])
    r = CachedResult("ok", 1.0, (1.0,), 0.1)
    cache.insert(cid, r)
    cache.insert(cid, r)  # identical re-insert is fine (idempotent)
    with pytest.raises(ValueError, match="different result"):
        cache.insert(cid, CachedResult("ok", 2.0, (2.0,), 0.1))
    cache.insert(cid, CachedResult("ok", 2.0, (2.0,), 0.1), overwrite=True)
    assert cache.results[cid].time_s == 2.0


# -------------------------------------------------------------------- CLI
def test_cli_record_merge_simulate_end_to_end(tmp_path, capsys):
    out = str(tmp_path / "gemm.json.gz")
    rc = cli_main(["record", "--kernel", "gemm", "--runner", "costmodel",
                   "--device", "tpu_v5e", "--workers", "2", "--backend",
                   "thread", "--max-evals", "10", "--out", out])
    assert rc == 0 and os.path.exists(out)
    merged = str(tmp_path / "remerged.json")
    rc = cli_main(["merge-cache",
                   str(tmp_path / "gemm.shard-00.jsonl"),
                   str(tmp_path / "gemm.shard-01.jsonl"),
                   "--out", merged])
    assert rc == 0
    assert CacheFile.load(merged).results == CacheFile.load(out).results
    rc = cli_main(["simulate", "--strategy", "random_search",
                   "--cache", out, "--repeats", "2"])
    assert rc == 0
    assert "aggregate score" in capsys.readouterr().out


def test_cli_parallel_live_record_with_guaranteed_overlap(tmp_path):
    """Two live workers sampling flash attention's 12-config smoke space at
    7 evals each are guaranteed to overlap; the merge must reconcile the
    noisy duplicate timings instead of failing (regression: parallel live
    recording used to crash at the merge step)."""
    out = str(tmp_path / "fa.json.gz")
    rc = cli_main(["record", "--kernel", "flash_attention", "--workers", "2",
                   "--backend", "thread", "--max-evals", "7", "--repeats",
                   "1", "--out", out])
    assert rc == 0
    cache = CacheFile.load(out)
    space = registry_space("flash_attention", None)
    assert 7 <= len(cache.results) <= space.size == 12


def test_cli_rejects_unknown_kernel(tmp_path):
    with pytest.raises(SystemExit, match="unknown kernel"):
        cli_main(["record", "--kernel", "nope",
                  "--out", str(tmp_path / "x.json")])


# ------------------------------------------------------------ device label
def test_live_spec_is_labelled_with_its_device():
    """A live recording is labelled with the device it runs on (here the
    CPU, in interpret mode); a ``--device`` naming another one is an
    error, while the cost model keeps its device-model default."""
    assert RecordSpec.create("gemm", runner="live").device == "cpu_interpret"
    assert RecordSpec.create("gemm", runner="live",
                             device="cpu_interpret").device == "cpu_interpret"
    with pytest.raises(ValueError, match="runs on 'cpu_interpret'"):
        RecordSpec.create("gemm", runner="live", device="tpu_v5e")
    assert RecordSpec.create("gemm", runner="costmodel").device == "tpu_v5e"
    assert RecordSpec.create("gemm", runner="costmodel",
                             device="tpu_v4").device == "tpu_v4"


def test_live_kernel_with_no_runnable_config_is_a_fault(tmp_path,
                                                       monkeypatch):
    """Compile refusals are ``error`` results, but when every config
    fails the recording fails, naming the first refusal."""
    from repro.kernels import gemm

    def make_live(_problem, _interpret):
        def fn(_conf):
            raise RuntimeError("Mosaic failed to compile TPU kernel\nmore")
        return fn
    monkeypatch.setattr(gemm, "make_live", make_live)
    with pytest.raises(SystemExit, match="no config of gemm ran on "
                       "cpu_interpret .*RuntimeError: Mosaic failed"):
        cli_main(["record", "--kernel", "gemm", "--max-evals", "3",
                  "--repeats", "1", "--out", str(tmp_path / "g.json.gz")])
