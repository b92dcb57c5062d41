"""The SSD recording cells at a tiny grouped size, on the CPU with the chip
check skipped: both traffic modes of ``live_record_ssd`` end to end, the
float8 control, the roofline count and its reader."""
import json
import os

import pytest

import harness
import run as runmod
import ssd_counts
from test_tracing import _synthetic

RECORD = {
    "kind": "live_record_ssd", "kernel": "ssd",
    "problem": {"bh": 4, "bh_g": 2, "seq": 64, "p": 8, "n": 16},
    "itemsize": 4, "repeats": 1,
    "space": {"tunables": {"chunk": [32, 64, 128],
                           "head_block": [1, 2, 4]},
              "divides": {"seq": ["chunk"]}}}
CHECK = {"sample": 2, "max_abs_error": 1e-4}
TRAFFIC = {"tiny-ssd-sweep": {"mode": "sweep", "check": CHECK},
           "tiny-ssd-cold": {"mode": "cold", "strategy": "random_search",
                             "max_evals": 3, "check": CHECK}}
BENCH = {
    "workloads": [
        {"name": "tiny-record-ssd", "config": "tiny-ssd",
         "traffic": "tiny-ssd-sweep", "chips": 1},
        {"name": "tiny-record-ssd-cold", "config": "tiny-ssd",
         "traffic": "tiny-ssd-cold", "chips": 1}],
    "end_to_end": [
        {"name": "configs_recorded_per_s", "unit": "configs/s"},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": []}
# chunk 32 or 64 over seq 64, head_block 1 or 2 over the 2 heads a group
VALID = {"32,1", "32,2", "64,1", "64,2"}


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    """The tiny SSD cells' files, a work directory of their own, the CPU
    in place of the chip, and the compile-cache setting put back after."""
    import jax
    monkeypatch.setattr(harness, "WORK", str(tmp_path / "work"))
    monkeypatch.setattr(harness, "peaks_for", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    files = {"configs/tiny-ssd.json": RECORD,
             **{f"traffic/{k}.json": v for k, v in TRAFFIC.items()}}
    for rel, body in files.items():
        path = tmp_path / "cells" / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(body))
    was = jax.config.jax_enable_compilation_cache
    yield str(tmp_path / "cells"), jax.devices()[:1]
    jax.config.update("jax_enable_compilation_cache", was)


def _measure(tiny, name, monkeypatch, control=None):
    root, devices = tiny
    cell = harness.Cell(BENCH, name, root=root)
    kind = cell.generator()
    monkeypatch.setattr(kind, "expected_label", lambda k: "cpu_interpret")
    monkeypatch.setattr(cell, "generator", lambda: kind)
    made = []
    orig = kind.Generator.__init__

    def init(self, *a, **kw):
        orig(self, *a, **kw)
        made.append(self)
    monkeypatch.setattr(kind.Generator, "__init__", init)
    result, compared = runmod.measure(cell, 2 ** 40 + 17, 0.3, False,
                                      control=control, devices=devices)
    return result, dict((n, (v, lim)) for n, v, lim in compared), made[0]


def test_the_sweep_records_the_whole_grouped_space(tiny, monkeypatch):
    import jax
    cache_on = jax.config.jax_enable_compilation_cache
    result, compared, gen = _measure(tiny, "tiny-record-ssd", monkeypatch)
    assert result["correct"], compared
    assert set(gen.last.results) == VALID
    assert result["attempted"] == gen.passes * len(VALID)
    assert result["failed"] == 0
    # the smallest program and one more drawn from the seed were compared
    assert (("chunk", 32), ("head_block", 1)) in gen.sample
    assert len(gen.sample) in (2, 3)
    assert compared["ssd_max_abs_error"][0] <= 1e-4
    assert set(result["metrics"]) == {"configs_recorded_per_s", "setup_s"}
    assert jax.config.jax_enable_compilation_cache == cache_on


def test_the_cold_mode_records_its_budget_with_the_cache_off(tiny,
                                                             monkeypatch):
    import jax
    result, compared, gen = _measure(tiny, "tiny-record-ssd-cold",
                                     monkeypatch)
    assert result["correct"], compared
    assert not jax.config.jax_enable_compilation_cache
    # exactly the budget, of valid configurations, at the pass's seed
    assert len(gen.last.results) == 3 and set(gen.last.results) <= VALID
    assert gen.tuner is None and gen.passes >= 1
    assert result["attempted"] == 3 * gen.passes
    assert compared["recording_faults"][0] == 0


def test_the_cold_passes_meet_the_same_programs_in_every_run(tiny,
                                                             monkeypatch):
    """Pass p records at seed 1 + p whatever ``--seed`` is."""
    root, devices = tiny
    cell = harness.Cell(BENCH, "tiny-record-ssd-cold", root=root)
    seen = []
    for seed in (3, 2 ** 35 + 11):
        gen = cell.generator().Generator(cell, seed)
        gen.setup()
        gen.step()
        seen.append((gen.tuner.seed, sorted(gen.last.results)))
        gen.close()
    assert seen[0] == seen[1] and seen[0][0] == 1


def test_the_ssd_control_fails(tiny, monkeypatch):
    result, compared, _ = _measure(tiny, "tiny-record-ssd", monkeypatch,
                                   control="float8_e4m3fn")
    assert not result["correct"]
    assert compared["ssd_max_abs_error"][0] > \
        compared["ssd_max_abs_error"][1]


def test_a_kernel_output_altered_where_it_is_made(tiny, monkeypatch):
    from repro.kernels import ssd
    orig = ssd.ssd_scan
    monkeypatch.setattr(ssd, "ssd_scan",
                        lambda *a, **kw: orig(*a, **kw).at[0, -1, 0].add(1.0))
    result, compared, _ = _measure(tiny, "tiny-record-ssd-cold", monkeypatch)
    assert not result["correct"]
    assert compared["ssd_max_abs_error"][0] > 0.5


def test_a_program_without_the_grouped_space_is_refused_at_setup(
        tiny, monkeypatch):
    from repro.core.searchspace import SearchSpace
    from repro.core.tunable import tunables_from_dict
    from repro.kernels import ssd
    monkeypatch.setattr(ssd, "space", lambda **_: SearchSpace(
        tunables_from_dict({"chunk": (32, 64), "state_block": (32, 64)}),
        name="ssd"))
    with pytest.raises(SystemExit, match="state_block"):
        _measure(tiny, "tiny-record-ssd", monkeypatch)


def test_grouped_scan_counts_at_the_nemotron_h_47b_mixer():
    flops, hbm = ssd_counts.grouped_scan(256, 8, 8192, 64, 256, 4,
                                         (32, 64, 128, 256, 512))
    # bytes: x and y (256 x 8192 x 64), B and C (8 x 8192 x 256), dt, A
    assert hbm == 4 * (2 * 256 * 8192 * 64 + 2 * 8 * 8192 * 256
                       + 256 * 8192 + 256)
    # flops at chunk 32: per chunk and head W.X (2 Q^2 P), C.h and B^T.X
    # (2 Q N P each); C.B^T (2 Q^2 N) once per group
    q = 32
    per_chunk = 256 * (2 * q * q * 64 + 4 * q * 256 * 64) \
        + 8 * 2 * q * q * 256
    assert flops == 8192 // q * per_chunk == 147102629888
    import counts
    t, bound = counts.least_time(flops, hbm, {"bf16_flops_per_s": 197e12,
                                              "hbm_bytes_per_s": 819e9})
    assert bound == "memory" and t == pytest.approx(1.4853e-3, rel=1e-3)


def test_ssd_roofline_reads_calls_against_device_time():
    mod = harness.load_module(os.path.join(
        harness.HERE, "metrics", "ssd_roofline.py"), "ssd_roof")
    tr = _synthetic()
    tr.device_modules["/device:TPU:0"].append(("jit_ssd_scan(3)", 720, 920))
    tr.device_modules["/device:TPU:0"].append(("jit_ssd_scan(4)", 930, 980))

    class Run:
        trace = tr
        lo, hi = 0, 1000
        config = {"problem": {"bh": 2, "bh_g": 1, "seq": 4, "p": 2, "n": 2},
                  "itemsize": 4, "space": {"tunables": {"chunk": [2, 4]}}}
        peaks = {"bf16_flops_per_s": 1e15, "hbm_bytes_per_s": 1e9}
    # bytes 4 x (2 x 2 x 4 x 2 + 2 x 1 x 4 x 2 + 2 x 4 + 2) = 232: 232 ns
    # a call at 1e9 B/s, two calls against 250 ns of device time
    assert mod.read(Run) == pytest.approx(100 * 2 * 232 / 250)
    Run.hi = 700  # no scan inside: nothing to read
    assert mod.read(Run) is None
