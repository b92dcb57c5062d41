"""The reduction from a profiler trace to the per-layer metrics."""
import glob
import os

import pytest

import counts
import tracing


def test_union_merges_overlaps_and_drops_empty_intervals():
    got = tracing.union_ns([(5, 7), (0, 2), (1, 3), (9, 9), (6, 8)])
    assert got == [(0, 3), (5, 8)]
    assert tracing.covered_ns([(0, 2), (1, 3), (5, 8)]) == 6


def test_gaps_are_the_window_less_its_busy_intervals():
    busy = tracing.union_ns([(2, 4), (6, 7)])
    assert tracing.gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert tracing.gaps([], 0, 10) == [(0, 10)]
    assert tracing.gaps([(0, 10)], 2, 8) == []


def test_clip_keeps_only_the_window():
    assert tracing.clip([(0, 5), (8, 12), (20, 30)], 3, 10) == \
        [(3, 5), (8, 10)]


@pytest.mark.parametrize("name,hit", [
    ("jit__replay_segment", True), ("jit_vmap__replay_segment", True),
    ("jit_flash_attention", False), ("REPLAY", True)])
def test_event_names_match_case_insensitive_substrings(name, hit):
    assert tracing.matches(name, ("replay",)) is hit


def _synthetic():
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ("jit__replay_segment(1)", 100, 200),
            ("jit_flash_attention(2)", 300, 700),
            ("jit__replay_segment(1)", 900, 950)]},
        {"name": "XLA Ops", "events": [
            ("fusion.1", 100, 150), ("gather", 150, 200),
            ("tpu_custom_call", 300, 700), ("fusion.1", 900, 950)]}]}
    host = {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [
            ("chipbench.window", 0, 1000),
            ("chipbench.simulate a", 0, 500),
            ("chipbench.simulate b", 500, 1000),
            ("PjitFunction(step)", 720, 890)]}]}
    return tracing.Trace([dev, host])


def test_busy_executions_and_breakdown_of_a_synthetic_trace():
    tr = _synthetic()
    lo, hi = tr.window("chipbench.window")
    assert (lo, hi) == (0, 1000)
    assert tr.busy_ns(lo, hi) == 50 + 50 + 400 + 50
    reps = tr.executions(("replay",), lo, hi)
    assert [e[1] for e in reps] == [100, 900]
    assert tr.executions(("replay",), 150, hi) == [reps[1]]
    top = tr.top_ops(lo, hi)
    assert top[0] == ["tpu_custom_call", 400 / 1e9]
    assert top[1] == ["fusion.1", 100 / 1e9]
    gaps = tr.idle_gaps(lo, hi)
    assert gaps[0] == ["chipbench.simulate b > PjitFunction(step)",
                       200 / 1e9]
    assert sorted(g[1] for g in gaps) == sorted(
        x / 1e9 for x in (100, 100, 200, 50))


@pytest.mark.parametrize("python_tracer_level", [0, 1])
def test_a_cpu_trace_holds_the_harness_spans(tmp_path, python_tracer_level):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = python_tracer_level
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        with jax.profiler.TraceAnnotation("chipbench.window"):
            for _ in range(3):
                f(x).block_until_ready()
    tr = tracing.Trace.load(str(tmp_path))
    lo, hi = tr.window("chipbench.window")
    assert hi > lo
    # the CPU backend writes no device plane: nothing to read, not zero
    assert tr.n_devices == 0 and tr.idle_gaps(lo, hi) == []


def test_causal_attention_counts_at_the_mistral_7b_layer():
    flops, hbm = counts.causal_attention(32, 8, 4096, 128, 4)
    assert flops == 4 * 32 * 4096 ** 2 * 128 / 2 == 137438953472.0
    assert hbm == 4 * 4096 * 128 * (2 * 32 + 2 * 8)
    t, bound = counts.least_time(flops, hbm, {"bf16_flops_per_s": 197e12,
                                              "hbm_bytes_per_s": 819e9})
    assert bound == "compute" and t == pytest.approx(0.6977e-3, rel=1e-3)
    t, bound = counts.least_time(1.0, 1e9, {"bf16_flops_per_s": 197e12,
                                            "hbm_bytes_per_s": 819e9})
    assert bound == "memory"


def test_roofline_reader_counts_calls_against_device_time():
    import harness
    mod = harness.load_module(os.path.join(
        harness.HERE, "metrics", "flash_attention_roofline.py"), "roof")

    class Run:
        trace = _synthetic()
        lo, hi = 0, 1000
        config = {"problem": {"bh": 1, "bh_kv": 1, "seq": 2, "d": 2},
                  "itemsize": 4}
        peaks = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e12}
    # one call of 16 flops at 1e9 flop/s is 16 ns against 400 ns
    assert mod.read(Run) == pytest.approx(100 * 16 / 400)
    Run.hi = 250  # no attention call inside: nothing to read
    assert mod.read(Run) is None


def test_peaks_know_the_v5e_and_refuse_an_unknown_kind():
    import harness
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        harness.peaks_for("TPU v9 imaginary")
