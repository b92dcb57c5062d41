"""Cells are found by name from their files; a run on the CPU is refused;
a tiny cell runs end to end with the chip check skipped."""
import json
import os
import subprocess
import sys

import pytest

import cells
import harness
import run as runmod

ROOT = os.path.dirname(harness.HERE)


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    """The tiny cells' files, a work directory of their own, the CPU in
    place of the chip and a peak table entry for it."""
    import jax
    monkeypatch.setattr(harness, "WORK", str(tmp_path / "work"))
    monkeypatch.setattr(harness, "peaks_for", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    root = cells.write_root(tmp_path / "cells")
    return root, jax.devices()[:1]


def test_every_benchmark_cell_finds_its_files_by_name():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for w in bench["workloads"]:
        cell = harness.Cell(bench, w["name"])
        assert hasattr(cell.generator(), "Generator")
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in [m["name"] for m in cell.end_to_end]
        for m in cell.per_layer:
            assert callable(cell.metric_reader(m["name"]).read)
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))


def test_a_cell_added_by_files_and_entries_alone(tiny):
    root, _ = tiny
    cell = cells.cell(root, "tiny-sa")
    assert cell.kind == "campaign" and cell.traffic["strategy"] == \
        "simulated_annealing"
    assert [m["name"] for m in cell.end_to_end] == ["hp_configs_per_s",
                                                     "setup_s"]


def test_the_command_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "record-attn",
         "--seed", str(2 ** 33 + 5), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "needs 1 TPU chip" in out.stderr
    assert out.stdout.strip() == ""


@pytest.mark.parametrize("name", ["tiny-ga", "tiny-sa", "tiny-record"])
def test_a_tiny_cell_runs_and_checks_correct(tiny, name, monkeypatch):
    root, devices = tiny
    kind = cells.cell(root, name).generator()
    if hasattr(kind, "expected_label"):
        monkeypatch.setattr(kind, "expected_label", lambda k: "cpu_interpret")
    cell = cells.cell(root, name)
    monkeypatch.setattr(cell, "generator", lambda: kind)
    result, compared = runmod.measure(cell, 2 ** 40 + 17, 0.5, False,
                                      devices=devices)
    assert result["correct"], compared
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    json.dumps(result)


def test_the_ga_cycle_is_a_balanced_fraction_of_the_grid():
    """Every level of every hyperparameter appears equally often in the
    cycle, and every configuration is a point of the grid."""
    from collections import Counter
    traffic = harness.load_json(os.path.join(
        harness.HERE, "traffic", "table3-ga-grid.json"))
    grid, cycle = traffic["grid"], traffic["hyperparams"]
    assert len({json.dumps(hp, sort_keys=True) for hp in cycle}) == \
        len(cycle) == 12
    for name, levels in grid.items():
        counts = Counter(hp[name] for hp in cycle)
        assert set(counts) == set(levels)
        assert set(counts.values()) == {len(cycle) // len(levels)}


def test_no_campaign_pass_rescores_a_seed(tiny):
    root, _ = tiny
    cell = cells.cell(root, "tiny-ga")
    gen = cell.generator().Generator(cell, 2 ** 40 + 3)
    seeds = []
    gen.setup()
    seeds.append(gen.tuner.seed)
    for _ in range(3):
        assert gen.step() == len(gen.cycle)
        seeds.append(gen.tuner.seed)
    gen.close()
    assert len(set(seeds)) == len(seeds)
    assert len(gen.done) == 3 * len(gen.cycle)


def test_the_compile_clock_tells_a_cache_load_from_a_compile(tmp_path):
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import harness, jax, jax.numpy as jnp\n"
        "clock = harness.CompileClock()\n"
        "f = jax.jit(lambda x: jnp.sin(x) * 3)\n"
        "with clock.phase() as a:\n"
        "    f(jnp.ones(7)).block_until_ready()\n"
        "jax.clear_caches()\n"
        "with clock.phase() as b:\n"
        "    f(jnp.ones(7)).block_until_ready()\n"
        "print(a[2], a[3], b[2], b[3])\n") % harness.HERE
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="true",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    compiled, loaded_first, again, loaded_again = map(
        int, out.stdout.split()[-4:])
    assert compiled >= 1 and loaded_first == 0
    assert again == compiled and loaded_again == again
