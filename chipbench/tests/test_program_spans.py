"""The split of the window by the program's spans, and the ten metrics
that read it."""
import os

import pytest

import cells
import harness
import program_spans
import run as runmod
import tracing

ROOT = os.path.dirname(harness.HERE)
BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
SPAN_METRICS = {cell: [m["name"] for m in BENCH["per_layer"]
                       if m["source"] == "program_span"
                       and m["name"].endswith(suffix)
                       and m["name"].split(".")[0].endswith("_ms")]
                for cell, suffix in (("hypertune-ga", ".hypertune"),
                                     ("record-attn", ".record"))}


def _host(events):
    return tracing.Trace([{"name": "/host:CPU", "lines": [
        {"name": f"thread {i}", "events": evs}
        for i, evs in enumerate(events)]}])


def _run(trace, lo, hi, units):
    class Run:
        pass
    r = Run()
    r.trace, r.lo, r.hi, r.units = trace, lo, hi, units
    return r


def test_the_innermost_span_takes_each_instant_and_the_parts_add_up():
    main = [("chipbench.window", 0, 1000),
            ("repro.campaign.step", 100, 500),
            ("repro.gc", 200, 300),          # inside stepping: gc
            ("PjitFunction(f)", 350, 450),   # not the program's: ignored
            ("repro.replay.dispatch", 600, 700),
            ("repro.score", 950, 1200)]      # clipped to the window
    other = [("repro.journal.append", 650, 800)]  # another thread
    parts = program_spans.attribute(main + other, 0, 1000)
    assert parts == {"repro.campaign.step": 300, "repro.gc": 100,
                     "repro.replay.dispatch": 50,
                     "repro.journal.append": 150, "repro.score": 50,
                     program_spans.UNATTRIBUTED: 350}
    assert sum(parts.values()) == 1000


def test_equal_starts_go_to_the_shorter_span():
    parts = program_spans.attribute([("repro.a", 0, 10), ("repro.b", 0, 4)],
                                    0, 10)
    assert parts == {"repro.b": 4, "repro.a": 6,
                     program_spans.UNATTRIBUTED: 0}


def test_a_program_without_spans_gives_no_value():
    run = _run(_host([[("chipbench.window", 0, 10),
                       ("PjitFunction(f)", 1, 2)]]), 0, 10, 3)
    assert program_spans.split(run) is None
    for names in SPAN_METRICS.values():
        for name in names:
            cell = harness.Cell(BENCH, "hypertune-ga" if name.endswith(
                ".hypertune") else "record-attn")
            assert cell.metric_reader(name).read(run) is None


@pytest.mark.parametrize("cell_name", sorted(SPAN_METRICS))
def test_a_cells_five_metrics_add_up_to_its_window(cell_name):
    names = SPAN_METRICS[cell_name]
    assert len(names) == 5
    run = _run(_host([[("repro.campaign.step", 0, 4e6),
                       ("repro.gc", 1e6, 2e6),
                       ("repro.live.timed", 5e6, 6e6),
                       ("repro.journal.append", 6e6, 7e6)]]), 0, 1e7, 4)
    cell = harness.Cell(BENCH, cell_name)
    values = {n: cell.metric_reader(n).read(run) for n in names}
    assert all(v is not None and v >= 0 for v in values.values())
    # every span the cell's program writes belongs to one of its metrics
    other = {"hypertune-ga": 2e6, "record-attn": 3e6}[cell_name]
    assert sum(values.values()) == pytest.approx(1e7 / 1e6 / 4 - other / 1e6
                                                 / 4)


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    import jax
    monkeypatch.setattr(harness, "WORK", str(tmp_path / "work"))
    monkeypatch.setattr(harness, "peaks_for", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    return cells.write_root(tmp_path / "cells"), jax.devices()[:1]


@pytest.mark.parametrize("name,real", [("tiny-ga", "hypertune-ga"),
                                       ("tiny-record", "record-attn")])
def test_a_traced_tiny_cell_reports_its_five_span_metrics(tiny, name, real,
                                                          monkeypatch):
    """The program's spans reach the harness's trace on the CPU too, and a
    cell's five metrics add up to its traced window per unit."""
    root, devices = tiny
    bench = dict(cells.BENCH, per_layer=[
        dict(m, workloads=[name]) for m in BENCH["per_layer"]
        if m["name"] in SPAN_METRICS[real]])
    cell = harness.Cell(bench, name, root=root)
    kind = cell.generator()
    if hasattr(kind, "expected_label"):
        monkeypatch.setattr(kind, "expected_label", lambda k: "cpu_interpret")
    monkeypatch.setattr(cell, "generator", lambda: kind)
    result, compared = runmod.measure(cell, 2 ** 40 + 17, 0.5, True,
                                      devices=devices)
    assert result["correct"], compared
    got = result["metrics"]
    assert set(got) == set(SPAN_METRICS[real])
    units = result["attempted"]
    window_ms = 1000 * result["device"]["window_s"] / units
    assert sum(v["value"] for v in got.values()) == pytest.approx(
        window_ms, rel=1e-6)
    unattributed = next(v["value"] for n, v in got.items()
                        if n.startswith("unattributed_ms."))
    assert unattributed < 0.5 * window_ms
