"""A run with the timed path broken underneath, or with the reference in
a lower precision in the program's place, comes out not correct. On the
CPU, with the chip check skipped, at the tiny cells' sizes."""
import pytest

import cells
import run as runmod
from test_cells import tiny  # noqa: F401  (fixture)


def _measure(root, devices, name, monkeypatch, control=None):
    cell = cells.cell(root, name)
    kind = cell.generator()
    if hasattr(kind, "expected_label"):
        monkeypatch.setattr(kind, "expected_label", lambda k: "cpu_interpret")
    monkeypatch.setattr(cell, "generator", lambda: kind)
    result, compared = runmod.measure(cell, 2 ** 35 + 3, 0.3, False,
                                      control=control, devices=devices)
    return result, dict((n, (v, lim)) for n, v, lim in compared)


def _perturbed(fn):
    """A replay dispatch whose accounting answer is one unit in the last
    place off: the budget spent it returns (float64 bits)."""
    def broken(*args):
        out = list(fn(*args))
        out[4] = out[4] + 1
        return tuple(out)
    return broken


@pytest.mark.parametrize("name", ["tiny-ga", "tiny-sa"])
def test_a_replay_answer_altered_where_it_is_made(tiny, name,  # noqa: F811
                                                  monkeypatch):
    from repro.core.engine_jax import campaign, replay
    monkeypatch.setattr(campaign, "_replay_vjit",
                        _perturbed(campaign._replay_vjit))
    monkeypatch.setattr(replay, "_replay_jit", _perturbed(replay._replay_jit))
    result, compared = _measure(*tiny, name, monkeypatch)
    assert not result["correct"]
    assert compared["simulated_s_max_abs_gap"][0] > 0


def test_half_the_spaces_left_out_of_the_score(tiny, monkeypatch):  # noqa
    from repro.core import hypertuner
    orig = hypertuner.evaluate_strategy

    def half(make, scorers, **kw):
        return orig(make, scorers[:max(1, len(scorers) // 2)], **kw)
    monkeypatch.setattr(hypertuner, "evaluate_strategy", half)
    monkeypatch.setitem(cells.CAMPAIGN["spaces"], "devices",
                        ["tpu_v5e", "tpu_v4"])
    root, devices = tiny
    cells.write_root(root)
    result, compared = _measure(root, devices, "tiny-ga", monkeypatch)
    assert not result["correct"]
    assert compared["score_max_abs_gap"][0] == float("inf")


def test_a_kernel_output_altered_where_it_is_made(tiny, monkeypatch):  # noqa
    from repro.kernels import flash_attention as fa
    orig = fa.flash_attention
    monkeypatch.setattr(fa, "flash_attention",
                        lambda *a, **kw: orig(*a, **kw).at[0, -1, 0].add(1.0))
    result, compared = _measure(*tiny, "tiny-record", monkeypatch)
    assert not result["correct"]
    assert compared["attn_max_abs_error"][0] > \
        compared["attn_max_abs_error"][1]


def test_half_the_space_left_out_of_the_recording(tiny, monkeypatch):  # noqa
    from repro.core import record
    orig = record.bruteforce_shard_task
    monkeypatch.setattr(record, "bruteforce_shard_task",
                        lambda spec, w, n, prefix: orig(spec, w, 2 * n,
                                                        prefix))
    result, compared = _measure(*tiny, "tiny-record", monkeypatch)
    assert not result["correct"]
    assert compared["recording_faults"][0] > 0


def test_the_campaign_control_fails(tiny, monkeypatch):  # noqa: F811
    result, compared = _measure(*tiny, "tiny-ga", monkeypatch,
                                control="float32")
    assert not result["correct"]
    assert compared["simulated_s_max_abs_gap"][0] > 0


def test_the_record_control_fails(tiny, monkeypatch):  # noqa: F811
    result, compared = _measure(*tiny, "tiny-record", monkeypatch,
                                control="float8_e4m3fn")
    assert not result["correct"]
    assert compared["attn_max_abs_error"][0] > \
        compared["attn_max_abs_error"][1]
