"""Roofline analysis: collective parser, trip-count scaling, analytic-cost
validation against XLA cost_analysis on trip-count-1 configurations."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import SHAPES, ShapeConfig, get_config
from repro.roofline.analysis import (_max_element_bytes, analytic_cost,
                                     model_flops, parse_collectives,
                                     roofline)

SYNTH_HLO = """
HloModule test

%loop_body (p: (s32[], f32[128,256])) -> (s32[], f32[128,256]) {
  %p = (s32[], f32[128,256]) parameter(0)
  %ar = f32[128,256]{1,0} all-reduce(%gte), replica_groups={{0,1,2,3}}, to_apply=%add
  ROOT %t = (s32[], f32[128,256]) tuple(%c, %ar)
}

%loop_cond (p: (s32[], f32[128,256])) -> pred[] {
  %p2 = (s32[], f32[128,256]) parameter(0)
  %bound = s32[] constant(10)
  ROOT %cmp = pred[] compare(%i, %bound), direction=LT
}

ENTRY %main (a: f32[128,256]) -> f32[128,256] {
  %a = f32[128,256] parameter(0)
  %ag = f32[512,256]{1,0} all-gather(%a), replica_groups=[2,2]<=[4], dimensions={0}
  %w = (s32[], f32[128,256]) while(%tup), condition=%loop_cond, body=%loop_body
  ROOT %out = f32[128,256] get-tuple-element(%w), index=1
}
"""


def test_parser_counts_and_trip_scales():
    summary = parse_collectives(SYNTH_HLO, n_chips=4)
    # the all-reduce inside the 10-iteration loop counts 10 times
    assert summary.counts["all-reduce"] == 10
    assert summary.counts["all-gather"] == 1
    ar_payload = 128 * 256 * 4
    expected_ar = 2 * (4 - 1) / 4 * ar_payload * 4 * 10
    assert summary.wire_bytes["all-reduce"] == pytest.approx(expected_ar)
    ag_payload = 512 * 256 * 4
    expected_ag = (2 - 1) / 2 * ag_payload * 4  # iota groups of 2
    assert summary.wire_bytes["all-gather"] == pytest.approx(expected_ag)


# one ENTRY computation exercising every collective op the ring model
# prices, with explicit replica groups of 4 on 8 chips
ALL_OPS_HLO = """
HloModule ops

ENTRY %main (a: f32[128,256]) -> f32[128,256] {
  %a = f32[128,256] parameter(0)
  %ar = f32[128,256]{1,0} all-reduce(%a), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add
  %ag = f32[512,256]{1,0} all-gather(%ar), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
  %rs = f32[32,256]{1,0} reduce-scatter(%ar), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}, to_apply=%add
  %aa = f32[128,256]{1,0} all-to-all(%ar), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
  %cp = f32[128,256]{1,0} collective-permute(%ar), source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
  ROOT %out = f32[128,256] add(%ar, %cp)
}
"""

# async -start variants print a tuple type (operand, result [, scratch]);
# the payload is the largest tuple element
START_HLO = """
HloModule starts

ENTRY %main (a: f32[128,256]) -> f32[512,256] {
  %a = f32[128,256] parameter(0)
  %ars = (f32[128,256], f32[128,256]) all-reduce-start(%a), replica_groups={{0,1}}, to_apply=%add
  %ard = f32[128,256] all-reduce-done(%ars)
  %ags = (f32[128,256], f32[512,256]) all-gather-start(%ard), replica_groups=[2,4]<=[8], dimensions={0}
  ROOT %agd = f32[512,256] all-gather-done(%ags)
}
"""


def test_parse_collectives_every_op_ring_model():
    s = parse_collectives(ALL_OPS_HLO, n_chips=8)
    assert s.counts == {"all-reduce": 1, "all-gather": 1,
                        "reduce-scatter": 1, "all-to-all": 1,
                        "collective-permute": 1}
    elt = 256 * 4  # f32 row
    # explicit groups of g=4; wire bytes aggregate across all 8 chips
    assert s.wire_bytes["all-reduce"] == pytest.approx(
        2 * 3 / 4 * 128 * elt * 8)
    # all-gather payload is the gathered (output) shape
    assert s.wire_bytes["all-gather"] == pytest.approx(
        3 / 4 * 512 * elt * 8)
    # reduce-scatter payload is its (scattered) result shape
    assert s.wire_bytes["reduce-scatter"] == pytest.approx(
        3 / 4 * 32 * elt * 8)
    assert s.wire_bytes["all-to-all"] == pytest.approx(
        3 / 4 * 128 * elt * 8)
    # permute: one hop, full payload, group size irrelevant
    assert s.wire_bytes["collective-permute"] == pytest.approx(
        128 * elt * 8)
    assert s.total_wire_bytes == pytest.approx(sum(s.wire_bytes.values()))


def test_parse_collectives_start_variants_and_iota_groups():
    s = parse_collectives(START_HLO, n_chips=8)
    # -start ops count under the base op name; -done ops don't double count
    assert s.counts == {"all-reduce": 1, "all-gather": 1}
    elt = 256 * 4
    # tuple type: payload is the largest element (here equal halves)
    assert s.wire_bytes["all-reduce"] == pytest.approx(
        2 * 1 / 2 * 128 * elt * 8)
    # iota replica_groups=[2,4]<=[8] means 2 groups of 4 => g=4;
    # payload is the larger tuple element (the gathered output)
    assert s.wire_bytes["all-gather"] == pytest.approx(
        3 / 4 * 512 * elt * 8)


def test_parse_collectives_defaults_group_to_world():
    # no replica_groups printed at all: the group is all n_chips
    hlo = """
HloModule w

ENTRY %main (a: bf16[64]) -> bf16[64] {
  %a = bf16[64] parameter(0)
  ROOT %ar = bf16[64]{0} all-reduce(%a), to_apply=%add
}
"""
    s = parse_collectives(hlo, n_chips=4)
    assert s.wire_bytes["all-reduce"] == pytest.approx(
        2 * 3 / 4 * 64 * 2 * 4)


def test_max_element_bytes_dtype_table():
    cases = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
             "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "f8e4m3": 1,
             "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1}
    for dt, nbytes in cases.items():
        assert _max_element_bytes(f"{dt}[16,8]") == 16 * 8 * nbytes, dt
    # scalars have one element; unknown dtypes fall back to 4 bytes
    assert _max_element_bytes("s32[]") == 4
    assert _max_element_bytes("c64[8]") == 8 * 4
    # tuples: the largest element wins
    assert _max_element_bytes("(f32[8], bf16[128,64])") == 128 * 64 * 2
    assert _max_element_bytes("") == 0.0


def test_roofline_dominant_term():
    r = roofline(1e12, 1e9, 1e12, 256, mflops=2.56e14)
    assert r.dominant == "collective"
    assert r.useful_ratio == pytest.approx(1.0)


def test_analytic_cost_matches_xla_on_trip_count_one():
    """With L=1, one KV block and one microbatch every scan has trip count 1,
    so XLA's cost_analysis is exact — the analytic model must agree on FLOPs
    within 25 % (it approximates elementwise/softmax work)."""
    from repro.launch.dryrun import lower_cell
    from repro.launch.mesh import make_host_mesh

    base = get_config("olmo-1b")
    cfg = dataclasses.replace(base, name="olmo-probe", n_layers=1,
                              vocab=4096)
    shape = ShapeConfig("probe", seq_len=512, global_batch=4, kind="train")
    mesh = make_host_mesh()
    compiled = lower_cell(cfg, shape, mesh, remat="none").compile()
    xla_flops = float(compiled.cost_analysis()["flops"])
    ours, _ = analytic_cost(cfg, shape, remat="none", n_chips=1)
    assert ours == pytest.approx(xla_flops, rel=0.25)


def test_model_flops_moe_uses_active_params():
    grok = get_config("grok-1-314b")
    mf = model_flops(grok, SHAPES["train_4k"])
    tokens = SHAPES["train_4k"].global_batch * SHAPES["train_4k"].seq_len
    # dominated by 6·N_active·T, plus attention
    assert mf > 6 * grok.active_param_count() * tokens * 0.9
    assert mf < 6 * grok.param_count() * tokens


def test_useful_ratio_bounded_for_all_cells():
    from repro.configs import ARCHS, cell_supported
    for cfg in ARCHS.values():
        for shape in SHAPES.values():
            if not cell_supported(cfg, shape)[0]:
                continue
            mf = model_flops(cfg, shape)
            af, ab = analytic_cost(cfg, shape, "full", 1)
            assert 0.0 < mf / af <= 1.02, (cfg.name, shape.name, mf / af)
            assert ab > 0
