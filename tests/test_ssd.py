"""The grouped Mamba-2 SSD scan: B and C per group, ``head_block`` heads
of one group per grid step. Pallas in interpret mode against the
sequential oracle, the oracle against a NumPy loop, the search space, the
live objective and the cost model's pricing of a head block."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import get_kernel, ssd

BH, L, P, N = 4, 128, 16, 32


def _case(bh, groups, head_block, p, chunk, seq, name):
    return pytest.param(bh, groups, head_block, p, chunk, seq,
                        id=f"{name}-{chunk}")


# (G, head_block) with head_block dividing the BH / G heads of a group
GROUPINGS = [(1, 1), (1, 2), (1, 4), (BH // 2, 1), (BH // 2, 2), (BH, 1)]
# at the published head dim P = 64 the kernel's head loop takes two heads
# a pass, their states stacked; a block of all 8 heads of a group takes
# four passes
CASES = ([_case(BH, g, hb, P, chunk, L, f"{g}-{hb}")
          for g, hb in GROUPINGS for chunk in (32, 64)]
         + [_case(4, 1, hb, 64, chunk, L, f"p64-1-{hb}")
            for hb in (2, 4) for chunk in (32, 64)]
         + [_case(8, 1, 8, 64, chunk, 512, "p64-1-8")
            for chunk in (64, 128, 256)])


@pytest.mark.parametrize("bh,groups,head_block,p,chunk,seq", CASES)
def test_grouped_scan_matches_the_grouped_oracle(bh, groups, head_block, p,
                                                 chunk, seq):
    x, dt, a, b, c = ssd.live_inputs(11, bh, groups, seq, p, N)
    assert b.shape == c.shape == (groups, seq, N)
    out = ssd.ssd_scan(x, dt, a, b, c, chunk=chunk, head_block=head_block,
                       interpret=True)
    ref = ssd.ssd_ref(x, dt, a, b, c)
    # float32 on both sides, in interpret mode; the chunked form sums the
    # same products in another order (prefix sums of dt·A, then
    # exp(cum_i - cum_j)) and |y| reaches about 5: a few float32 ulps of
    # that, far below an error of any one term
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_heads_of_a_group_read_its_b_and_c():
    """Changing group 1's B moves only the heads of group 1."""
    x, dt, a, b, c = ssd.live_inputs(5, BH, 2, L, P, N)
    base = ssd.ssd_scan(x, dt, a, b, c, chunk=64, head_block=2,
                        interpret=True)
    moved = ssd.ssd_scan(x, dt, a, b.at[1].multiply(-1.0), c, chunk=64,
                         head_block=2, interpret=True)
    same = np.all(np.asarray(base) == np.asarray(moved), axis=(1, 2))
    assert same.tolist() == [True, True, False, False]


def _numpy_recurrence(x, dt, a, b, c):
    x, dt, a, b, c = (np.asarray(v, np.float64) for v in (x, dt, a, b, c))
    bh, l, p = x.shape
    g = b.shape[0]
    y = np.zeros_like(x)
    for h in range(bh):
        grp = h // (bh // g)
        state = np.zeros((b.shape[-1], p))
        for t in range(l):
            state = (np.exp(dt[h, t] * a[h]) * state
                     + dt[h, t] * np.outer(b[grp, t], x[h, t]))
            y[h, t] = c[grp, t] @ state
    return y


@pytest.mark.parametrize("groups", [1, 2, 4])
def test_grouped_oracle_matches_a_numpy_loop(groups):
    x, dt, a, b, c = ssd.live_inputs(3, BH, groups, 48, 8, 16)
    got = np.asarray(ssd.ssd_ref(x, dt, a, b, c))
    # float32 scan against a float64 loop: rounding of 48 steps
    np.testing.assert_allclose(got, _numpy_recurrence(x, dt, a, b, c),
                               rtol=1e-5, atol=1e-5)


def test_space_holds_only_tunables_a_program_reads():
    space = ssd.space(seq=8192, bh=256, bh_g=8)
    assert [t.name for t in space.tunables] == ["chunk", "head_block"]
    configs = [space.as_dict(c) for c in space.valid_configs]
    assert len(configs) == 30
    programs = {(c["chunk"], c["head_block"]) for c in configs}
    assert len(programs) == len(configs)
    # 6 heads a group: head blocks 4, 8, 16 and 32 do not divide it
    narrow = ssd.space(seq=256, bh=12, bh_g=2)
    blocks = {narrow.as_dict(c)["head_block"] for c in narrow.valid_configs}
    assert blocks == {1, 2}
    chunks = {narrow.as_dict(c)["chunk"] for c in narrow.valid_configs}
    assert chunks == {32, 64, 128, 256}


def test_kernel_spec_passes_the_groups_to_the_space():
    spec = get_kernel("ssd")
    space = spec.space({"bh": 256, "bh_g": 8, "seq": 8192, "p": 64,
                        "n": 256})
    assert space.size == 30 and len(space.valid_configs) == 30
    smoke = spec.space()
    assert {smoke.as_dict(c)["head_block"]
            for c in smoke.valid_configs} == {1, 2, 4}


def test_make_live_builds_grouped_inputs_from_the_problem(monkeypatch):
    seen = {}

    def scan(x, dt, a, b, c, **kw):
        seen.update(x=x, dt=dt, a=a, b=b, kw=kw)
        return jnp.zeros(1)
    monkeypatch.setattr(ssd, "ssd_scan", scan)
    problem = {"bh": 8, "bh_g": 2, "seq": 64, "p": 8, "n": 16, "seed": 4}
    ssd.make_live(problem, interpret=True)({"chunk": 32, "head_block": 2})
    assert seen["x"].shape == (8, 64, 8) and seen["b"].shape == (2, 64, 16)
    assert seen["kw"] == {"chunk": 32, "head_block": 2, "interpret": True}
    dt, a = np.asarray(seen["dt"]), np.asarray(seen["a"])
    assert 0.001 <= dt.min() and dt.max() <= 0.1
    assert -16.0 <= a.min() and a.max() <= -1.0
    ref = ssd.live_inputs(4, 8, 2, 64, 8, 16)
    assert np.array_equal(np.asarray(seen["b"]), np.asarray(ref[3]))


def test_live_bruteforce_recording_holds_every_grouped_config(tmp_path):
    from repro.api import Tuner
    problem = {"bh": 4, "bh_g": 2, "seq": 64, "p": 8, "n": 16}
    with Tuner(seed=0) as tuner:
        run = tuner.record("ssd", runner="live", problem=problem, repeats=1,
                           max_evals=None, bruteforce=True,
                           out=str(tmp_path / "ssd.json.gz"))
    space = get_kernel("ssd").space(problem)
    assert set(run.cache.results) == {space.config_id(c)
                                      for c in space.valid_configs}
    assert set(run.cache.results) == {"32,1", "32,2", "64,1", "64,2"}
    assert all(r.status == "ok" and r.time_s > 0
               for r in run.cache.results.values())
    assert run.cache.device == "cpu_interpret"


def test_workload_reads_b_and_c_once_per_head_block():
    wl = ssd.workload(bh=256, bh_g=8, seq=8192, p=64, n=256)
    from repro.core.devices import DEVICES_BY_NAME
    dev = DEVICES_BY_NAME["tpu_v5e"]
    conf = {"chunk": 128}
    hbm = [wl.hbm_bytes({**conf, "head_block": hb}, dev)
           for hb in (1, 2, 4, 8, 16, 32)]
    assert all(x > y for x, y in zip(hbm, hbm[1:]))
    # at 32 heads a block, B and C are read once per group: the least bytes
    assert hbm[-1] == 4 * 8192 * (2 * 256 * 64 + 256 + 2 * 8 * 256)
    flops = [wl.flops({**conf, "head_block": hb}) for hb in (1, 32)]
    assert flops[0] - flops[1] == 8192 // 128 * (256 - 8) * 2 * 128 ** 2 * 256
    vmem = [wl.vmem_bytes({**conf, "head_block": hb}) for hb in (1, 32)]
    assert vmem[0] < vmem[1]
    assert wl.grid_size({**conf, "head_block": 32}) == 8 * 8192 // 128
