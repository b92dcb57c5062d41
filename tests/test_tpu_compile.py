"""Compile the chip's main path for a TPU v5e that is described, not attached.

The TPU compiler is installed with JAX, so every kernel of the registry
and the fused campaign's replay kernel compile here, with
``interpret=False``, at the sizes the chip runs: the hub problems and a
Table III campaign segment. Interpret mode hides what this shows — blocks
off the (8, 128) tiling, primitives Mosaic cannot lower, VMEM overflow.
Nothing runs, so nothing here is a time or a result.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.engine_jax.replay import _replay_vjit
from repro.kernels import convolution as cv
from repro.kernels import dedispersion as dd
from repro.kernels import flash_attention as fa
from repro.kernels import gemm as gm
from repro.kernels import hotspot as hs
from repro.kernels import ssd


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


F32, BF16 = jnp.float32, jnp.bfloat16
N = gm.HUB_M  # 4096: the hub's GEMM/stencil edge and the attention length

# (kernel config, wrapper, argument shapes) at hub / real-model sizes
CASES = {
    "gemm-128/128/128": (
        lambda a, b, c: gm.gemm(a, b, c, block_m=128, block_n=128,
                                block_k=128),
        [((N, N), BF16)] * 3),
    "gemm-512/1024/512": (
        lambda a, b, c: gm.gemm(a, b, c, block_m=512, block_n=1024,
                                block_k=512),
        [((N, N), BF16)] * 3),
    "flash_attention-gqa-512/1024": (
        lambda q, k, v: fa.flash_attention(q, k, v, block_q=512,
                                           block_kv=1024),
        [((32, N, 128), F32), ((8, N, 128), F32), ((8, N, 128), F32)]),
    "hotspot-64/512/2": (
        lambda t, p: hs.hotspot(t, p, strip_h=64, block_w=512, t_block=2),
        [((N, N), F32)] * 2),
    "convolution-16/256": (
        lambda x, f: cv.conv2d(x, f, strip_h=16, block_w=256),
        [((N, N), F32), ((cv.HUB_FH, cv.HUB_FW), F32)]),
    "ssd-64": (
        lambda x, dt, a, b, c: ssd.ssd_scan(x, dt, a, b, c, chunk=64),
        [((192, N, 64), F32), ((192, N), F32), ((192,), F32),
         ((192, N, 64), F32), ((192, N, 64), F32)]),
    "ssd-256": (
        lambda x, dt, a, b, c: ssd.ssd_scan(x, dt, a, b, c, chunk=256),
        [((192, N, 64), F32), ((192, N), F32), ((192,), F32),
         ((192, N, 64), F32), ((192, N, 64), F32)]),
    # Nemotron-H-47B's mixer: 256 heads, B/C in 8 groups, state 256, seq
    # 8192; the smallest and the largest blocks of the recorded space, and
    # its fastest program, whose head loops pass several heads at once
    "ssd-grouped-32/1": (
        lambda x, dt, a, b, c: ssd.ssd_scan(x, dt, a, b, c, chunk=32,
                                            head_block=1),
        [((256, 8192, 64), F32), ((256, 8192), F32), ((256,), F32),
         ((8, 8192, 256), F32), ((8, 8192, 256), F32)]),
    "ssd-grouped-128/32": (
        lambda x, dt, a, b, c: ssd.ssd_scan(x, dt, a, b, c, chunk=128,
                                            head_block=32),
        [((256, 8192, 64), F32), ((256, 8192), F32), ((256,), F32),
         ((8, 8192, 256), F32), ((8, 8192, 256), F32)]),
    "ssd-grouped-512/32": (
        lambda x, dt, a, b, c: ssd.ssd_scan(x, dt, a, b, c, chunk=512,
                                            head_block=32),
        [((256, 8192, 64), F32), ((256, 8192), F32), ((256,), F32),
         ((8, 8192, 256), F32), ((8, 8192, 256), F32)]),
    "dedispersion-16/256": (
        lambda x, d: dd.dedisperse(x, d, block_dm=16, block_t=256),
        [((dd.HUB_NCHAN, dd.HUB_NTIME), F32),
         ((dd.HUB_NCHAN, dd.HUB_NDM), jnp.int32)]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]
    compiled = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in compiled.as_text()


def test_vmem_overflow_is_refused_for_v5e(one_chip):
    """A tile set whose blocks exceed the scoped VMEM is a real constraint
    of the GEMM space: the compiler refuses it, and a live recording
    stores it as an ``error`` result."""
    with pytest.raises(Exception, match="vmem"):
        _compile(lambda a, b, c: gm.gemm(a, b, c, block_m=512, block_n=1024,
                                         block_k=2048),
                 one_chip, *[((N, N), BF16)] * 3)


def test_replay_vjit_compiles_for_v5e(one_chip):
    """The fused campaign's budget scan at one Table III dispatch: 25 runs
    x 1024 rows over the 10,140-row GEMM table, the rows as int32, as
    ``campaign._drive_group`` sends them. Its float64 columns are int64
    bit patterns, added in integer arithmetic: nothing in the compiled
    program may be a float64, which the TPU would split into a pair of
    float32."""
    runs, rows, table = 25, 1024, 10_140
    with jax.enable_x64():
        compiled = _compile(
            _replay_vjit, one_chip,
            ((runs, rows), jnp.int32), ((runs, rows), jnp.bool_),
            ((table,), jnp.int32), ((table,), jnp.int64),
            ((table,), jnp.int64), ((), jnp.int64),
            ((runs,), jnp.int64), ((runs,), jnp.int64),
            ((runs,), jnp.int64), ((runs,), jnp.int64))
    assert compiled.memory_analysis() is not None
    assert "f64[" not in compiled.as_text()


@pytest.mark.parametrize("strategy", ["genetic_algorithm", "pso",
                                      "differential_evolution",
                                      "random_search"])
def test_free_run_compiles_for_v5e(one_chip, strategy):
    """A free-running campaign in one dispatch: 32 runs x 100 generations
    over the hub GEMM space. Its budget and its best value are int64 bit
    patterns, as in the replay kernel, and no int64 dot is left (the TPU
    has none)."""
    from repro.core.engine_jax.strategies import (FREE_RUN_STRATEGIES,
                                                  _free_run_jit)
    from repro.core.engine_jax.tables import space_tables
    impl = FREE_RUN_STRATEGIES[strategy]
    hp = impl.defaults
    space = gm.space().compiled
    st = space_tables(space)
    runs, rows = 32, space.n_valid

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    with jax.enable_x64():
        compiled = _free_run_jit.lower(
            impl, int(hp["popsize"]), 100,
            tuple(sorted(hp.items())), st.cards,
            arg((runs, 2), jnp.uint32), arg((rows,), jnp.int32),
            arg((rows,), jnp.int64), arg((rows,), jnp.int64),
            *[arg(a.shape, a.dtype)
              for a in (st.vidx, st.row_of_flat, st.strides, st.x_hi)],
            arg((), jnp.int64), arg((), jnp.int64),
            arg((), jnp.int64)).compile()
    assert rows == 10_140
    assert compiled.memory_analysis() is not None
