"""Sharding rules: divisibility validity on the production meshes.

These run with the default single-device runtime: NamedSharding validity
(divisibility) is checked structurally against an *abstract* 16×16 / 2×16×16
mesh — no 512-device init, which belongs to the dry-run only.
"""
import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, AxisType, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, cell_supported
from repro.distribution.sharding import (_spec_for_param, batch_shardings,
                                         cache_shardings, mesh_axes,
                                         param_shardings)
from repro.models.transformer import init_cache, init_params


def abstract_mesh(multi_pod: bool):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return AbstractMesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def _axis_size(mesh, axes):
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _check_divisible(mesh, tree, shardings):
    for (path, leaf), sh in zip(
            jax.tree_util.tree_flatten_with_path(tree)[0],
            jax.tree.leaves(shardings, is_leaf=lambda x: hasattr(x, "spec"))):
        spec = sh.spec
        for dim, axes in zip(leaf.shape, spec):
            size = _axis_size(mesh, axes)
            assert dim % size == 0, (path, leaf.shape, spec)


@pytest.mark.parametrize("arch", sorted(ARCHS))
@pytest.mark.parametrize("multi_pod", [False, True])
def test_param_shardings_divisible(arch, multi_pod):
    cfg = ARCHS[arch]
    mesh = abstract_mesh(multi_pod)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    shardings = param_shardings(mesh, params)
    _check_divisible(mesh, params, shardings)


@pytest.mark.parametrize("arch", ["gemma3-1b", "qwen3-moe-235b-a22b",
                                  "zamba2-1.2b", "whisper-small"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_cache_shardings_divisible(arch, shape):
    cfg, sh = ARCHS[arch], SHAPES[shape]
    if sh.kind != "decode" or not cell_supported(cfg, sh)[0]:
        pytest.skip("decode cells only")
    mesh = abstract_mesh(False)
    cache = jax.eval_shape(
        lambda: init_cache(cfg, sh.global_batch, sh.seq_len))
    shardings = cache_shardings(mesh, cache, sh.global_batch)
    _check_divisible(mesh, cache, shardings)


def test_tp_shards_big_matrices():
    """The big FFN/attention matrices must actually be sharded on the model
    axis (not silently replicated)."""
    mesh = abstract_mesh(False)
    spec = _spec_for_param(mesh, "layers/mlp/wi", (16, 2048, 8192))
    assert "model" in jax.tree.leaves(tuple(spec))
    spec_o = _spec_for_param(mesh, "layers/attn/wo", (16, 2048, 2048))
    assert spec_o[1] == "model"


def test_moe_expert_sharding_adapts():
    mesh = abstract_mesh(False)
    # qwen3: 128 experts divisible by 16 -> expert-parallel
    s = _spec_for_param(mesh, "layers/moe/wi", (94, 128, 4096, 1536))
    assert s[1] == "model"
    # grok: 8 experts NOT divisible -> FFN dim sharded instead
    s = _spec_for_param(mesh, "layers/moe/wi", (64, 8, 6144, 32768))
    assert s[1] is None and s[3] == "model"


def test_long_context_cache_context_parallel():
    """batch=1 long_500k: the sequence dim (not batch) goes on data."""
    mesh = abstract_mesh(False)
    cfg = ARCHS["gemma3-1b"]
    cache = jax.eval_shape(lambda: init_cache(cfg, 1, 524288))
    shardings = cache_shardings(mesh, cache, 1)
    k_spec = shardings["k"].spec
    assert k_spec[2] == ("data",) or k_spec[2] == "data"


def test_batch_shardings_use_dp():
    mesh = abstract_mesh(True)
    batch = {"tokens": jax.ShapeDtypeStruct((256, 4097), np.int32)}
    sh = batch_shardings(mesh, batch)
    assert sh["tokens"].spec[0] == ("pod", "data")
