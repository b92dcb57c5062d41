"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ALL_KERNELS, HUB_KERNELS
from repro.kernels import (convolution as cv, dedispersion as dd,
                           flash_attention as fa, gemm as gm, hotspot as hs,
                           ssd)

RTOL = {jnp.float32: 2e-4, jnp.bfloat16: 2e-2}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,n,k,bm,bn,bk", [
    (128, 128, 128, 64, 128, 128),
    (192, 256, 320, 96, 128, 64),     # non-dividing K handled by padding
    (200, 130, 90, 64, 128, 128),     # all dims padded
])
def test_gemm_sweep(dtype, m, n, k, bm, bn, bk):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    a, b = _rand(ks[0], (m, k), dtype), _rand(ks[1], (k, n), dtype)
    c0 = _rand(ks[2], (m, n), dtype)
    out = gm.gemm(a, b, c0, block_m=bm, block_n=bn, block_k=bk,
                  alpha=0.5, beta=1.5, interpret=True)
    ref = gm.gemm_ref(a, b, c0, alpha=0.5, beta=1.5)
    tol = RTOL[dtype] * k ** 0.5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("h,w,fh,fw,sh,bw", [
    (64, 128, 5, 5, 32, 128),
    (96, 130, 3, 7, 48, 96),          # padded width
    (128, 256, 17, 17, 16, 128),      # hub filter size
])
def test_convolution_sweep(h, w, fh, fw, sh, bw):
    x = _rand(jax.random.PRNGKey(1), (h, w), jnp.float32)
    f = _rand(jax.random.PRNGKey(2), (fh, fw), jnp.float32)
    out = cv.conv2d(x, f, strip_h=sh, block_w=bw, interpret=True)
    ref = cv.conv2d_ref(x, f)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("tb", [1, 2, 4])
def test_hotspot_temporal_blocking_exact(tb):
    t = _rand(jax.random.PRNGKey(3), (64, 128), jnp.float32)
    p = _rand(jax.random.PRNGKey(4), (64, 128), jnp.float32) * 0.1
    out = hs.hotspot(t, p, strip_h=32, block_w=128, t_block=tb,
                     interpret=True)
    ref = hs.hotspot_ref(t, p, t_block=tb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bdm,bt", [(8, 256), (4, 192), (16, 128)])
def test_dedispersion_sweep(bdm, bt):
    x = _rand(jax.random.PRNGKey(5), (32, 768 + dd.MAX_DELAY), jnp.float32)
    delays = dd.make_delays(32, 24)
    out = dd.dedisperse(x, delays, block_dm=bdm, block_t=bt, interpret=True)
    ref = dd.dedisperse_ref(x, delays)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = _rand(ks[0], (4, 256, 64), dtype)
    k = _rand(ks[1], (2, 256, 64), dtype)   # GQA group of 2
    v = _rand(ks[2], (2, 256, 64), dtype)
    out = fa.flash_attention(q, k, v, block_q=128, block_kv=128,
                             causal=causal, window=window, interpret=True)
    ref = fa.attention_ref(q, k, v, causal=causal, window=window)
    tol = RTOL[dtype]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("chunk", [32, 64, 128])
def test_ssd_sweep(chunk):
    bh, l, p, n = 3, 256, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = _rand(ks[0], (bh, l, p), jnp.float32)
    dt = jax.nn.softplus(_rand(ks[1], (bh, l), jnp.float32)) * 0.1
    a = -jax.nn.softplus(_rand(ks[2], (bh,), jnp.float32))
    b = _rand(ks[3], (bh, l, n), jnp.float32)
    c = _rand(ks[4], (bh, l, n), jnp.float32)
    out = ssd.ssd_scan(x, dt, a, b, c, chunk=chunk, interpret=True)
    ref = ssd.ssd_ref(x, dt, a, b, c)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-3, atol=3e-3)


def test_every_kernel_exposes_space_and_workload():
    for name, mod in ALL_KERNELS.items():
        space = mod.space()
        assert space.size >= 30, name
        wl = mod.workload()
        cfg = space.as_dict(space.valid_configs[0])
        assert wl.flops(cfg) > 0
        assert wl.vmem_bytes(cfg) > 0


def test_hub_kernel_spaces_have_failures():
    """Real auto-tuning spaces contain configs that fail at runtime (VMEM
    overflow on the smallest device model)."""
    from repro.core.costmodel import estimate
    from repro.core.devices import LITE_A
    failing = 0
    for name in ("convolution", "hotspot", "gemm"):
        mod = HUB_KERNELS[name]
        space, wl = mod.space(), mod.workload()
        if any(estimate(wl, space.as_dict(cfg), LITE_A,
                        space.config_id(cfg)).status == "error"
               for cfg in space.valid_configs):
            failing += 1
    assert failing >= 2


# ------------------------------------------------------ live: interpret mode
LIVE_WRAPPERS = {"gemm": "gemm", "convolution": "conv2d",
                 "dedispersion": "dedisperse",
                 "flash_attention": "flash_attention", "hotspot": "hotspot",
                 "ssd": "ssd_scan"}


def test_live_device_follows_the_platform(monkeypatch):
    from types import SimpleNamespace

    from repro.core.devices import live_device
    assert live_device() == ("cpu_interpret", True)
    monkeypatch.setattr(jax, "devices", lambda: [
        SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")])
    assert live_device() == ("tpu_v5_lite", False)


@pytest.mark.parametrize("interpret", [True, False])
@pytest.mark.parametrize("name", sorted(ALL_KERNELS))
def test_make_live_takes_interpret_from_the_platform(monkeypatch, name,
                                                     interpret):
    """Every live objective compiles for the chip on a TPU and interprets
    elsewhere — never a hard-coded mode."""
    import repro.kernels
    spec = repro.kernels.get_kernel(name)
    module = ALL_KERNELS[name]
    label = "cpu_interpret" if interpret else "tpu_v5_lite"
    monkeypatch.setattr(repro.kernels, "live_device",
                        lambda: (label, interpret))
    seen = []

    def wrapper(*_args, **kw):
        seen.append(kw["interpret"])
        return jnp.zeros(1)
    monkeypatch.setattr(module, LIVE_WRAPPERS[name], wrapper)
    space = spec.space()
    spec.make_live()(space.as_dict(space.valid_configs[0]))
    assert seen == [interpret]
