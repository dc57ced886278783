"""Per-kernel validation: shape/dtype sweeps of the Pallas engine vs the
pure-jnp oracle and vs jnp.fft ground truth."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels import ref
from repro.kernels.fft_radix2 import fft1d_pallas, ifft1d_pallas, pick_batch_tile
from repro.kernels.ops import fft1d, irfft1d, rfft1d

TOL = {jnp.float32: 2e-4, jnp.float64: 1e-10}


def rel_l2(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


def rand_planar(shape, dtype, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k1, shape, dtype=dtype),
            jax.random.normal(k2, shape, dtype=dtype))


@pytest.mark.parametrize("n", [2, 4, 8, 64, 128, 512, 1024])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_ref_matches_jnp_fft(n, dtype):
    xr, xi = rand_planar((5, n), dtype)
    yr, yi = ref.fft_dif_planar(xr, xi)
    z = np.fft.fft(np.asarray(xr, np.float64) + 1j * np.asarray(xi, np.float64))
    assert rel_l2(yr, z.real) < TOL[dtype]
    assert rel_l2(yi, z.imag) < TOL[dtype]


@pytest.mark.parametrize("n", [8, 128, 256, 1024, 4096])
@pytest.mark.parametrize("batch", [1, 3, 8, 37])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_pallas_matches_ref(n, batch, dtype):
    xr, xi = rand_planar((batch, n), dtype, seed=n + batch)
    pr, pi = fft1d_pallas(xr, xi)
    rr, ri = ref.fft_dif_planar(xr, xi)
    tol = dict(rtol=1e-4, atol=1e-3) if dtype == jnp.float32 else dict(rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(np.asarray(pr), np.asarray(rr), **tol)
    np.testing.assert_allclose(np.asarray(pi), np.asarray(ri), **tol)


@pytest.mark.parametrize("n", [64, 512])
def test_pallas_multi_lead_axes(n):
    xr, xi = rand_planar((2, 3, 4, n), jnp.float32, seed=1)
    pr, pi = fft1d_pallas(xr, xi)
    z = np.fft.fft(np.asarray(xr, np.float64) + 1j * np.asarray(xi, np.float64))
    assert rel_l2(pr, z.real) < 2e-4
    assert rel_l2(pi, z.imag) < 2e-4


@pytest.mark.parametrize("n", [16, 256])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_pallas_roundtrip(n, dtype):
    xr, xi = rand_planar((4, n), dtype, seed=2)
    yr, yi = fft1d_pallas(xr, xi)
    br, bi = ifft1d_pallas(yr, yi)
    assert rel_l2(br, xr) < TOL[dtype]
    assert rel_l2(bi, xi) < TOL[dtype]


@pytest.mark.parametrize("backend", ["pallas", "ref", "jnp"])
@pytest.mark.parametrize("axis", [0, 1, -1])
def test_fft1d_axis(backend, axis):
    xr, xi = rand_planar((8, 16, 32), jnp.float32, seed=3)
    yr, yi = fft1d(xr, xi, axis=axis, backend=backend)
    z = np.fft.fft(np.asarray(xr, np.float64) + 1j * np.asarray(xi, np.float64), axis=axis)
    assert rel_l2(yr, z.real) < 2e-4
    assert rel_l2(yi, z.imag) < 2e-4


@pytest.mark.parametrize("backend", ["pallas", "ref"])
@pytest.mark.parametrize("packed", [False, True])
def test_rfft_and_inverse(backend, packed):
    n = 128
    x = jax.random.normal(jax.random.PRNGKey(4), (6, n), dtype=jnp.float64)
    yr, yi = rfft1d(x, backend=backend, packed=packed)
    z = np.fft.rfft(np.asarray(x, np.float64))
    assert rel_l2(yr, z.real) < 1e-10
    assert rel_l2(yi, z.imag) < 1e-10
    back = irfft1d(yr, yi, n=n, backend=backend)
    assert rel_l2(back, x) < 1e-10


@pytest.mark.parametrize("backend", ["pallas", "ref", "jnp"])
def test_rfft_packed_rejects_odd_length(backend):
    # even/odd packing assumes n % 2 == 0; odd lengths must fail loudly at
    # trace time, not silently mangle the spectrum
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 9), dtype=jnp.float64)
    with pytest.raises(ValueError, match="even transform length"):
        rfft1d(x, backend=backend, packed=True)
    # the faithful unpacked path still handles odd lengths (jnp engine)
    yr, yi = rfft1d(x, backend="jnp", packed=False)
    z = np.fft.rfft(np.asarray(x, np.float64))
    assert rel_l2(yr, z.real) < 1e-10 and rel_l2(yi, z.imag) < 1e-10


#: (backend, n): every backend at a power of two, and the jnp engine at an
#: even and an odd length no radix-2 engine takes
R2C_ENGINES = [("jnp", 16), ("jnp", 12), ("jnp", 9), ("ref", 16),
               ("pallas", 16), ("mxu", 16)]
#: leading shapes and the path each takes: pairs along the outermost even
#: axis, or lines one by one where no leading axis is even
R2C_LEADS = [((4,), "paired"), ((3, 4), "paired"), ((3,), "unpaired"),
             ((5, 3), "unpaired"), ((), "unpaired")]


@pytest.mark.parametrize("lead,path", R2C_LEADS,
                         ids=["4", "3x4", "3", "5x3", "none"])
@pytest.mark.parametrize("backend,n", R2C_ENGINES,
                         ids=[f"{b}-{n}" for b, n in R2C_ENGINES])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_rfft_irfft_pair_lines_like_numpy(backend, n, lead, path, dtype):
    rng = np.random.default_rng(n + len(lead))
    x = rng.standard_normal(lead + (n,))
    # a spectrum with non-zero imaginary parts in every bin, DC and
    # Nyquist too: irfft drops those two as numpy does
    z = rng.standard_normal(lead + (n // 2 + 1,)) \
        + 1j * rng.standard_normal(lead + (n // 2 + 1,))
    rfft1d.clear_cache()
    irfft1d.clear_cache()
    with obs.capture() as (_, metrics):
        yr, yi = rfft1d(jnp.asarray(x, dtype), backend=backend)
        back = irfft1d(jnp.asarray(z.real, dtype), jnp.asarray(z.imag, dtype),
                       n=n, backend=backend)
    assert metrics.counters() == {f"fft.r2c.{path}": 2}
    want = np.fft.rfft(x)
    assert yr.dtype == dtype and back.dtype == dtype
    assert rel_l2(yr, want.real) < TOL[dtype]
    assert rel_l2(yi, want.imag) < TOL[dtype]
    assert rel_l2(back, np.fft.irfft(z, n)) < TOL[dtype]


def test_pick_batch_tile_respects_vmem():
    for n in [512, 1024, 4096, 8192]:
        tb = pick_batch_tile(n, 4096, 4)
        assert 6 * tb * n * 4 <= 8 * 1024 * 1024 or tb == 8


def test_twiddle_table_is_rom_like():
    twr, twi = ref.twiddle_table_np(16)
    assert twr.shape == (4, 8)
    # stage 0 row: W_16^j, j=0..7
    j = np.arange(8)
    np.testing.assert_allclose(twr[0], np.cos(-2 * np.pi * j / 16), atol=1e-15)
    # last stage: all-ones (W_2^0 tiled)
    np.testing.assert_allclose(twr[-1], np.ones(8), atol=1e-15)
    np.testing.assert_allclose(twi[-1], np.zeros(8), atol=1e-15)
