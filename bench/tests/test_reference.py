"""The plain references against NumPy and against exact solutions."""

import jax
import numpy as np

from bench.reference import bf16, rel_l2
from bench.reference import fft3d_pair as fref
from bench.reference.navier_stokes import NSStep


def test_rfft3_is_numpy_fftn_in_the_program_layout():
    x = np.random.default_rng(0).standard_normal((16, 16, 16))
    want = np.fft.fftn(x)                       # [ky, kz, kx], full
    got = fref.rfft3(x)                         # [kx, ky, kz], kx <= N/2
    assert got.shape == (9, 16, 16)
    np.testing.assert_allclose(got, np.moveaxis(want[:, :, :9], 2, 0),
                               rtol=1e-12, atol=1e-10)
    np.testing.assert_allclose(fref.irfft3(got, 16), x, atol=1e-12)


def test_spectrum_error_counts_padding_rows():
    x = np.random.default_rng(1).standard_normal((8, 8, 8))
    s = fref.rfft3(x)
    pad = np.zeros((1, 8, 8))
    re = np.concatenate([s.real, pad]).astype(np.float32)
    im = np.concatenate([s.imag, pad]).astype(np.float32)
    assert fref.spectrum_rel_l2(re, im, s) < 1e-6
    im[-1, 0, 0] = 1e3
    assert fref.spectrum_rel_l2(re, im, s) > 1e-2


def test_bf16_reference_is_the_coarser_control():
    x = np.random.default_rng(2).uniform(-1, 1, (16, 16, 16))
    low = fref.rfft3(x, rnd=bf16)
    err = fref.spectrum_rel_l2(low.real, low.imag, fref.rfft3(x))
    assert 1e-4 < err < 3e-2
    assert 1e-4 < rel_l2(fref.irfft3(low, 16, rnd=bf16), x) < 3e-2


def _shear_wave(n, amp=1.0):
    """u = amp·sin(y) x̂ as a [3, kx, ky, kz] half spectrum: an exact
    solution whose nonlinear term is a pure gradient."""
    y = np.linspace(0, 2 * np.pi, n, endpoint=False)
    u = np.zeros((3, n, n, n))
    u[0] = amp * np.sin(y)[:, None, None]       # [c, y, z, x]
    return np.moveaxis(np.fft.rfftn(u, axes=(1, 2, 3)), 3, 1)


def test_ns_step_decays_a_shear_wave_exactly():
    n, nu, dt = 16, 0.05, 0.01
    v = _shear_wave(n)
    out = NSStep(n, nu, dt)(v)
    np.testing.assert_allclose(out, np.exp(-nu * dt) * v, atol=1e-9)


def test_ns_step_keeps_the_field_divergence_free():
    n = 16
    rng = np.random.default_rng(3)
    step = NSStep(n, 1 / 1600, 2e-3)
    u = rng.standard_normal((3, n, n, n)) * 0.1
    v = np.moveaxis(np.fft.rfftn(u, axes=(1, 2, 3)), 3, 1)
    v = np.moveaxis(step.project(np.moveaxis(v, 1, 3)), 3, 1)
    out = step(v)
    kx, ky, kz = (np.moveaxis(k, 2, 0) for k in step.k)
    div = kx * out[0] + ky * out[1] + kz * out[2]
    assert np.max(np.abs(div)) < 1e-9 * np.max(np.abs(out))


def test_ns_reference_matches_the_program_in_float64():
    """The reference and the program's solver take the same step where
    both compute in float64 (CPU), so the reference is the same method."""
    from jax.sharding import Mesh

    from repro.solvers import make_solver

    n, nu, dt = 16, 1 / 1600, 2e-3
    jax.config.update("jax_enable_x64", True)
    try:
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
        solver = make_solver("navier_stokes", mesh, n, nu=nu, dt=dt,
                             dtype="float64")
        state = solver.init_state()
        nxt = solver.step(state)
        prev = np.asarray(state.fields[0]) + 1j * np.asarray(state.fields[1])
        got = np.asarray(nxt.fields[0]) + 1j * np.asarray(nxt.fields[1])
    finally:
        jax.config.update("jax_enable_x64", False)
    want = NSStep(n, nu, dt)(prev)
    assert rel_l2(got - prev, want - prev) < 1e-9
