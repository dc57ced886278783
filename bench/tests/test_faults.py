"""Whole runs of the harness on the CPU: sound ones come out correct, and
ones with the timed path broken underneath come out not correct.

The look for a chip is skipped (``require_tpu=False``); everything else is
a benchmark run of the real cells at a tiny grid.
"""

import jax
import jax.numpy as jnp
import pytest

from bench import harness

SPEED1 = "speed3d_r2c_f32.n512.1x1"
SPEED4 = "speed3d_r2c_f32.n1024.2x2"
TGV = "tgv_re1600.n256.1x1"


def run(root, workload, seed=2 ** 31 + 11):
    return harness.run(workload, seed, 0.3, False, 0.0, require_tpu=False,
                       root=root)


@pytest.mark.parametrize("workload", [SPEED1, SPEED4, TGV])
def test_sound_run_is_correct(tiny_root, workload):
    r = run(tiny_root, workload)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"step_ms", "setup_s"}
    assert list(r)[-1] == "checks"


def _alter_forward(monkeypatch):
    from repro.core import fft3d

    orig = fft3d.fft3d_local

    def altered(plan, xr, xi=None):
        kr, ki = orig(plan, xr, xi)
        return kr.at[0, 1, 1].add(100.0), ki

    monkeypatch.setattr(fft3d, "fft3d_local", altered)


def _alter_inverse(monkeypatch):
    from repro.core import fft3d

    orig = fft3d.ifft3d_local

    def altered(plan, kr, ki):
        return orig(plan, kr, ki).at[0, 0, 0].add(1.0)

    monkeypatch.setattr(fft3d, "ifft3d_local", altered)


def _leave_out_exchange(monkeypatch):
    """Every all-to-all keeps the local blocks where a peer's belong."""
    def local_only(x, axis_name, split_axis, concat_axis, tiled=False, **kw):
        p = jax.lax.axis_size(axis_name)
        return jnp.concatenate(jnp.split(x, p, axis=split_axis),
                               axis=concat_axis)

    monkeypatch.setattr(jax.lax, "all_to_all", local_only)


def _state_unchanged(monkeypatch):
    from repro.solvers.navier_stokes import NavierStokesSolver

    monkeypatch.setattr(NavierStokesSolver, "step_fields",
                        lambda self, plan, fields: fields)


def _alter_state(monkeypatch):
    from repro.solvers.navier_stokes import NavierStokesSolver

    orig = NavierStokesSolver.step_fields

    def altered(self, plan, fields):
        vr, vi = orig(self, plan, fields)
        return vr.at[0, 1, 1, 1].multiply(1.01), vi

    monkeypatch.setattr(NavierStokesSolver, "step_fields", altered)


@pytest.mark.parametrize("workload,fault", [
    (SPEED1, _alter_forward),
    (SPEED1, _alter_inverse),
    (SPEED4, _alter_forward),
    (SPEED4, _leave_out_exchange),
    (TGV, _state_unchanged),
    (TGV, _alter_state),
])
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, workload,
                                          fault):
    fault(monkeypatch)
    r = run(tiny_root, workload)
    assert not r["correct"] and r["failed"] >= 1


def test_no_accelerator_exits_without_a_result(capsys):
    rc = harness.main(["--workload", SPEED1, "--seed", "1", "--seconds", "1"])
    assert rc == 2
    assert capsys.readouterr().out == ""
