"""The lower-precision control fails the limits that sound runs meet."""

import pytest

from bench import control


@pytest.mark.parametrize("workload", ["speed3d_r2c_f32.n512.1x1",
                                      "speed3d_r2c_f32.n1024.2x2",
                                      "tgv_re1600.n256.1x1"])
def test_control_fails_a_limit_the_program_meets(tiny_root, workload):
    rows = list(control.readings(workload, [3, 2 ** 31 + 5, 77], 0.2,
                                 require_tpu=False, root=tiny_root))
    for row in rows:
        limits = row["limits"]
        assert all(v <= limits[k] for k, v in row["program"].items()), row
        assert any(v > limits[k] for k, v in row["control"].items()), row
