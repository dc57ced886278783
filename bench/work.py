"""The algorithm's own work: operations and HBM bytes of 3D FFT passes.

Counted from N and the number of chips, never from what an implementation
does, so a roofline share reads the same whatever runs the transform. A
3D r2c transform of an N³ real field is three passes of 1D FFTs:

* X: N² real transforms of length N, each read as N reals and written as
  N/2+1 complex bins (2.5·N·log2 N operations, half a complex FFT's);
* Y and Z: (N/2+1)·N complex transforms of length N each, read and written
  as complex (5·N·log2 N operations).

The c2r inverse is the same passes in reverse order. Bytes are the least
HBM traffic: every pass reads its input once and writes its output once.
The work divides evenly over the chips of a pencil grid.
"""

from __future__ import annotations

import math

ITEMSIZE = {"float32": 4, "float64": 8}


def fft3d_r2c_passes(n: int, dtype: str = "float32") -> list[tuple[str, float, float]]:
    """``[(pass, operations, bytes)]`` of one r2c (or c2r) 3D transform."""
    real = ITEMSIZE[dtype]
    cplx = 2 * real
    kh = n // 2 + 1
    lg = math.log2(n)
    return [
        ("x_r2c", n * n * 2.5 * n * lg, n * n * n * real + kh * n * n * cplx),
        ("y_c2c", kh * n * 5.0 * n * lg, 2.0 * kh * n * n * cplx),
        ("z_c2c", kh * n * 5.0 * n * lg, 2.0 * kh * n * n * cplx),
    ]


def fft_step_work(n: int, per_step: dict, chips: int,
                  dtype: str = "float32") -> tuple[float, float]:
    """``(operations, bytes)`` per chip of one step that runs
    ``per_step = {"r2c": a, "c2r": b}`` 3D transforms of size N³."""
    transforms = sum(int(v) for v in per_step.values())
    flops = sum(p[1] for p in fft3d_r2c_passes(n, dtype)) * transforms
    nbytes = sum(p[2] for p in fft3d_r2c_passes(n, dtype)) * transforms
    return flops / chips, nbytes / chips


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """Least time for the work at the chip's peaks, and which bound sets it."""
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    t_ops = flops / peaks["flops_per_s"]
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "compute")
