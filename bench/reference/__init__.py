"""Plain NumPy float64 references, independent of the program under test.

Each takes only arrays in the program's documented layouts and computes
the same mathematics from its published definition. ``bf16`` emulates the
reference in bfloat16 (every stored array rounded), the lower-precision
control that the correctness limits must reject.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import ml_dtypes
import numpy as np

WORKERS = os.cpu_count() or 1


def _round(a):
    if np.iscomplexobj(a):
        return (a.real.astype(ml_dtypes.bfloat16).astype(np.float64)
                + 1j * a.imag.astype(ml_dtypes.bfloat16).astype(np.float64))
    return a.astype(ml_dtypes.bfloat16).astype(np.float64)


def bf16(a):
    """Round a float or complex array to bfloat16 (parts separately),
    returned in float64/complex128 so later arithmetic stays NumPy's.
    Large arrays are rounded in slabs of axis 0, in threads."""
    a = np.asarray(a)
    if a.ndim == 0 or a.shape[0] < 2 * WORKERS:
        return _round(a)
    step = -(-a.shape[0] // WORKERS)
    with ThreadPoolExecutor(WORKERS) as pool:
        parts = list(pool.map(lambda i: _round(a[i:i + step]),
                              range(0, a.shape[0], step)))
    return np.concatenate(parts)


def rel_l2(got, want) -> float:
    """||got - want|| / ||want|| in float64."""
    got = np.asarray(got)
    want = np.asarray(want)
    num = np.sqrt(np.sum(np.abs(got - want) ** 2))
    den = np.sqrt(np.sum(np.abs(want) ** 2))
    return float(num / den)
