"""Reference for the real-to-complex 3D FFT pair (heFFTe ``speed3d_r2c``).

Layouts are the program's interface (``make_fft3d``): a physical field is
the X-pencil array indexed ``[y, z, x]``; its spectrum is the Z-pencil
array indexed ``[kx, ky, kz]`` with ``kx = 0 .. N/2`` (a padded program
spectrum carries zero rows beyond ``N/2``). The forward transform is
unnormalised and the inverse divides by N³, as in NumPy.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.fft as sfft

WORKERS = os.cpu_count() or 1


def rfft3(x, rnd=None):
    """Forward r2c 3D FFT of a real ``[y, z, x]`` field → ``[kx, ky, kz]``.

    ``rnd`` (e.g. :func:`bench.reference.bf16`) rounds the input and the
    output of each 1D pass, emulating the transform in that precision.
    """
    x = np.asarray(x, np.float64)
    if rnd is None:
        r = sfft.rfftn(x, axes=(0, 1, 2), workers=WORKERS)
    else:
        r = rnd(sfft.rfft(rnd(x), axis=2, workers=WORKERS))
        r = rnd(sfft.fft(r, axis=0, workers=WORKERS))
        r = rnd(sfft.fft(r, axis=1, workers=WORKERS))
    return np.moveaxis(r, 2, 0)


def irfft3(s, n: int, rnd=None):
    """Inverse c2r 3D FFT of a ``[kx, ky, kz]`` half spectrum → ``[y, z, x]``."""
    r = np.moveaxis(np.asarray(s, np.complex128), 0, 2)
    if rnd is None:
        return sfft.irfftn(r, s=(n, n, n), axes=(0, 1, 2), workers=WORKERS)
    r = rnd(sfft.ifft(rnd(r), axis=1, workers=WORKERS))
    r = rnd(sfft.ifft(r, axis=0, workers=WORKERS))
    return rnd(sfft.irfft(r, n=n, axis=2, workers=WORKERS))


def spectrum_rel_l2(got_re, got_im, want, block: int = 4) -> float:
    """Relative L2 error of a planar program spectrum against ``want``.

    ``got_re``/``got_im`` are ``[Kx, ky, kz]`` with ``Kx >= N/2+1``; rows
    beyond ``want``'s are padding and must be zero, so they count as error.
    Summed in blocks of kx rows, in threads, to bound the float64
    temporaries and the time.
    """
    keep = want.shape[0]

    def sums(i):
        j = min(i + block, keep)
        w = want[i:j]
        d = ((got_re[i:j].astype(np.float64) - w.real) ** 2
             + (got_im[i:j].astype(np.float64) - w.imag) ** 2)
        return float(np.sum(d)), float(np.sum(w.real ** 2 + w.imag ** 2))

    with ThreadPoolExecutor(WORKERS) as pool:
        parts = list(pool.map(sums, range(0, keep, block)))
    num = sum(p[0] for p in parts)
    den = sum(p[1] for p in parts)
    pad = (np.sum(np.square(got_re[keep:], dtype=np.float64))
           + np.sum(np.square(got_im[keep:], dtype=np.float64)))
    return float(np.sqrt((num + pad) / den))
