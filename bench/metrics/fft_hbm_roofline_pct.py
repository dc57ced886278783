"""The 1D-FFT passes' share of their roofline.

The least time is the algorithm's own work per chip and step
(``bench.work``) at the peaks table's HBM bandwidth or FLOP/s, whichever
bounds it (HBM, for float32 FFT passes on a TPU v5e); the time taken is
the device time of the ops of the ``fft`` table.
"""

from bench.work import roofline_seconds


def read(s):
    t = s.class_s.get("fft", 0.0)
    if t <= 0:
        return None
    least, _bound = roofline_seconds(s.fft_flops, s.fft_bytes, s.peaks)
    return 100.0 * least / (t / s.steps)
