"""Device time per step of the 1D-FFT passes: ops of the ``fft`` table."""


def read(s):
    t = s.class_s.get("fft", 0.0)
    return 1e3 * t / s.steps if t > 0 else None
