"""Device time per step of every op that is neither FFT nor collective:
relayouts, pointwise spectral and physical-space work, copies."""


def read(s):
    t = s.class_s.get("local", 0.0)
    return 1e3 * t / s.steps if t > 0 else None
