"""Collective time per step during which no other op ran on that device."""


def read(s):
    if s.class_s.get("collective", 0.0) <= 0:
        return None
    return 1e3 * s.exposed_s.get("collective", 0.0) / s.steps
