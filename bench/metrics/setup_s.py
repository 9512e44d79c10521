"""Seconds from the start of the process to the start of the window: JAX,
traffic, the plane and its compiles, and the warm-up (host clock)."""


def read(run):
    return run.setup_s
