"""Seconds from process start to the first broadcast of the window: JAX
start-up, the overlay build, compilation or cache reads, and warm-up."""


def read(r):
    return r.counters["setup_s"]
