"""Host milliseconds per broadcast spent in ``init_swarm`` (with drawing its
origins and key), totalled over the window."""


def read(r):
    return r.counters["reset_s"] / r.counters["broadcasts"] * 1e3
