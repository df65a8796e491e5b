"""Seconds of XLA backend compilation in set-up, from JAX's monitoring
events. JAX times a persistent-cache read under the same event, so on a run
that finds every program in the cache this is the time of those reads."""


def read(r):
    return r.counters["compile_s"]
