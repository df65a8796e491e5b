"""Seconds of the overlay build (``matching_powerlaw_graph``), from the call
to a scalar fetched off its CSR."""


def read(r):
    return r.counters["graph_build_s"]
