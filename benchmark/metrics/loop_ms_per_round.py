"""Milliseconds per round from dispatching ``run_until_coverage`` to the
host holding the final coverage, totalled over the window's broadcasts and
divided by their rounds."""


def read(r):
    rounds = r.counters["rounds"]
    return r.counters["loop_s"] / rounds * 1e3 if rounds else None
