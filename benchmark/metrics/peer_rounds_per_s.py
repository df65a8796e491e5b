"""Peers times rounds over every broadcast of the window, per wall second of
the window (resets and fetches inside it)."""


def read(r):
    return r.counters["peer_rounds"] / r.counters["window_s"]
