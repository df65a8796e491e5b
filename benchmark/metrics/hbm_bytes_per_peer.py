"""Device memory a chip must hold per peer: the fullest device's
``peak_bytes_in_use`` after the window, times the device count, over the
peers (the overlay build's transient included)."""


def read(r):
    peak = r.counters["memory_peak_bytes"]
    return peak * r.counters["devices"] / r.counters["peers"] if peak else None
