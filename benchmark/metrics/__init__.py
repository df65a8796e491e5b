"""One reader per metric, found by the metric's name in BENCHMARK.json.

Each module defines ``read(r)``: the metric's value from a run's readings
(``benchmark.run.Readings``: the harness's counters and host spans, and
the device trace of a traced run), or None where it finds nothing to read,
which leaves the metric out of the result line.
"""
