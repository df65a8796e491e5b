"""The chip benchmark of tpu_gossip: cells, traffic, reference and readers.

``python -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU this process
finds and prints one JSON result line. Everything a cell needs is found by
name: ``configs/<config>.toml``, ``traffic/<traffic>.toml``,
``metrics/<metric>.py`` and ``peaks.toml``.
"""
