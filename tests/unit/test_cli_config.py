"""Config cache + CLI port-prompt parity (SURVEY.md §2.1 is_my_turn,
reference Seed.py:479-492 / Peer.py:456-465 stdin prompts)."""

import pytest

from tpu_gossip.cli import prompt_port
from tpu_gossip.compat.seed import ConfigCache, SeedNode, load_config


def test_config_cache_invalidates_on_append(tmp_path):
    p = tmp_path / "config.txt"
    p.write_text("127.0.0.1:121\n")
    cache = ConfigCache(str(p))
    assert cache.entries() == [("127.0.0.1", 121)]
    with open(p, "a") as f:
        f.write("127.0.0.1:122\n")
    assert cache.entries() == [("127.0.0.1", 121), ("127.0.0.1", 122)]


def test_config_cache_skips_reparse_when_unchanged(tmp_path, monkeypatch):
    p = tmp_path / "config.txt"
    p.write_text("127.0.0.1:121\n127.0.0.1:122\n")
    cache = ConfigCache(str(p))
    first = cache.entries()
    # a second read with the same (mtime, size) must not touch the parser
    import tpu_gossip.compat.seed as seed_mod

    def boom(path):
        raise AssertionError("load_config called on unchanged file")

    monkeypatch.setattr(seed_mod, "load_config", boom)
    assert cache.entries() is first


def test_is_my_turn_elects_exactly_one_quorum_seed(tmp_path):
    p = tmp_path / "config.txt"
    addrs = [("127.0.0.1", 121 + i) for i in range(5)]
    p.write_text("".join(f"{ip}:{port}\n" for ip, port in addrs))
    seeds = [
        SeedNode(ip, port, config_path=str(p), log_dir=str(tmp_path))
        for ip, port in addrs
    ]
    quorum = addrs[: len(addrs) // 2 + 1]
    for peer in [("10.0.0.9", 5000 + i) for i in range(20)]:
        winners = [s.addr for s in seeds if s.is_my_turn(peer)]
        assert len(winners) == 1
        assert winners[0] in quorum


def test_prompt_port_retries_until_valid(monkeypatch):
    answers = iter(["nope", "99999", " 5001 "])
    monkeypatch.setattr("builtins.input", lambda _: next(answers))
    assert prompt_port("peer") == 5001


def test_prompt_port_eof_exits(monkeypatch):
    def eof(_):
        raise EOFError

    monkeypatch.setattr("builtins.input", eof)
    with pytest.raises(SystemExit):
        prompt_port("seed")


def test_bare_cli_parsers_accept_missing_port():
    from tpu_gossip.cli.run_peer import build_parser as peer_parser
    from tpu_gossip.cli.run_seed import build_parser as seed_parser

    assert peer_parser().parse_args([]).port is None
    assert seed_parser().parse_args([]).port is None


@pytest.mark.parametrize("cell", ["pushpull_10m", "flood_1m", "pushpull_1m"])
def test_benchmark_cell_parses(cell):
    """Every benchmark cell's run_sim arguments still parse, pick the local
    engine and run the fused tail: the seam between run_sim's parser and
    the benchmark harness."""
    from benchmark import harness, spec

    args, engine = harness.sim_args(spec.load_cell(cell), 0)
    assert engine.__name__ == "benchmark.engines.local"
    assert args.tail == "fused"
