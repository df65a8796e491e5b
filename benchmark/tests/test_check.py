"""The comparison that decides ``correct``, driven through a whole run on
the CPU at a size a test run can hold (the cells' configurations and
traffic with the swarm cut to 20,000 peers): a sound run is correct, and
the control and each fault the cells can have are refused."""

import dataclasses
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from benchmark import check, engines
from benchmark.control import coarse_build, control_answers
from benchmark.harness import Swarm, sim_args
from benchmark.run import run_cell
from benchmark.spec import ROOT, load_cell

PEERS = 20_000
SEED = 2**31 + 29
CELLS = ("flood_1m", "pushpull_1m")


def small(name):
    cell = load_cell(name)
    cell.traffic["peers"] = PEERS
    return cell


def run(name, breaker=None, build=None):
    import jax

    result, _ = run_cell(small(name), SEED, 1.0, False, jax.devices(),
                         time.perf_counter(), breaker, build)
    return result


@pytest.mark.parametrize("name", CELLS)
def test_a_sound_run_is_correct(name):
    result = run(name)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 1
    assert list(result)[-1] == "check"
    assert result["check"]["erased_share"]["value"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_the_control_is_refused(name):
    """The reference in the program's place, relaying two hops a round."""
    cell = small(name)
    swarm = Swarm(cell, SEED)
    rp, ci = swarm.overlay()
    swarm.release()
    k = int(cell.traffic["checked_broadcasts"])
    broadcasts, sample = control_answers(swarm, rp, ci, k)
    numbers, _ = swarm.compare(swarm.args, rp, ci, PEERS, broadcasts, sample,
                               SEED, swarm.law())
    correct, shown = check.verdict(numbers, cell.config["check"])
    assert not correct
    assert shown["faults"]["value"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_a_build_that_drops_edges_is_refused(name):
    """The program's build with a coarser class plan erases the stubs paired
    with its padding: the broadcasts over it are sound, the overlay not."""
    result = run(name, build=coarse_build)
    assert not result["correct"]
    assert result["check"]["faults"]["value"] == 0
    erased = result["check"]["erased_share"]
    assert erased["value"] > erased["limit"]


def unchanged(swarm):
    """A round loop that returns its state unchanged."""
    swarm.run = lambda state, *a, **k: state


def half_left_out(swarm):
    """Half the peers left out of the broadcast, coverage taken over the
    rest."""
    run = swarm.run

    def broken(state, *a, **k):
        keep = np.arange(state.alive.shape[0]) % 2 == 0
        return run(dataclasses.replace(state, alive=state.alive & keep),
                   *a, **k)

    swarm.run = broken


def answer_altered(swarm):
    """The round count the loop produces, off by one."""
    run = swarm.run

    def broken(state, *a, **k):
        fin = run(state, *a, **k)
        return dataclasses.replace(fin, round=fin.round + 1)

    swarm.run = broken


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [unchanged, half_left_out, answer_altered])
def test_a_broken_timed_path_is_refused(name, fault):
    result = run(name, fault)
    assert not result["correct"]
    assert result["check"]["faults"]["value"] > 0


@pytest.mark.parametrize("name", ["flood_1m", "pushpull_1m", "pushpull_10m"])
def test_the_configuration_picks_its_engine(name):
    args, module = sim_args(load_cell(name), SEED)
    assert module.__name__ == "benchmark.engines.local"
    assert args.seed == SEED and args.peers == load_cell(name).peers


@pytest.mark.parametrize("extra", [["--churn-leave", "0.01"],
                                   ["--shard", "--builder", "dist"],
                                   ["--silent-frac", "0.1"]])
def test_an_option_no_engine_honours_is_refused(extra):
    """Churn, the sharded path and silent peers: no engine drives them."""
    cell = load_cell("pushpull_1m")
    cell.config["run_sim"] = cell.config["run_sim"] + extra
    with pytest.raises(ValueError, match="0 engines"):
        sim_args(cell, SEED)


def test_an_engine_arrives_as_a_file(tmp_path, monkeypatch):
    """A module dropped beside the others is found by listing the package
    and takes the options it honours; where two engines accept one
    configuration, the harness refuses it."""
    (tmp_path / "churning.py").write_text(textwrap.dedent("""
        from benchmark.engines.local import HONOURED as LOCAL

        HONOURED = LOCAL | {"churn_leave"}

        def accepts(args):
            return args.graph == "matching"
    """))
    monkeypatch.setattr(engines, "__path__", [*engines.__path__,
                                              str(tmp_path)])
    cell = load_cell("pushpull_1m")
    cell.config["run_sim"] = cell.config["run_sim"] + ["--churn-leave", "0.01"]
    try:
        _, module = sim_args(cell, SEED)
        assert module.__name__ == "benchmark.engines.churning"
        with pytest.raises(ValueError, match="2 engines"):
            sim_args(load_cell("pushpull_1m"), SEED)
    finally:
        sys.modules.pop("benchmark.engines.churning", None)


def test_no_tpu_no_result():
    """On a CPU the command exits nonzero and prints no result line."""
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "flood_1m",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_a_chip_count_other_than_the_cells_is_refused():
    from benchmark.run import device_error

    class Dev:
        platform = "tpu"

    assert device_error([Dev()], 1) is None
    assert "asks for 4" in device_error([Dev()], 4)
    assert "asks for 1" in device_error([Dev()] * 4, 1)
