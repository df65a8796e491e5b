"""The comparison that decides ``correct``, driven through a whole run on
the CPU at a size a test run can hold (the cells' configurations and
traffic with the swarm cut to 20,000 peers): a sound run is correct, and
the control and each fault the cells can have are refused."""

import dataclasses
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark import check, reference
from benchmark.control import coarse_build, control_answers
from benchmark.harness import Swarm
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
    numbers, _ = check.compare(swarm.args, rp, ci, PEERS, broadcasts, sample,
                               SEED, reference.law_degrees(PEERS, 2.5))
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
