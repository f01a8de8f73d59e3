"""The comparison that decides ``correct`` for the paper cells, at sizes
a test run can hold.

A sound run comes out correct; the control (the plain
reference one precision below the configuration's, in the program's place)
does not; and a run with the timed path broken underneath comes out not
correct, once for each fault the cell can have: a step that returns its
state unchanged, half the batch left out, the exchange between nodes left
out, an answer altered where it is produced.  Each run skips the look for a
chip and is otherwise a whole run of the harness.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import pytest

from bench import calibrate, harness
from bench.tests import tiny

PAPER = "paper.mnist8.dpsvrg"


@pytest.mark.parametrize("name", [PAPER])
def test_sound_run_is_correct(fresh, name):
    line = tiny.run(name)
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["compiles_in_window"] == 0


@pytest.mark.parametrize("name", [PAPER])
def test_control_is_not_correct(name):
    _, workload, config = tiny.cell(name)
    rows = calibrate.readings(name, [11], 1, faults=(), workload=workload,
                              config=config, emit=lambda _: None)
    control = next(r for r in rows if r["kind"] == "control")
    limits = workload["limits"]
    assert any(v > limits[k] for k, v in control["values"].items()), control


def _paper_fault(monkeypatch, fault):
    from repro.core import algorithm, gossip, prox, runner
    if fault == "no_mix":
        monkeypatch.setattr(gossip, "mix_stacked", lambda phi, tree: tree)
        return
    if fault == "answer":
        objective = runner._resolved_objective
        monkeypatch.setattr(runner, "_resolved_objective", lambda meta, p:
                            objective(meta, p._replace(prox=prox.none())))
        return
    factory = algorithm.ALGORITHMS["dpsvrg"]

    def broken(problem, hp):
        algo = factory(problem, hp)
        if fault == "unchanged":
            return dataclasses.replace(
                algo, step=lambda state, batch, phi, alpha: state)
        outer = algo.outer_traced
        return dataclasses.replace(algo, outer_traced=lambda s, data: outer(
            s, jax.tree.map(lambda a: a[:, :a.shape[1] // 2], data)))
    monkeypatch.setitem(algorithm.ALGORITHMS, "dpsvrg", broken)


@pytest.mark.parametrize("fault", calibrate.FAULTS)
def test_paper_fault_is_not_correct(fresh, monkeypatch, fault):
    _paper_fault(monkeypatch, fault)
    line = tiny.run(PAPER)
    assert not line["correct"], line["compared"]


FOUR_DEVICES = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
from bench.tests import tiny
from repro.core import gossip
sound = tiny.run("paper.mnist4.ppermute4", nodes=4)
gossip.mix_stacked = lambda phi, tree: tree
from repro.core import runner
runner.reset_executable_caches()
cut = tiny.run("paper.mnist4.ppermute4", nodes=4)
print(json.dumps([sound["correct"], cut["correct"], cut["compared"]]))
"""


def test_ppermute_across_four_devices_and_the_exchange_left_out():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = FOUR_DEVICES.format(root=str(harness.ROOT),
                                 src=str(harness.ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    sound, cut, compared = json.loads(proc.stdout.strip().splitlines()[-1])
    assert sound
    assert not cut, compared
