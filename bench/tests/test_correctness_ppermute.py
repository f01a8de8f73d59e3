"""The comparison that decides ``correct`` for the four-chip cell, on four
virtual CPU devices (a process of its own each, since the device count is
fixed when JAX starts): the control and each fault the cell can have come
out not correct (the faults are planted as for the one-chip paper cell);
the exchange left out is tested in test_correctness_paper.py."""

import json
import os
import subprocess
import sys

import pytest

from bench import harness

CELL = "paper.mnist4.ppermute4"

SCRIPT = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
import pytest
from bench import calibrate
from bench.tests import tiny
from bench.tests.test_correctness_paper import _paper_fault
kind = {kind!r}
if kind == "control":
    _, workload, config = tiny.cell({cell!r}, nodes=4)
    rows = calibrate.readings({cell!r}, [11], 1, faults=(),
                              workload=workload, config=config,
                              emit=lambda _: None)
    values = next(r for r in rows if r["kind"] == "control")["values"]
    print(json.dumps([any(v > workload["limits"][k]
                          for k, v in values.items()), values]))
else:
    _paper_fault(pytest.MonkeyPatch(), kind)
    line = tiny.run({cell!r}, nodes=4)
    print(json.dumps([not line["correct"], line["compared"]]))
"""


def _four_devices(kind: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = SCRIPT.format(root=str(harness.ROOT),
                           src=str(harness.ROOT / "src"), kind=kind,
                           cell=CELL)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_control_is_not_correct():
    failed, values = _four_devices("control")
    assert failed, values


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "answer"])
def test_fault_is_not_correct(fault):
    failed, compared = _four_devices(fault)
    assert failed, compared
