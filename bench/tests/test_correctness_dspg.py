"""The DSPG LM cell at sizes a test run can hold: each fault the cell can
have comes out not correct (planted as for the DPSVRG cell, whose tests
also take this cell's sound run and control), and the checked job's state
is gone before DSPG's one-step job starts."""

import jax
import pytest

from bench import calibrate
from bench.tests import tiny
from bench.tests.test_correctness_lm import _lm_fault

DSPG = "lm.danube2.dspg"


@pytest.mark.parametrize("fault", calibrate.FAULTS)
def test_fault_is_not_correct(fresh, monkeypatch, fault):
    _lm_fault(monkeypatch, fault)
    line = tiny.run(DSPG)
    assert not line["correct"], line["compared"]


def test_dspg_drops_the_checked_job_before_its_one_step_job(fresh,
                                                             monkeypatch):
    """DSPG's first gradient comes from a second, one-step job; at the
    cell's widths the checked job's trees and that job's do not fit one
    chip together, so none of the checked job's arrays may be alive when it
    starts."""
    import gc
    import weakref

    from bench.drivers import lm_train
    _, workload, config = tiny.cell(DSPG)
    setup = lm_train.Setup.build(config, workload["job"], 11)
    train = lm_train.Setup.train
    checked, alive = [], []

    def spy(self, num_steps, tracker=None):
        if checked:
            gc.collect()
            alive.append(sum(ref() is not None for ref in checked))
        hist = train(self, num_steps, tracker)
        if not checked:
            checked.extend(weakref.ref(leaf) for leaf in
                           jax.tree.leaves(hist["final_state"]))
        return hist

    monkeypatch.setattr(lm_train.Setup, "train", spy)
    got = setup.program_answers()
    assert checked and alive == [0]
    assert set(got["grad"]) == set(got["change"])
