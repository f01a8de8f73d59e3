"""The comparison that decides ``correct`` for the LM cells, at sizes a
test run can hold.

A sound run comes out correct; the control (the plain
reference one precision below the configuration's, in the program's place)
does not; and a run with the timed path broken underneath comes out not
correct, once for each fault the cell can have: a step that returns its
state unchanged, half the batch left out, the exchange between nodes left
out, an answer altered where it is produced.  Each run skips the look for a
chip and is otherwise a whole run of the harness.
"""

import jax
import pytest

from bench import calibrate
from bench.tests import tiny

LM = "lm.danube2.dpsvrg"
DSPG = "lm.danube2.dspg"


@pytest.mark.parametrize("name", [LM, DSPG])
def test_sound_run_is_correct(fresh, name):
    line = tiny.run(name)
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["compiles_in_window"] == 0


@pytest.mark.parametrize("name", [LM, DSPG])
def test_control_is_not_correct(name):
    _, workload, config = tiny.cell(name)
    rows = calibrate.readings(name, [11], 1, faults=(), workload=workload,
                              config=config, emit=lambda _: None)
    control = next(r for r in rows if r["kind"] == "control")
    limits = workload["limits"]
    assert any(v > limits[k] for k, v in control["values"].items()), control


def _lm_fault(monkeypatch, fault):
    from repro.core import algorithm, compression
    from repro.models import transformer
    loss_fn = transformer.loss_fn
    if fault == "unchanged":
        def update(params, v, phi, alpha, prox, mix_fn):
            mix_fn(phi, params)
            return params
        monkeypatch.setattr(algorithm, "prox_gossip_update", update)
    elif fault == "half_batch":
        def half(cfg):
            fn = loss_fn(cfg)
            return lambda params, batch: fn(params, {
                k: v[:v.shape[0] // 2] for k, v in batch.items()})
        monkeypatch.setattr(transformer, "loss_fn", half)
    elif fault == "no_mix":
        monkeypatch.setattr(compression, "mix_with_state",
                            lambda phi, tree, state: (tree, state))
    else:
        # the reported loss is the first half-batch's; the step is sound
        def answer(cfg):
            fn = loss_fn(cfg)

            def altered(params, batch):
                full = fn(params, batch)
                part = fn(params, {k: v[:v.shape[0] // 2]
                                   for k, v in batch.items()})
                return full + jax.lax.stop_gradient(part - full)
            return altered
        monkeypatch.setattr(transformer, "loss_fn", answer)


@pytest.mark.parametrize("fault", calibrate.FAULTS)
def test_lm_fault_is_not_correct(fresh, monkeypatch, fault):
    _lm_fault(monkeypatch, fault)
    line = tiny.run(LM)
    assert not line["correct"], line["compared"]


