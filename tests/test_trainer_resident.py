"""Device-resident LM training: host/resident equivalence, O(1) transfers,
device sampling, stateful transports, realized-alpha semantics.

The module shares ONE ModelConfig + prox instance across tests so the
bundle cache (steps._BUNDLE_CACHE) and the runner's executor cache serve
every train_loop call from the same jitted steps."""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graphs, prox, runner
from repro.data.loader import LMLoader
from repro.models.api import ModelConfig
from repro.train import trainer
from repro.core.exec_spec import ExecSpec

TINY = ModelConfig(name="tiny-rt", arch_type="dense", num_layers=1,
                   d_model=32, num_heads=2, num_kv_heads=1, d_ff=64,
                   vocab_size=64)
PROX = prox.l1(1e-4)
M = 4
TOKENS = np.random.default_rng(0).integers(0, 64, size=2400).astype(np.int32)


def _loader(seed=1):
    return LMLoader(TOKENS, num_nodes=M, per_node_batch=2, seq_len=16,
                    seed=seed)


def _sched():
    return graphs.b_connected_ring_schedule(M, b=2, seed=0)


def _tc(**kw):
    base = dict(num_steps=13, snapshot_every=5, log_every=4, alpha=0.05,
                consensus_rounds=2, seed=0)
    base.update(kw)
    return trainer.TrainerConfig(**base)


def _max_param_diff(a, b):
    return max(float(jnp.max(jnp.abs(x - y))) for x, y in
               zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)))


@pytest.mark.parametrize("algorithm", ["dpsvrg", "dspg"])
def test_host_and_resident_histories_match(algorithm):
    tc = _tc(algorithm=algorithm)
    host = trainer.train_loop(TINY, PROX, _sched(), _loader(), tc)
    res = trainer.train_loop(TINY, PROX, _sched(), _loader(), tc, exec=ExecSpec(resident=True))
    assert host["step"] == res["step"]
    np.testing.assert_allclose(host["loss"], res["loss"], atol=1e-5)
    np.testing.assert_allclose(host["v_norm"], res["v_norm"], rtol=1e-4)
    assert host["wire_bytes"] == res["wire_bytes"]
    assert host["alpha"] == res["alpha"]
    assert _max_param_diff(host["final_state"], res["final_state"]) < 1e-5


def test_resident_transfers_are_o1_per_log_window():
    tc = _tc(num_steps=21, log_every=5)
    res = trainer.train_loop(TINY, PROX, _sched(), _loader(), tc, exec=ExecSpec(resident=True))
    n_windows = len(res["step"])           # 0, 5, 10, 15, 20
    assert n_windows == 5
    # ONE staging put for all chunks + the shard buffer; ONE pull per window
    ledger = res["transfers"]
    assert {k: ledger[k] for k in ("h2d", "d2h")} == \
        {"h2d": 1, "d2h": n_windows}
    # every step's (loss, v_norm) pair of f32s crosses exactly once
    assert ledger["bytes_d2h"] == 2 * 4 * tc.num_steps
    assert ledger["bytes_h2d"] > TOKENS.nbytes // 2


def test_resident_dispatch_is_transfer_free_under_xla_guard():
    """Chunk dispatches run under ``jax.transfer_guard("disallow")``: XLA
    faults on ANY implicit host<->device transfer inside the hot path, the
    runtime-level form of the O(1) claim (staging and window pulls happen
    outside the guarded dispatches via explicit device_put/get)."""
    old = runner._RESIDENT_DISPATCH_GUARD
    runner._RESIDENT_DISPATCH_GUARD = lambda: jax.transfer_guard("disallow")
    try:
        res = trainer.train_loop(TINY, PROX, _sched(), _loader(), _tc(), exec=ExecSpec(resident=True))
    finally:
        runner._RESIDENT_DISPATCH_GUARD = old
    assert np.isfinite(res["loss"]).all()


def test_device_sampling_is_seed_deterministic():
    tc = _tc()
    a = trainer.train_loop(TINY, PROX, _sched(), _loader(), tc, exec=ExecSpec(resident=True, sampling="device"))
    b = trainer.train_loop(TINY, PROX, _sched(), _loader(), tc, exec=ExecSpec(resident=True, sampling="device"))
    assert a["loss"] == b["loss"]
    assert a["transfers"]["h2d"] == 1      # not even batch starts staged
    c = trainer.train_loop(TINY, PROX, _sched(), _loader(),
                           dataclasses.replace(tc, seed=1), exec=ExecSpec(resident=True, sampling="device"))
    assert a["loss"] != c["loss"]


def test_compressed_transport_matches_on_both_paths():
    # stateful transport (error-feedback mix state in TrainState.mix_state)
    # works on the LM path — and identically on host and resident
    tc = _tc(gossip="compressed")
    host = trainer.train_loop(TINY, PROX, _sched(), _loader(), tc)
    res = trainer.train_loop(TINY, PROX, _sched(), _loader(), tc, exec=ExecSpec(resident=True))
    np.testing.assert_allclose(host["loss"], res["loss"], atol=1e-5)
    assert host["final_state"].mix_state is not None


def test_dspg_ignores_lr_schedule_with_warning():
    tc = _tc(algorithm="dspg", lr_schedule="cosine")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        hist = trainer.train_loop(TINY, PROX, _sched(), _loader(), tc)
    assert any("OVERRIDDEN" in str(w.message) for w in caught
               if w.category is RuntimeWarning)
    # the realized alpha column records the DSPG decaying step, not cosine
    expected = [tc.alpha / (k + 1) ** 0.5 for k in hist["step"]]
    np.testing.assert_allclose(hist["alpha"], expected, rtol=1e-12)


def test_vr_rule_records_scheduled_alpha():
    tc = _tc(lr_schedule="cosine", num_steps=9, log_every=4)
    hist = trainer.train_loop(TINY, PROX, _sched(), _loader(), tc)
    lr = trainer._lr_fn(tc)
    np.testing.assert_allclose(hist["alpha"],
                               [float(lr(s)) for s in hist["step"]])


def test_resident_rejects_iterators_and_device_sampling_on_host():
    it = iter(_loader())
    with pytest.raises(ValueError, match="LMLoader"):
        trainer.train_loop(TINY, PROX, _sched(), it, _tc(), exec=ExecSpec(resident=True))
    with pytest.raises(ValueError, match="resident"):
        trainer.train_loop(TINY, PROX, _sched(), _loader(), _tc(), exec=ExecSpec(sampling="device"))


def test_legacy_iterator_path_still_works():
    ld = _loader()

    def batches():
        for t, l in ld:
            yield {"tokens": t, "labels": l}

    hist = trainer.train_loop(TINY, PROX, _sched(), batches(), _tc())
    assert len(hist["loss"]) == 4 and np.isfinite(hist["loss"]).all()


def test_tracker_spec_receives_stream(tmp_path):
    import json
    path = tmp_path / "m.jsonl"
    tc = _tc(num_steps=9, log_every=4)
    hist = trainer.train_loop(TINY, PROX, _sched(), _loader(), tc, exec=ExecSpec(resident=True), tracker=f"jsonl:{path}")
    rows = [json.loads(l) for l in path.read_text().splitlines()]
    assert [r["step"] for r in rows[:-1]] == hist["step"]
    assert rows[-1]["summary"]["transfers"]["h2d"] == 1
    assert rows[-1]["summary"]["final_loss"] == hist["loss"][-1]
