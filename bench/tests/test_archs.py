"""LM architectures are found by name: a configuration's key ``"arch"``
names ``bench/archs/<arch>.py``, and the driver, the small cells and the
reference's training rule take the model from that module alone."""

import dataclasses
import types

import pytest

from bench import harness
from bench.archs import dense_decoder
from bench.drivers import lm_train
from bench.tests import tiny

LM = "lm.danube2.dpsvrg"
DANUBE = "danube1.8b-2L-m2"


def _danube(**model) -> dict:
    config = harness.load_config(DANUBE)
    return dict(config, model=dict(config["model"], **model))


# what lm_train.model_config built before the architectures had modules
@pytest.mark.parametrize("config, want", [
    (_danube(), dict(
        name=DANUBE, arch_type="dense", num_layers=2, d_model=2560,
        num_heads=32, num_kv_heads=8, d_ff=6912, vocab_size=32000,
        sliding_window=4096, rope_theta=10000.0, tie_embeddings=False)),
    (_danube(**dense_decoder.TINY), dict(
        name=DANUBE, arch_type="dense", num_layers=2, d_model=64,
        num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
        sliding_window=24, rope_theta=10000.0, tie_embeddings=False)),
], ids=["published", "tiny"])
def test_dense_decoder_builds_the_parents_model_config(config, want):
    from repro.models.api import ModelConfig
    got = harness.load_arch(config).program_config(config)
    assert dataclasses.asdict(got) == dataclasses.asdict(ModelConfig(**want))


@pytest.mark.parametrize("arch", [None, "no_such_arch", "../reference"])
def test_a_configuration_without_a_known_arch_fails_at_load(arch):
    config = {k: v for k, v in harness.load_config(DANUBE).items()
              if k != "arch"}
    if arch is not None:
        config["arch"] = arch
    job = harness.load_workload(LM)["job"]
    with pytest.raises(ValueError, match='"arch"'):
        harness.load_arch(config)
    with pytest.raises(ValueError, match='"arch"'):
        lm_train.Setup.build(config, job, 7)
    with pytest.raises(ValueError, match='"arch"'):
        lm_train.flops_per_step(config, job)


def _tied_decoder(calls: list) -> types.ModuleType:
    """An architecture that no file of the benchmark knows: a dense decoder
    whose head is the embedding, with sizes of its own."""
    dense = dense_decoder.reference

    def init_params(model, seed, precision):
        calls.append(("reference.init_params", model["hidden_size"]))
        return dense.init_params(model, seed, precision)

    def loss_fn(params, tokens, labels, model):
        calls.append(("reference.loss_fn", "lm_head" in params))
        return dense.loss_fn(params, tokens, labels, model)

    def program_config(config):
        calls.append(("program_config", config["model"]["hidden_size"]))
        return dense_decoder.program_config(config)

    def train_flops_per_token(model, seq):
        calls.append(("train_flops_per_token", model["hidden_size"]))
        return dense_decoder.train_flops_per_token(model, seq)

    arch = types.ModuleType("tied_decoder")
    arch.NESTING = dense_decoder.NESTING
    arch.TINY = dict(dense_decoder.TINY, hidden_size=48,
                     intermediate_size=96, tie_word_embeddings=True)
    arch.program_config = program_config
    arch.train_flops_per_token = train_flops_per_token
    arch.reference = types.SimpleNamespace(init_params=init_params,
                                           loss_fn=loss_fn)
    return arch


def test_a_second_architecture_is_new_files_only(fresh, monkeypatch):
    calls: list = []
    arch = _tied_decoder(calls)
    load_config, load_arch = harness.load_config, harness.load_arch
    monkeypatch.setattr(harness, "load_config",
                        lambda name: dict(load_config(name),
                                          arch="tied_decoder"))
    monkeypatch.setattr(harness, "load_arch",
                        lambda config: arch
                        if config["arch"] == "tied_decoder"
                        else load_arch(config))

    _, _, config = tiny.cell(LM)
    assert config["model"] == dict(load_config(DANUBE)["model"], **arch.TINY)
    line = tiny.run(LM)
    assert line["correct"], line["compared"]
    assert ("program_config", 48) in calls
    assert ("train_flops_per_token", 48) in calls
    assert ("reference.init_params", 48) in calls
    assert ("reference.loss_fn", False) in calls      # the head is tied
    assert not any(name == "reference.loss_fn" and untied
                   for name, untied in calls)
