"""bench/flops.py against the hand count for danube at 2 layers."""

import pytest

from bench import flops, harness
from bench.drivers import lm_train

DANUBE = harness.load_config("danube1.8b-2L-m2")["model"]


def test_matmul_parameters_of_two_danube_layers():
    # per layer: q 2560^2, k and v 2560x640 each, o 2560^2, MLP 3x2560x6912
    assert 69_468_160 == 2 * 2560 ** 2 + 2 * 2560 * 640 + 3 * 2560 * 6912
    # two layers and the (tied) head once as a matmul
    assert flops.decoder_matmul_params(DANUBE) == 220_856_320


def test_train_flops_per_token_at_seq_512():
    # 6N + causal attention 6 * s * d per layer
    want = 6 * 220_856_320 + 2 * 6 * 512 * 2560
    assert want == 1_340_866_560
    assert flops.decoder_train_flops_per_token(DANUBE, 512) == want


def test_sliding_window_caps_the_attention_context():
    model = dict(DANUBE, sliding_window=256)
    got = flops.decoder_train_flops_per_token(model, 512)
    assert got == 6 * 220_856_320 + 2 * 6 * 256 * 2560


@pytest.mark.parametrize("algorithm, tflop", [("dpsvrg", 11.20),
                                              ("dspg", 5.49)])
def test_required_operations_per_step(algorithm, tflop):
    job = dict(harness.load_workload("lm.danube2.dpsvrg")["job"],
               algorithm=algorithm)
    got = lm_train.flops_per_step(harness.load_config("danube1.8b-2L-m2"),
                                  job)
    assert got / 1e12 == pytest.approx(tflop, abs=0.01)
