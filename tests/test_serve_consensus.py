"""Training -> serving bridge: a train_loop checkpoint loads through
serve.consensus as the node-averaged x̄ (with per-node disagreement), and
launch.serve serves requests straight from --ckpt-dir."""

import numpy as np
import pytest

from repro.core import graphs, prox
from repro.data.loader import LMLoader
from repro.models.api import ModelConfig
from repro.serve import consensus
from repro.train import trainer

TINY = ModelConfig(name="tiny-consensus", arch_type="dense", num_layers=1,
                   d_model=16, num_heads=1, num_kv_heads=1, d_ff=32,
                   vocab_size=64)
M = 4


def _make_ckpt(tmp_path, cfg, steps=6):
    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=4_000).astype(np.int32)
    ld = LMLoader(toks, num_nodes=M, per_node_batch=1, seq_len=8, seed=1)
    sched = graphs.b_connected_ring_schedule(M, b=2, seed=0)
    # ONE consensus round: on the 4-ring two rounds mix to exact uniform
    # averaging, which would leave zero per-node disagreement to observe
    tc = trainer.TrainerConfig(num_steps=steps, snapshot_every=steps,
                               log_every=steps, alpha=0.05,
                               consensus_rounds=1, seed=0,
                               ckpt_dir=str(tmp_path), ckpt_every=steps)
    trainer.train_loop(cfg, prox.l1(1e-5), sched, ld, tc)
    return str(tmp_path)


@pytest.fixture(scope="module")
def ckpt_dir(tmp_path_factory):
    return _make_ckpt(tmp_path_factory.mktemp("ckpt"), TINY)


def test_consensus_params_average_and_disagreement(ckpt_dir):
    import jax

    params, info = consensus.consensus_params(ckpt_dir, TINY)
    assert info.num_nodes == M and info.step == 6
    assert info.algorithm == "dpsvrg"
    assert len(info.node_dist) == M

    # x̄ really is the node-axis mean of the stacked checkpoint params,
    # and the disagreement matches a by-hand recomputation
    import glob
    import os
    arrays = np.load(os.path.join(
        sorted(glob.glob(os.path.join(ckpt_dir, "step_*")))[-1],
        "arrays.npz"))
    stacked = {k: arrays[k] for k in arrays.files
               if k.startswith("state/.params/")}
    flat_mean = {k: v.mean(axis=0) for k, v in stacked.items()}
    served = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        key = "/".join(str(getattr(e, "key", getattr(e, "idx", e)))
                       for e in path)
        served["state/.params/" + key] = np.asarray(leaf)
    assert set(served) == set(flat_mean)
    for k in flat_mean:
        np.testing.assert_allclose(served[k], flat_mean[k], rtol=1e-5,
                                   atol=1e-6)

    sq = np.zeros(M)
    for k, v in stacked.items():
        d = v - flat_mean[k][None]
        sq += (d.reshape(M, -1) ** 2).sum(axis=1)
    np.testing.assert_allclose(info.node_dist, np.sqrt(sq), rtol=1e-6)
    # nodes actually trained on different shards: disagreement is nonzero
    assert max(info.node_dist) > 0


def test_consensus_params_feed_the_engine(ckpt_dir):
    from repro.serve.engine import ResidentEngine
    from repro.serve.scheduler import Request

    params, _ = consensus.consensus_params(ckpt_dir, TINY)
    eng = ResidentEngine(TINY, params, max_slots=2, max_len=32)
    rng = np.random.default_rng(0)
    for i in range(3):
        eng.submit(Request(uid=i, tokens=rng.integers(
            0, TINY.vocab_size, size=5).astype(np.int32),
            max_new_tokens=4))
    outs = eng.run_until_done()
    assert sorted(outs) == [0, 1, 2]
    assert all(len(v) == 4 for v in outs.values())


def test_consensus_missing_checkpoint(tmp_path):
    with pytest.raises(FileNotFoundError):
        consensus.consensus_params(str(tmp_path), TINY)


def test_launch_serve_from_checkpoint(tmp_path, capsys):
    """End-to-end: decentralized LM run -> checkpoint -> launch.serve
    --ckpt-dir serves requests off the consensus average."""
    from repro import configs
    from repro.launch import serve as launch_serve

    arch = "minicpm-2b"
    cfg = configs.smoke_variant(configs.get_config(arch))
    ckpt = _make_ckpt(tmp_path, cfg, steps=2)
    summary = launch_serve.main([
        "--arch", arch, "--ckpt-dir", ckpt, "--slots", "2",
        "--max-len", "48", "--requests", "3", "--prompt-len", "8",
        "--new", "4", "--verify-host"])
    assert summary["requests"] == 3 and summary["tokens"] == 3 * 4
    assert summary["tokens_per_s"] > 0
    out = capsys.readouterr().out
    assert "consensus ckpt step=2 m=4" in out
    assert "tok/s" in out
    assert "all 3 outputs equal the host batcher's" in out


def test_launch_serve_stream_mode(tmp_path):
    from repro.launch import serve as launch_serve

    summary = launch_serve.main([
        "--arch", "minicpm-2b", "--stream", "--requests", "4",
        "--rate", "500", "--slots", "2", "--max-len", "48",
        "--prompt-len", "8", "--new", "4"])
    assert summary["requests"] == 4
    assert {"ttft_ms", "tpot_ms", "tokens_per_s"} <= set(summary)
