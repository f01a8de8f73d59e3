"""Distributed-path equivalence: the GSPMD/shard_map gossip paths must equal
the host einsum on an 8-device mesh.  Runs in a SUBPROCESS because the forced
host-device count must be set before jax initializes (the main test process
keeps the single real device)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    src = os.environ["REPRO_SRC"]
    import sys; sys.path.insert(0, src)
    from repro.core import gossip, graphs
    from repro.core.mesh import make_mesh
    from repro.train import sharding, steps as steps_lib
    from repro.core import prox as prox_lib
    from repro.models.api import ModelConfig

    out = {}
    m = 8
    mesh = make_mesh((8, 1), ("data", "model"))
    rng = np.random.default_rng(0)

    # 1) einsum gossip under jit+mesh == host numpy
    x = rng.normal(size=(m, 64)).astype(np.float32)
    sched = graphs.b_connected_ring_schedule(m, b=2, seed=0)
    phi = sched.consensus_rounds(0, 3)
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
    mixed = jax.jit(lambda p, t: gossip.mix_stacked(p, t))(
        jnp.asarray(phi, jnp.float32), xs)
    out["einsum_err"] = float(np.abs(np.asarray(mixed) - phi @ x).max())

    # 2) shard_map ppermute banded gossip == dense ring matrix product
    # (PermutePhi generalizes the old ring-only shard_map path: any banded
    # product, here ring^2, lowers to one collective-permute per band)
    w2 = np.linalg.matrix_power(graphs.ring_matrix(m, 1.0 / 3.0), 2)
    offs, _ = gossip.band_decompose(w2)
    pphi = gossip.PermutePhi.from_dense(w2, offs, mesh, "data")
    ring_out = jax.jit(lambda p, t: gossip.mix_stacked(p, t))(pphi, xs)
    out["ring_err"] = float(np.abs(np.asarray(ring_out) - w2 @ x).max())

    # 3) sharded decentralized train step == single-device reference
    cfg = ModelConfig(name="tiny", arch_type="dense", num_layers=2,
                      d_model=32, num_heads=4, num_kv_heads=2, d_ff=64,
                      vocab_size=64, scan_layers=False)
    plan = sharding.MeshPlan(node_axes=("data",))
    bundle_sharded = steps_lib.build_train_step(
        cfg, prox_lib.l1(1e-4), m, plan=plan, mesh=mesh, donate=False)
    bundle_local = steps_lib.build_train_step(
        cfg, prox_lib.l1(1e-4), m, donate=False)
    state_s = bundle_sharded.init_state(jax.random.PRNGKey(0))
    state_l = bundle_local.init_state(jax.random.PRNGKey(0))
    toks = rng.integers(0, 64, size=(m, 2, 16)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}
    phi2 = jnp.asarray(sched.consensus_rounds(0, 2), jnp.float32)
    alpha = jnp.float32(0.1)
    big = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}
    state_s = bundle_sharded.snapshot_step(state_s, big)
    state_l = bundle_local.snapshot_step(state_l, big)
    new_s, ms = bundle_sharded.train_step(state_s, batch, phi2, alpha)
    new_l, ml = bundle_local.train_step(state_l, batch, phi2, alpha)
    diffs = [float(jnp.max(jnp.abs(a - b)))
             for a, b in zip(jax.tree.leaves(new_s.params),
                             jax.tree.leaves(new_l.params))]
    out["step_err"] = max(diffs)
    out["loss_err"] = abs(float(ms["loss"]) - float(ml["loss"]))
    out["devices"] = len(jax.devices())
    print(json.dumps(out))
""")


@pytest.mark.slow
def test_eight_device_equivalence():
    env = dict(os.environ)
    env["REPRO_SRC"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 8
    assert out["einsum_err"] < 1e-5, out
    assert out["ring_err"] < 1e-5, out
    assert out["step_err"] < 5e-5, out
    assert out["loss_err"] < 1e-5, out
