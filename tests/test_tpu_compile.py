"""The Pallas kernels of the main paths compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered and compiled for the first chip of a
described (not attached) ``v5e:2x2`` topology, and the compiled program must
hold the kernel as a ``tpu_custom_call``.  That catches what interpret mode
cannot: block shapes the TPU tiling refuses, and kernels that need more
VMEM than the chip has.  Where no TPU topology can be described, the tests
skip.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.fused_update import kernel as fu_kernel
from repro.kernels.fused_update import ops as fu_ops
from repro.kernels.rmsnorm import kernel as rms_kernel

# h2o-danube-1.8b's published attention widths
DANUBE = dict(heads=32, kv_heads=8, head_dim=80, window=4096, d_model=2560)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("rule", ["svrg", "sgd"])
def test_fused_step_kernel_compiles_at_lm_width(one_chip, rule):
    m, d = 8, 131072
    m_pad, d_pad, _ = fu_ops.stacked_layout(m, d)
    n_streams = 4 if rule == "svrg" else 2
    buf = jax.ShapeDtypeStruct((m_pad, d_pad), jnp.float32,
                               sharding=one_chip)
    w = jax.ShapeDtypeStruct((m_pad, 128), jnp.float32, sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    fn = functools.partial(fu_kernel.fused_step_kernel_call, m=m, rule=rule,
                           prox_kind="l1", interpret=False)
    text = _compiled_text(fn, w, (buf,) * n_streams, scalar, scalar)
    assert "tpu_custom_call" in text


def test_rmsnorm_kernel_compiles_at_danube_width(one_chip):
    x = jax.ShapeDtypeStruct((4096, DANUBE["d_model"]), jnp.float32,
                             sharding=one_chip)
    wt = jax.ShapeDtypeStruct((DANUBE["d_model"],), jnp.float32,
                              sharding=one_chip)
    text = _compiled_text(
        functools.partial(rms_kernel.rmsnorm_kernel_call, interpret=False),
        x, wt)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("seq", [512, 100])
def test_flash_attention_compiles_at_danube_heads(one_chip, dtype, seq):
    hd = DANUBE["head_dim"]
    q = jax.ShapeDtypeStruct((1, seq, DANUBE["heads"], hd), dtype,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, seq, DANUBE["kv_heads"], hd), dtype,
                              sharding=one_chip)
    text = _compiled_text(
        functools.partial(fa_ops.flash_attention, causal=True,
                          sliding_window=DANUBE["window"], interpret=False),
        q, kv, kv)
    assert "tpu_custom_call" in text
