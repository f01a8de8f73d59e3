"""Per-kernel shape/dtype sweeps vs. the pure-jnp oracles (interpret=True)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.fused_update import kernel as fu_kernel
from repro.kernels.fused_update import ops as fu_ops, ref as fu_ref


# ---------------------------------------------------------------------------
# fused_update
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows", [8, 24, 64])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_svrg_step_sweep(rows, dtype):
    rng = np.random.default_rng(rows)
    shp = (rows, fu_kernel.BLOCK_COLS)
    x, gn, gs, mu = (jnp.asarray(rng.normal(size=shp), dtype)
                     for _ in range(4))
    for alpha in (0.0, 0.05, 1.0):
        out = fu_ops.svrg_step(x, gn, gs, mu, alpha)
        ref = fu_ref.svrg_step_ref(x, gn, gs, mu, alpha)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6)


@pytest.mark.parametrize("rows", [8, 40])
def test_mix_prox_sweep(rows):
    rng = np.random.default_rng(rows + 100)
    shp = (rows, fu_kernel.BLOCK_COLS)
    qs, qu, qd = (jnp.asarray(rng.normal(size=shp), jnp.float32)
                  for _ in range(3))
    for (w0, w1, w2, th) in [(1.0, 0.0, 0.0, 0.0), (1 / 3, 1 / 3, 1 / 3, 0.01),
                             (0.5, 0.25, 0.25, 0.3)]:
        out = fu_ops.mix_prox(qs, qu, qd, w0, w1, w2, th)
        ref = fu_ref.mix_prox_ref(qs, qu, qd, w0, w1, w2, th)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6)


def test_flatten_tree_roundtrip():
    tree = {"a": jnp.arange(10.0).reshape(2, 5),
            "b": {"c": jnp.ones((3,), jnp.bfloat16),
                  "d": jnp.zeros((7, 3), jnp.float32)}}
    buf, aux = fu_ops.flatten_tree(tree)
    assert buf.shape[1] == fu_kernel.BLOCK_COLS
    assert buf.shape[0] % fu_kernel.BLOCK_ROWS == 0
    back = fu_ops.unflatten_tree(buf, aux)
    for k1, k2 in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert k1.dtype == k2.dtype
        np.testing.assert_allclose(np.asarray(k1, np.float32),
                                   np.asarray(k2, np.float32))


def test_fused_inner_step_composition():
    """kernel(svrg) |> kernel(mix_prox) == unfused jnp inner step."""
    rng = np.random.default_rng(7)
    shp = (16, fu_kernel.BLOCK_COLS)
    x, gn, gs, mu, xu, xd = (jnp.asarray(rng.normal(size=shp), jnp.float32)
                             for _ in range(6))
    alpha, lam = 0.1, 0.02
    q = fu_ops.svrg_step(x, gn, gs, mu, alpha)
    out = fu_ops.mix_prox(q, xu, xd, 1 / 3, 1 / 3, 1 / 3, alpha * lam)
    ref = fu_ref.inner_step_ref(x, gn, gs, mu, xu, xd, 1 / 3, 1 / 3, 1 / 3,
                                alpha, alpha * lam)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

CASES = [
    # b, h, kv, sq, sk, hd, causal, window, softcap, bq, bk
    (1, 4, 2, 128, 128, 64, True, None, None, 64, 64),
    (2, 4, 4, 256, 256, 32, True, None, None, 128, 128),
    (1, 8, 2, 128, 128, 64, True, 64, None, 64, 64),     # GQA 4x + SWA
    (1, 2, 1, 128, 256, 64, True, None, 50.0, 64, 64),   # softcap, sk > sq
    (1, 2, 2, 192, 192, 16, True, 32, None, 64, 64),     # narrow window
    (1, 1, 1, 64, 64, 24, True, None, None, 32, 32),     # hd pad (24 -> 24, %8==0)
]


@pytest.mark.parametrize("case", CASES)
def test_flash_attention_sweep(case):
    b, h, kv, sq, sk, hd, causal, win, cap, bq, bk = case
    rng = np.random.default_rng(abs(hash(case)) % 2 ** 31)
    q = jnp.asarray(rng.normal(size=(b, sq, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, sk, kv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, sk, kv, hd)), jnp.float32)
    out = fa_ops.flash_attention(q, k, v, causal=causal, sliding_window=win,
                                 softcap=cap, block_q=bq, block_k=bk)
    ref = fa_ref.attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, sliding_window=win,
        softcap=cap).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_bf16():
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(1, 128, 2, 32)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 128, 2, 32)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 128, 2, 32)), jnp.bfloat16)
    out = fa_ops.flash_attention(q, k, v, block_q=64, block_k=64)
    ref = fa_ref.attention_ref(q.transpose(0, 2, 1, 3).astype(jnp.float32),
                               k.transpose(0, 2, 1, 3).astype(jnp.float32),
                               v.transpose(0, 2, 1, 3).astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref.transpose(0, 2, 1, 3)),
                               atol=3e-2)


def test_flash_attention_ragged_q_padding():
    """Sq not a multiple of block_q exercises the wrapper's padding path."""
    rng = np.random.default_rng(12)
    q = jnp.asarray(rng.normal(size=(1, 100, 2, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 100, 1, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 100, 1, 32)), jnp.float32)
    out = fa_ops.flash_attention(q, k, v, block_q=64, block_k=50)
    ref = fa_ref.attention_ref(q.transpose(0, 2, 1, 3),
                               k.transpose(0, 2, 1, 3),
                               v.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(8, 128), (3, 7, 64), (5, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    from repro.kernels.rmsnorm import ops as rn_ops, ref as rn_ref
    rng = np.random.default_rng(sum(shape))
    x = jnp.asarray(rng.normal(size=shape) * 3, dtype)
    w = jnp.asarray(rng.normal(size=shape[-1]) * 0.1, dtype)
    out = rn_ops.rmsnorm(x, w)
    refo = rn_ref.rmsnorm_ref(x.reshape(-1, shape[-1]),
                              w).reshape(shape)
    tol = 1e-6 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(refo, np.float32), atol=tol)


def test_rmsnorm_matches_model_norm():
    """The kernel must be drop-in for models.common.rms_norm."""
    from repro.kernels.rmsnorm import ops as rn_ops
    from repro.models import common
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 9, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=32) * 0.05, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(rn_ops.rmsnorm(x, w)),
        np.asarray(common.rms_norm(x, w)), atol=1e-6)


# ---------------------------------------------------------------------------
# fused resident step (gossip mix + variance-reduced correction + prox)
# ---------------------------------------------------------------------------

def _fused_case(m, d, seed, n_streams):
    rng = np.random.default_rng(seed)
    m_pad, d_pad, _ = fu_ops.stacked_layout(m, d)
    streams = []
    for _ in range(n_streams):
        buf = np.zeros((m_pad, d_pad), np.float32)
        buf[:m, :d] = rng.normal(size=(m, d))
        streams.append(jnp.asarray(buf))
    w = rng.dirichlet(np.ones(m), size=m).astype(np.float32)  # row-stochastic
    return fu_ops.pad_mix_matrix(jnp.asarray(w), m_pad), tuple(streams)


@pytest.mark.parametrize("rule", fu_ref.FUSED_RULES)
@pytest.mark.parametrize("prox_kind", fu_ref.FUSED_PROXES)
@pytest.mark.parametrize("m,d", [(8, 30), (5, 200)])
def test_fused_step_interpret_bitwise_vs_ref(rule, prox_kind, m, d):
    """Interpret-mode kernel output is BITWISE identical to the jitted
    whole-buffer oracle: both sides run ``ref.fused_step_math`` (per tile
    vs whole buffer) under jit, so XLA makes identical contraction
    decisions and the fused path can be swapped in with zero numeric
    drift."""
    n_streams = 4 if rule == "svrg" else 2
    w, streams = _fused_case(m, d, seed=d + len(prox_kind), n_streams=n_streams)
    run = jax.jit(functools.partial(
        fu_ops.fused_step_buf, m=m, rule=rule, prox_kind=prox_kind),
        static_argnames=("impl",))
    out = run(w, streams, 0.07, 0.02, impl="interpret")
    ref = run(w, streams, 0.07, 0.02, impl="ref")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # padding invariant: prox(0) = 0, so padded rows/cols stay exactly zero
    np.testing.assert_array_equal(np.asarray(out)[m:], 0.0)
    np.testing.assert_array_equal(np.asarray(out)[:, streams[0].shape[1]:],
                                  0.0)


def test_fused_step_interpret_bitwise_vs_ref_large_d():
    """The LM-sized shape (d >= 1e5) walks many (8, 1024) tiles.  XLA may
    round the per-tile dot and the whole-buffer dot differently at this
    size, so tile-wise kernel vs whole-buffer oracle agree to a few f32
    ulps of the output's magnitude (|z| < 4 here, ulp 2.4e-7), not
    bitwise."""
    m, d = 8, 131072
    w, streams = _fused_case(m, d, seed=0, n_streams=4)
    run = jax.jit(functools.partial(
        fu_ops.fused_step_buf, m=m, rule="svrg", prox_kind="l1"),
        static_argnames=("impl",))
    out = np.asarray(run(w, streams, 0.05, 0.01, impl="interpret"))
    ref = np.asarray(run(w, streams, 0.05, 0.01, impl="ref"))
    np.testing.assert_allclose(out, ref, rtol=0, atol=1e-6)


def test_fused_resident_step_tree_matches_manual():
    """Tree-level wrapper == dense numpy prox(W @ (x - alpha v)) per leaf,
    with multi-leaf trees flattened through one stacked buffer."""
    rng = np.random.default_rng(3)
    m, alpha, lam = 4, 0.1, 0.02
    tree = lambda: {"a": jnp.asarray(rng.normal(size=(m, 6)), jnp.float32),
                    "b": jnp.asarray(rng.normal(size=(m, 2, 3)), jnp.float32)}
    x, gn, gs, mu = tree(), tree(), tree(), tree()
    w = jnp.asarray(rng.dirichlet(np.ones(m), size=m), jnp.float32)
    out = fu_ops.fused_resident_step(w, x, (gn, gs, mu), alpha, lam,
                                     rule="svrg", prox_kind="l1")
    for k in ("a", "b"):
        q = (np.asarray(x[k]) - alpha * (np.asarray(gn[k]) - np.asarray(gs[k])
                                         + np.asarray(mu[k]))).reshape(m, -1)
        z = np.asarray(w, np.float64) @ q
        want = np.sign(z) * np.maximum(np.abs(z) - alpha * lam, 0.0)
        np.testing.assert_allclose(np.asarray(out[k]).reshape(m, -1), want,
                                   atol=1e-6)
    assert jax.tree.structure(out) == jax.tree.structure(x)


def test_stacked_layout_narrow_tiles_and_auto_fallback():
    """Paper-scale d=30 buffers get a narrow (8, 128) tile — not the legacy
    flatten_tree (8, 1024) tile that is >99% padding — and kernel='auto'
    falls back to the unfused XLA body below FUSED_MIN_D where the fused
    path cannot win."""
    m_pad, d_pad, block_cols = fu_ops.stacked_layout(8, 30)
    assert (m_pad, d_pad, block_cols) == (8, 128, 128)
    # the legacy single-shape layout pads the SAME buffer to 1024 columns
    legacy, _ = fu_ops.flatten_tree({"x": jnp.zeros((8, 30))})
    assert legacy.shape[1] == fu_kernel.BLOCK_COLS == 1024
    assert 1 - 30 / legacy.shape[1] > 0.97           # >97% padding (legacy)
    assert 1 - 30 / d_pad < 0.80                     # bounded overhead (new)
    # large-d keeps full-width tiles; odd m rounds up to the sublane tile
    assert fu_ops.stacked_layout(8, 131072) == (8, 131072, 1024)
    assert fu_ops.stacked_layout(5, 200) == (8, 256, 256)
    # the auto-mode fallback pin: small d never routes to the fused step
    assert not fu_ops.fused_wins(30)
    assert fu_ops.fused_wins(fu_ops.FUSED_MIN_D)
    assert fu_ops.tree_node_dim({"a": jnp.zeros((8, 30)),
                                 "b": jnp.zeros((8, 2, 5))}) == 40


def test_pad_mix_matrix_keeps_padded_rows_inert():
    """Padded W rows/cols are zero, so phantom nodes mix to exactly zero
    and never leak into live rows."""
    w = jnp.full((5, 5), 0.2, jnp.float32)
    wp = fu_ops.pad_mix_matrix(w, 8)
    assert wp.shape == (8, 128)
    np.testing.assert_array_equal(np.asarray(wp[:5, :5]), np.asarray(w))
    assert float(jnp.abs(wp[5:]).sum()) == 0.0
    assert float(jnp.abs(wp[:, 5:]).sum()) == 0.0
