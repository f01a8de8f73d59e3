"""Per-architecture smoke tests (deliverable f): a REDUCED variant of each
assigned architecture runs one forward/train step on CPU with asserted
output shapes and no NaNs, plus prefill/decode exactness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import multimodal, transformer


def _batch(cfg, b=2, l=16, seed=0):
    rng = np.random.default_rng(seed)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, l)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, l)), jnp.int32),
    }
    if cfg.frontend == "vision_stub":
        batch["image_embeds"] = jnp.asarray(
            multimodal.fake_image_patches(b, cfg.d_model, cfg.image_tokens))
    if cfg.frontend == "audio_stub":
        batch["audio_frames"] = jnp.asarray(
            multimodal.fake_audio_frames(b, cfg.d_model, cfg.encoder_seq))
    return batch


@pytest.mark.parametrize("arch", configs.ARCHITECTURES)
def test_smoke_forward_and_train_step(arch):
    cfg = configs.smoke_variant(configs.get_config(arch))
    assert cfg.num_layers <= 2 and cfg.d_model <= 512
    assert cfg.moe_experts <= 4
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    b, l = 2, 16
    batch = _batch(cfg, b, l)
    logits, aux = transformer.forward(
        cfg, params, batch["tokens"],
        image_embeds=batch.get("image_embeds"),
        audio_frames=batch.get("audio_frames"))
    assert logits.shape == (b, l, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits))), "NaN/inf in logits"

    loss_fn = transformer.loss_fn(cfg)
    loss, grads = jax.value_and_grad(loss_fn)(params, batch)
    assert bool(jnp.isfinite(loss))
    for leaf in jax.tree.leaves(grads):
        assert bool(jnp.all(jnp.isfinite(leaf)))
    # one SGD step changes the loss (the graph is actually wired)
    new_params = jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)
    loss2 = loss_fn(new_params, batch)
    assert float(loss2) != float(loss)


@pytest.mark.parametrize("arch", configs.ARCHITECTURES)
def test_smoke_prefill_decode_exactness(arch):
    cfg = configs.smoke_variant(configs.get_config(arch))
    if cfg.moe_experts:  # lossless routing so decode == forward exactly
        cfg = cfg.scaled(capacity_factor=float(cfg.moe_experts) / cfg.moe_top_k + 1)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    b, l = 2, 16
    batch = _batch(cfg, b, l)
    kw = {k: batch[k] for k in ("image_embeds", "audio_frames") if k in batch}
    logits, cache = transformer.prefill(cfg, params, batch["tokens"], **kw)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    step_logits, cache = transformer.decode_step(cfg, params, cache, nxt)
    full, _ = transformer.forward(
        cfg, params, jnp.concatenate([batch["tokens"], nxt[:, None]], 1), **kw)
    np.testing.assert_allclose(np.asarray(full[:, l - 1]), np.asarray(logits),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(full[:, l]), np.asarray(step_logits),
                               atol=2e-4)


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "gemma2-9b",
                                  "llama4-scout-17b-a16e"])
def test_smoke_windowed_decode_past_window(arch):
    """Decode must stay exact after the ring buffer wraps (pos > window)."""
    cfg = configs.smoke_variant(configs.get_config(arch))
    if cfg.moe_experts:
        cfg = cfg.scaled(capacity_factor=float(cfg.moe_experts) / cfg.moe_top_k + 1)
    # window 16 (smoke), prompt 20 > window: wrap immediately
    params = transformer.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    l = 20
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, l)), jnp.int32)
    logits, cache = transformer.prefill(cfg, params, toks, max_len=l + 8)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    step_logits, cache = transformer.decode_step(cfg, params, cache, nxt)
    full, _ = transformer.forward(cfg, params,
                                  jnp.concatenate([toks, nxt[:, None]], 1))
    np.testing.assert_allclose(np.asarray(full[:, l]),
                               np.asarray(step_logits), atol=2e-4)


def test_full_configs_match_assignment():
    """The FULL configs carry the exact assigned hyper-parameters."""
    expect = {
        "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
        "h2o-danube-1.8b": (24, 2560, 32, 8, 6912, 32000),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202048),
        "stablelm-12b": (40, 5120, 32, 8, 13824, 100352),
        "whisper-base": (6, 512, 8, 8, 2048, 51865),
        "xlstm-350m": (24, 1024, 4, 4, 0, 50304),
        "minicpm-2b": (40, 2304, 36, 36, 5760, 122753),
        "llava-next-mistral-7b": (32, 4096, 32, 8, 14336, 32000),
        "gemma2-9b": (42, 3584, 16, 8, 14336, 256000),
        "llama4-scout-17b-a16e": (48, 5120, 40, 8, 8192, 202048),
    }
    for arch, (nl, dm, nh, kv, dff, vs) in expect.items():
        cfg = configs.get_config(arch)
        assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                cfg.d_ff, cfg.vocab_size) == (nl, dm, nh, kv, dff, vs), arch
    # MoE assignments
    assert configs.get_config("jamba-1.5-large-398b").moe_experts == 16
    assert configs.get_config("jamba-1.5-large-398b").moe_top_k == 2
    assert configs.get_config("llama4-maverick-400b-a17b").moe_experts == 128
    assert configs.get_config("llama4-scout-17b-a16e").moe_experts == 16


def test_long_context_applicability_flags():
    runs = {a for a in configs.ARCHITECTURES
            if configs.get_config(a).supports_long_context}
    assert runs == {"jamba-1.5-large-398b", "h2o-danube-1.8b",
                    "llama4-maverick-400b-a17b", "xlstm-350m", "gemma2-9b",
                    "llama4-scout-17b-a16e"}
    shape = configs.INPUT_SHAPES["long_500k"]
    for a in configs.ARCHITECTURES:
        ok, reason = configs.shape_applicable(configs.get_config(a), shape)
        assert ok == (a in runs)
        if not ok:
            assert "full-attention" in reason


@pytest.mark.parametrize("arch", configs.ARCHITECTURES)
def test_depth_variant_keeps_published_widths(arch):
    """``depth_variant`` (``launch.train``/``launch.serve --layers``) cuts
    only the depth: every other field is the published config's."""
    import dataclasses

    cfg = configs.get_config(arch)
    cut = configs.depth_variant(cfg, 1)
    assert cut.num_layers == 1
    same = {f.name for f in dataclasses.fields(cfg)} - {"name", "num_layers"}
    assert {f: getattr(cut, f) for f in same} == \
        {f: getattr(cfg, f) for f in same}
    assert configs.depth_variant(cfg, cfg.num_layers) is cfg
    params = jax.eval_shape(lambda: transformer.init_params(
        cut, jax.random.PRNGKey(0)))
    assert len(params["layers"]) == 1
    for bad in (0, cfg.num_layers + 1):
        with pytest.raises(ValueError):
            configs.depth_variant(cfg, bad)
