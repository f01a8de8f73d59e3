"""Operations from shapes, the yardstick of the utilisation metrics.

Conventions for a dense decoder layer (attention and a gated MLP) and a
head of ``vocab`` rows, counted per trained token and per gradient
evaluation:

* every matmul parameter costs 6 operations per token (2 forward, 4
  backward); the head counts once, as a matmul, whether or not it is tied
  to the embedding; the embedding lookup costs nothing;
* causal attention scores and values cost 6 * s * d per token per layer:
  2 * 2 * (s / 2) * d forward for QK^T and PV over the s / 2 keys a causal
  query sees on average, times 3 for the backward pass; a sliding window w
  replaces s by min(s, w);
* norms, rotary positions, softmax, activations, the loss and the optimizer
  are left out, and recomputation (remat) is not counted.
"""

from __future__ import annotations


def decoder_matmul_params(model: dict) -> int:
    d = model["hidden_size"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = d // heads
    attn = d * heads * hd + 2 * d * kv * hd + heads * hd * d
    mlp = 3 * d * model["intermediate_size"]
    return model["num_hidden_layers"] * (attn + mlp) \
        + d * model["vocab_size"]


def decoder_train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward operations per token of one gradient evaluation."""
    ctx = min(seq, model.get("sliding_window") or seq)
    attn = 6 * ctx * model["hidden_size"] * model["num_hidden_layers"]
    return float(6 * decoder_matmul_params(model) + attn)
