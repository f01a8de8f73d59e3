"""Architecture ``dense_decoder``: a dense GQA decoder with rotary positions,
an optional sliding window and a SwiGLU MLP (h2o-danube, llama, mistral).

The program builds it as a ``dense`` ``ModelConfig``; its operations are
``bench/flops.py``'s dense count; the plain reference is
``bench/reference/dense_decoder.py``.
"""

from __future__ import annotations

from bench import flops
from bench.reference import dense_decoder as reference

# the program's parameter tree nests a layer's matmuls under "attn" and
# "ffn" and a norm's gain under "w"; the reference's does not
NESTING = ("attn", "ffn", "w")

# the sizes of a run on the CPU (``bench/tests/tiny.py``)
TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 256, "sliding_window": 24,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
        "tie_word_embeddings": False}

def program_config(config: dict):
    """The program's ``ModelConfig`` for the configuration's ``"model"``."""
    from repro.models.api import ModelConfig
    m = config["model"]
    return ModelConfig(
        name=config["name"], arch_type="dense",
        num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"], sliding_window=m["sliding_window"],
        rope_theta=m["rope_theta"], tie_embeddings=m["tie_word_embeddings"])


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward operations per token of one gradient evaluation."""
    return flops.decoder_train_flops_per_token(model, seq)
