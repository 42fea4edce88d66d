"""Required operations of a dense decoder's training step (SwiGLU,
MHA/GQA, untied head), counted from the configuration file's sizes.  A
configuration names its counting module under ``costs``.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Parameters that sit in a matrix multiplication: q, k, v, o, the
    three SwiGLU matrices of every layer, and the output head.  The
    embedding table is a gather and the norms are elementwise: neither
    counts."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg.get("num_key_value_heads", h)
    dh = cfg.get("head_dim", d // h)
    f = cfg["intermediate_size"]
    per_layer = d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    norms = (2 * cfg["num_hidden_layers"] + 1) * d
    return matmul_params(cfg) + cfg["vocab_size"] * d + norms


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations the forward and backward passes REQUIRE per token
    (PaLM appendix B, as ``bench_model.py`` counted them, minus the
    embedding gather): 6 per matmul parameter, plus causal attention --
    QK^T and PV are 2 * 2 * S * d a token forward over the full square,
    half of it under the causal mask, three times that with the
    backward.  Recomputed operations (remat) are not counted."""
    d_attn = cfg["num_attention_heads"] * cfg.get(
        "head_dim", cfg["hidden_size"] // cfg["num_attention_heads"])
    attn = 3 * (4 * seq_len * d_attn) / 2 * cfg["num_hidden_layers"]
    return 6.0 * matmul_params(cfg) + attn
