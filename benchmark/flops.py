"""Operations a training step *requires*, per token — the numerator of ``mfu.train``.

Required means what the forward and backward passes need once: 3 x forward (the backward
pass costs twice the forward), forward = 2 x matmul parameters (the tied head counted,
it is a matmul) + causal attention at half the square. Recomputation under a remat policy
is NOT counted (the program's ``train_utils.get_model_tflops`` adds it and counts
attention as the full square: right for its own report, wrong for utilization).
"""

from __future__ import annotations

from .weights import count_parameters, model_dims


def attention_forward_flops_per_token(cfg: dict, sequence_length: int) -> float:
    """QK^T and PV of causal attention: a token at position t reads t + 1 keys, so the mean
    over a sequence of length s is (s + 1) / 2 keys; 2 matmuls x 2 flops x heads x head_dim
    per key. Packed documents attend less than this; the count takes the sequence as one
    document (an upper bound of the attention term, which is the smaller term)."""
    m = model_dims(cfg)
    keys = (sequence_length + 1) / 2
    return m["n_layer"] * 4.0 * m["n_head"] * m["head_dim"] * keys


def forward_flops_per_token(cfg: dict, sequence_length: int) -> float:
    counts = count_parameters(cfg)
    matmul_params = cfg["n_layer"] * counts["per_layer_matmul"] + counts["table"]
    return 2.0 * matmul_params + attention_forward_flops_per_token(cfg, sequence_length)


def train_flops_per_token(cfg: dict, sequence_length: int) -> float:
    return 3.0 * forward_flops_per_token(cfg, sequence_length)
