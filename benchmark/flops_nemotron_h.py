"""Operations a training step of the `nemotron_h` tower *requires*, per token — the numerator
of ``mfu.tower_train`` — and their split by kind of layer.

Required means what the forward and backward passes need once: 3 x forward, no
recomputation. Forward, per token:

  M   2 x (in-projection + out-projection parameters) + the convolution (2 K conv_dim) + the
      selective scan as the chunked (SSD) algorithm at the configuration's chunk L needs it:
      C.B over the causal half of a chunk (G N (L + 1)), the masked product with X
      (H P (L + 1)), the chunk's state (2 H P N) and the entering state's part (2 H P N)
  *   2 x (q, k, v, o parameters) + causal attention at half the square, the row taken as one
      document (an upper bound of this term, as in ``benchmark/flops.py``)
  E   2 x (router + shared expert parameters) + 2 x one routed expert's parameters x the
      token-slots a token really sends to the experts HELD HERE (``routed_slots_per_token``,
      from the program's counter: about top_k x held / experts, not top_k)
  head  2 x vocabulary rows held x d (the untied head; the embedding is a lookup)
"""

from __future__ import annotations

from .weights_nemotron_h import count_parameters, model_dims


def scan_forward_flops_per_token(cfg: dict) -> float:
    m = model_dims(cfg)
    heads_width = m["m_heads"] * m["m_width"]
    return (m["m_groups"] * m["m_state"] + heads_width) * (m["chunk"] + 1) + 4.0 * heads_width * m["m_state"]


def forward_flops_per_token_by_kind(cfg: dict, sequence_length: int, routed_slots_per_token: float) -> dict:
    """{"M": ..., "E": ..., "*": ..., "head": ...}: forward operations a token, all layers of a
    kind together."""
    m, counts = model_dims(cfg), count_parameters(cfg)
    kinds = counts["layers_of_kind"]
    mamba = 2.0 * counts["mamba_matmul"] + 2.0 * m["conv_kernel"] * m["conv_dim"] + scan_forward_flops_per_token(cfg)
    attention = 2.0 * counts["attention_matmul"] + 4.0 * m["n_head"] * m["head_dim"] * (sequence_length + 1) / 2
    experts = 2.0 * (counts["router"] + counts["shared_expert"]) + 2.0 * counts["routed_expert"] * routed_slots_per_token
    return {
        "M": kinds["M"] * mamba,
        "E": kinds["E"] * experts,
        "*": kinds["*"] * attention,
        "head": 2.0 * m["vocab"] * m["d"],
    }


def even_routed_slots_per_token(cfg: dict) -> float:
    """What a router that spreads evenly sends here: top_k x held / experts (for a count
    made before any run; a run reads the program's counter)."""
    m = model_dims(cfg)
    return m["top_k"] * m["held"] / m["experts"]


def train_flops_per_token(cfg: dict, sequence_length: int, routed_slots_per_token: float | None = None) -> float:
    if routed_slots_per_token is None:
        routed_slots_per_token = even_routed_slots_per_token(cfg)
    return 3.0 * sum(forward_flops_per_token_by_kind(cfg, sequence_length, routed_slots_per_token).values())
