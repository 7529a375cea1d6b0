"""Operations a training step of `lfm2_moe` *requires*, per token — the numerator of
``mfu.lfm2_train`` — and their split by kind.

Required means what the forward and backward passes need once: 3 x forward, no
recomputation. Forward, per token:

  conv_projections      2 x (in-projection + out-projection parameters), every ``conv`` block
  conv_gates_taps       the convolution's own multiply-adds, counted and named: the gate before
                        the taps (d multiplies), the taps (2 x taps x d) and the gate after (d)
  attention_projections 2 x (q, k, v, o parameters), every ``full_attention`` block
  scores_values         2 x heads x 2 head x the keys a token attends (``attended_keys``: a token
                        at place t of its document reads t + 1), every ``full_attention`` block
  dense_mlp             2 x 3 d n_inner, the leading dense blocks
  router                2 x d experts, every layer of experts
  routed_experts        2 x one routed expert's parameters x the token-slots a token really sends
                        to the experts HELD HERE (``routed_slots_per_token``, from the program's
                        counter: about top_k x held / experts, not top_k); no shared expert
  head                  2 x vocabulary rows held x d (the tied table; the embedding is a lookup)

``attended_keys`` is read from the traffic file's law of document lengths packed into rows as
the corpus is, over as many documents as the run's corpus has (`corpus_documents`: the
generator's own arithmetic; `flops_joyai_flash.mean_attended_keys` does the packing).
"""

from __future__ import annotations

import math

from .flops_joyai_flash import mean_attended_keys  # noqa: F401  (the packing rule, with the count of documents given)
from .weights_lfm2_moe import count_parameters, model_dims

KINDS = ("conv_projections", "conv_gates_taps", "attention_projections", "scores_values", "dense_mlp", "router", "routed_experts", "head")


def corpus_documents(traffic: dict, seconds: float, rows: int, sequence_length: int) -> int:
    """Documents of distinct lengths in a run's corpus, as `traffic.write_packed_corpus` and the
    driver size it: the steps a run may take, two to spare, a row of ``sequence_length + 1``."""
    steps = traffic["warmup_steps"] + int(math.ceil(seconds * traffic["max_steps_per_second"])) + 2
    law = traffic["document_tokens"]
    mean_length = law["median"] * math.exp(law["sigma"] ** 2 / 2)
    return max(int((steps + 2) * rows * (sequence_length + 1) / mean_length), 1)


def forward_flops_per_token_by_kind(cfg: dict, attended_keys: float, routed_slots_per_token: float) -> dict:
    """{kind: forward operations a token}, all blocks together (`KINDS`)."""
    m, counts = model_dims(cfg), count_parameters(cfg)
    kinds = counts["layers_of_kind"]
    return {
        "conv_projections": kinds["conv"] * 2.0 * counts["conv_matmul"],
        "conv_gates_taps": kinds["conv"] * (2.0 * m["taps"] + 2.0) * m["d"],
        "attention_projections": kinds["full_attention"] * 2.0 * counts["attention_matmul"],
        "scores_values": kinds["full_attention"] * 2.0 * m["n_head"] * 2 * m["head_dim"] * attended_keys,
        "dense_mlp": kinds["dense"] * 2.0 * counts["dense_mlp"],
        "router": kinds["experts"] * 2.0 * counts["router"],
        "routed_experts": kinds["experts"] * 2.0 * counts["routed_expert"] * routed_slots_per_token,
        "head": 2.0 * m["vocab"] * m["d"],
    }


def even_routed_slots_per_token(cfg: dict) -> float:
    """What a router that spreads evenly sends here: top_k x held / experts (for a count
    made before any run; a run reads the program's counter)."""
    m = model_dims(cfg)
    return m["top_k"] * m["held"] / m["experts"]


def train_flops_per_token(cfg: dict, attended_keys: float, routed_slots_per_token: float | None = None) -> float:
    if routed_slots_per_token is None:
        routed_slots_per_token = even_routed_slots_per_token(cfg)
    return 3.0 * sum(forward_flops_per_token_by_kind(cfg, attended_keys, routed_slots_per_token).values())
