"""Operations a training step of `joyai_llm_flash` *requires*, per token — the numerator of
``mfu.joyai_train`` — and their split by kind.

Required means what the forward and backward passes need once: 3 x forward, no
recomputation. Forward, per token (blocks = the main blocks and the multi-token-prediction
module's one):

  mla_projections   2 x (q_a, q_b, kv_a, kv_b, o parameters), every block
  scores_values     2 x heads x (nope + rope + v) x the keys a token attends (``attended_keys``:
                    a token at place t of its document reads t + 1), every block
  dense_mlp         2 x 3 d n_inner, the leading dense blocks
  router, shared_expert   2 x their parameters, every layer of experts
  routed_experts    2 x one routed expert's parameters x the token-slots a token really sends to
                    the experts HELD HERE (``routed_slots_per_token``, from the program's counter:
                    about top_k x held / experts, not top_k), every layer of experts
  mtp_projection    2 x 2 d d
  head              2 x vocabulary rows held x d, once a pass: twice with the module

``attended_keys`` is NOT the packed row taken as one document, as the dense cells' count takes
it ((row + 1) / 2: an upper bound of what is their smaller term): here attention is most of a
block, and documents of median 600 tokens attend a third of that. `mean_attended_keys` reads
it from the traffic file's law of document lengths, packed into rows as the corpus is.
"""

from __future__ import annotations

import numpy as np

from .traffic import _lengths
from .weights_joyai_flash import count_parameters, model_dims

KINDS = ("mla_projections", "scores_values", "dense_mlp", "router", "shared_expert", "routed_experts", "mtp_projection", "head")


def mean_attended_keys(document_tokens: dict, row_length: int, documents: int = 8192) -> float:
    """Mean, over the tokens of rows of ``row_length`` packed from documents of the law's
    lengths (each with its eos; a document that a row's end cuts goes on as a new one in the
    next row), of the keys a token attends: its place in its document, counted from 1."""
    lengths = np.random.default_rng(0).permutation(_lengths(documents, document_tokens)) + 1
    pairs = tokens = 0
    room = row_length
    for length in lengths.tolist():
        while length:
            piece = min(length, room)
            pairs += piece * (piece + 1) // 2
            tokens += piece
            length -= piece
            room = room - piece or row_length
    return pairs / tokens


def forward_flops_per_token_by_kind(cfg: dict, attended_keys: float, routed_slots_per_token: float) -> dict:
    """{kind: forward operations a token}, all blocks together (`KINDS`)."""
    m, counts = model_dims(cfg), count_parameters(cfg)
    kinds = counts["layers_of_kind"]
    blocks = kinds["D"] + kinds["E"] + kinds["P"]
    expert_layers = kinds["E"] + kinds["P"]
    return {
        "mla_projections": blocks * 2.0 * counts["attention_matmul"],
        "scores_values": blocks * 2.0 * m["n_head"] * (m["nope"] + m["rope"] + m["v"]) * attended_keys,
        "dense_mlp": kinds["D"] * 2.0 * counts["dense_mlp"],
        "router": expert_layers * 2.0 * counts["router"],
        "shared_expert": expert_layers * 2.0 * counts["shared_expert"],
        "routed_experts": expert_layers * 2.0 * counts["routed_expert"] * routed_slots_per_token,
        "mtp_projection": kinds["P"] * 2.0 * counts["mtp_projection"],
        "head": (1 + kinds["P"]) * 2.0 * m["vocab"] * m["d"],
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
