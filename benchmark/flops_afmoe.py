"""Operations a training step of `afmoe` *requires*, per token — the numerator of
``mfu.afmoe_train`` — and their split by kind.

Required means what the forward and backward passes need once: 3 x forward, no
recomputation. Forward, per token:

  attention_projections 2 x (q, k, v, the gate's and the out-projection's parameters), every block
  scores_values_window  2 x heads x 2 head x the keys a token attends in a window layer
                        (``min(place, sliding_window)``: a token at place t of its document
                        reads t, at most the window), every ``sliding_attention`` block
  scores_values_full    the same over the keys of a full layer (``place``), every
                        ``full_attention`` block
  dense_mlp             2 x 3 d n_inner, the leading dense blocks
  router                2 x d experts, every layer of experts
  shared_expert         2 x 3 d f_shared, every layer of experts
  routed_experts        2 x one routed expert's parameters x the token-slots a token really sends
                        to the experts HELD HERE (``routed_slots_per_token``, from the program's
                        counter: about top_k x held / experts, not top_k)
  head                  2 x vocabulary rows held x d (the untied table; the embedding is a lookup)

Both counts of keys are read from the traffic file's law of document lengths packed into rows
as the corpus is, over as many documents as the run's corpus has (`attended_keys`; the packing
rule is `flops_joyai_flash.mean_attended_keys`'s) — over the cell's own corpus law, not over
half the square. `visited_block_pairs` counts, over the same packed rows, the (query block, key
block) pairs a layer of each kind needs: what the program's counters
``splash_blocks_visited_window`` / ``splash_blocks_visited_full`` should read a layer and row.
"""

from __future__ import annotations

import numpy as np

from .flops_lfm2_moe import corpus_documents  # noqa: F401  (the run's corpus, as the driver sizes it)
from .traffic import _lengths
from .weights_afmoe import count_parameters, model_dims

KINDS = (
    "attention_projections", "scores_values_window", "scores_values_full", "dense_mlp", "router", "shared_expert", "routed_experts", "head",
)


def packed_pieces(document_tokens: dict, row_length: int, documents: int) -> list:
    """Rows of ``row_length`` packed from documents of the law's lengths, each row the lengths of
    its pieces (each document with its eos; a document that a row's end cuts goes on as a new one
    in the next row: `flops_joyai_flash.mean_attended_keys`' rule). The last, unfilled row is left out."""
    lengths = np.random.default_rng(0).permutation(_lengths(documents, document_tokens)) + 1
    rows, row, room = [], [], row_length
    for length in lengths.tolist():
        while length:
            piece = min(length, room)
            row.append(piece)
            length -= piece
            room -= piece
            if not room:
                rows.append(row)
                row, room = [], row_length
    return rows or [row]


def attended_keys(document_tokens: dict, row_length: int, documents: int, window: int) -> tuple[float, float]:
    """(keys a token attends in a full layer, in a layer under ``window``), means over the tokens
    of the packed rows: its place in its piece counted from 1, and that capped at the window."""
    full = windowed = tokens = 0
    for row in packed_pieces(document_tokens, row_length, documents):
        for piece in row:
            full += piece * (piece + 1) // 2
            capped = min(piece, window)
            windowed += capped * (capped + 1) // 2 + (piece - capped) * window
            tokens += piece
    return full / tokens, windowed / tokens


def visited_block_pairs(document_tokens: dict, row_length: int, documents: int, window: int, block: int) -> tuple[float, float, float]:
    """(block pairs a row a full layer needs, a layer under ``window`` needs, pairs under the
    diagonal), means over the packed rows: query block i needs key block j <= i where a piece
    spans both and, under the window, j is within its reach (``(i - j - 1) * block + 1 <
    window``) — `ops/attention.document_block_pairs`' rule, written again here."""
    n = row_length // block
    reach = (window - 2) // block + 1
    full = windowed = rows = 0
    for row in packed_pieces(document_tokens, row_length, documents):
        ends = np.cumsum(row)
        first, last = (ends - np.asarray(row)) // block, (ends - 1) // block  # a piece's first and last block
        low = np.full(n, n)  # the lowest block that shares a piece with block i
        for a, b in zip(first.tolist(), last.tolist()):
            low[a : b + 1] = np.minimum(low[a : b + 1], a)
        index = np.arange(n)
        full += int(np.sum(index - low + 1))
        windowed += int(np.sum(index - np.maximum(low, index - reach) + 1))
        rows += 1
    return full / rows, windowed / rows, n * (n + 1) / 2


def forward_flops_per_token_by_kind(cfg: dict, full_keys: float, window_keys: float, routed_slots_per_token: float) -> dict:
    """{kind: forward operations a token}, all blocks together (`KINDS`)."""
    m, counts = model_dims(cfg), count_parameters(cfg)
    kinds = counts["layers_of_kind"]
    core = 2.0 * m["n_head"] * 2 * m["head_dim"]
    return {
        "attention_projections": m["n_layer"] * 2.0 * counts["attention_matmul"],
        "scores_values_window": kinds["sliding_attention"] * core * window_keys,
        "scores_values_full": kinds["full_attention"] * core * full_keys,
        "dense_mlp": kinds["dense"] * 2.0 * counts["dense_mlp"],
        "router": kinds["experts"] * 2.0 * counts["router"],
        "shared_expert": kinds["experts"] * 2.0 * counts["shared_expert"],
        "routed_experts": kinds["experts"] * 2.0 * counts["routed_expert"] * routed_slots_per_token,
        "head": 2.0 * m["vocab"] * m["d"],
    }


def even_routed_slots_per_token(cfg: dict) -> float:
    """What a router that spreads evenly sends here: top_k x held / experts (for a count
    made before any run; a run reads the program's counter)."""
    m = model_dims(cfg)
    return m["top_k"] * m["held"] / m["experts"]


def train_flops_per_token(cfg: dict, full_keys: float, window_keys: float, routed_slots_per_token: float | None = None) -> float:
    if routed_slots_per_token is None:
        routed_slots_per_token = even_routed_slots_per_token(cfg)
    return 3.0 * sum(forward_flops_per_token_by_kind(cfg, full_keys, window_keys, routed_slots_per_token).values())
