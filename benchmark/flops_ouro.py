"""Operations a training step of `ouro` (a looped language model) *requires*, per token — the
numerator of ``mfu.ouro_train`` — and their split by kind.

Required means what the forward and backward passes need once: 3 x forward, no recomputation.
The loop is the model: every block is applied `total_ut_steps` times a token and the head is
read as often, so both count that often (``6 x (passes x L x block + passes x head) x tokens``
for the matmuls). Forward, per token:

  attention_projections   2 x (q, k, v, o parameters), every block APPLICATION (passes x blocks)
  scores_values           2 x heads x 2 head x the keys a token attends (``attended_keys``: a token at
                          place t of its document reads t + 1), every block application
  mlp                     2 x 3 d n_inner, every block application
  head                    2 x vocabulary x d, once a pass (the embedding is a lookup)
  exit_gate               2 x d, once a pass: counted and named, five orders under the head

``attended_keys`` is read from the traffic file's law of document lengths packed into rows as the
corpus is, over as many documents as the run's corpus has (``corpus_documents``, the generator's
own arithmetic; ``flops_joyai_flash.mean_attended_keys`` does the packing).
"""

from __future__ import annotations

from .flops_joyai_flash import mean_attended_keys  # noqa: F401  (the packing rule, with the count of documents given)
from .flops_lfm2_moe import corpus_documents  # noqa: F401  (the corpus's size from the traffic file, as the driver sizes it)
from .weights_ouro import count_parameters, model_dims

KINDS = ("attention_projections", "scores_values", "mlp", "head", "exit_gate")


def forward_flops_per_token_by_kind(cfg: dict, attended_keys: float) -> dict:
    """{kind: forward operations a token}, all passes together (`KINDS`)."""
    m, counts = model_dims(cfg), count_parameters(cfg)
    applications, passes = counts["block_applications"], counts["passes"]
    return {
        "attention_projections": applications * 2.0 * counts["attention_matmul"],
        "scores_values": applications * 2.0 * m["n_head"] * 2 * m["head_dim"] * attended_keys,
        "mlp": applications * 2.0 * counts["mlp_matmul"],
        "head": passes * 2.0 * counts["table"],
        "exit_gate": passes * 2.0 * m["d"],
    }


def train_flops_per_token(cfg: dict, attended_keys: float) -> float:
    return 3.0 * sum(forward_flops_per_token_by_kind(cfg, attended_keys).values())
