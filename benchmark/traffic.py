"""The one general generator of inputs: a packed pretraining corpus and an open-loop arrival
schedule, both from a traffic file's parameters and ``--seed``.

Every seed gets the SAME multiset of sizes and arrival gaps (stratified quantiles of the
stated distributions), so that a run's amount of work does not depend on its seed; their
order, like the token values and the weights, is drawn from the seed.
"""

from __future__ import annotations

import math
import statistics
from dataclasses import dataclass

import numpy as np

_NORMAL = statistics.NormalDist()


def lognormal_quantiles(count: int, median: float, sigma: float, low: int, high: int) -> np.ndarray:
    """``count`` whole numbers at the stratified quantiles (i + 0.5) / count of a lognormal,
    clipped to [low, high]."""
    values = [
        median * math.exp(sigma * _NORMAL.inv_cdf((i + 0.5) / count)) for i in range(count)
    ]
    return np.clip(np.rint(values), low, high).astype(np.int64)


def exponential_gaps(count: int, total_seconds: float) -> np.ndarray:
    """``count`` gaps at the stratified quantiles of an exponential law (a Poisson process's
    gaps), scaled so that they sum to ``total_seconds``."""
    raw = np.array([-math.log(1.0 - (i + 0.5) / count) for i in range(count)])
    return raw * (total_seconds / raw.sum())


def _lengths(count: int, law: dict) -> np.ndarray:
    if law["distribution"] != "lognormal":
        raise ValueError(f"unknown length distribution {law['distribution']!r}")
    return lognormal_quantiles(count, law["median"], law["sigma"], law["min"], law["max"])


@dataclass
class Arrival:
    index: int
    due_s: float  # from the start of the schedule
    prompt_ids: list
    max_new_tokens: int
    measured: bool  # due inside the measured window (after the ramp)


def open_loop_schedule(
    traffic: dict, seed: int, seconds: float, vocab_size: int, length_scale: float = 1.0, tail_seconds: float = 0.0
) -> tuple[list, float]:
    """Arrivals of a ramp (fills the slots, not measured), then of the measured window of
    ``seconds``, then of a tail of ``tail_seconds`` at the same rate (not measured: a traced
    run traces it); returns them with the ramp's length. ``length_scale`` shrinks the
    lengths for the CPU rehearsal only."""
    rng = np.random.default_rng(seed)  # the order of gaps and sizes, and the token values
    rate, ramp_s = traffic["rate_per_s"], traffic["ramp_seconds"]
    arrivals: list[Arrival] = []
    phases = [(0.0, ramp_s, False), (ramp_s, seconds, True)]
    if tail_seconds > 0:
        phases.append((ramp_s + seconds, tail_seconds, False))
    for phase_start, phase_seconds, measured in phases:
        count = max(int(round(rate * phase_seconds)), 1)
        gaps = rng.permutation(exponential_gaps(count, phase_seconds))
        due = phase_start + np.cumsum(gaps) - gaps  # the first request of a phase is due at once
        prompts = rng.permutation(_lengths(count, traffic["prompt_tokens"]))
        outputs = rng.permutation(_lengths(count, traffic["output_tokens"]))
        for i in range(count):
            prompt_len = max(int(prompts[i] * length_scale), 4)
            output_len = max(int(outputs[i] * length_scale), 2)
            arrivals.append(
                Arrival(
                    index=len(arrivals),
                    due_s=float(due[i]),
                    # no shared prefix: every prompt is its own random tokens (0 is pad/eos)
                    prompt_ids=rng.integers(1, vocab_size, size=prompt_len).tolist(),
                    max_new_tokens=output_len,
                    measured=measured,
                )
            )
    return arrivals, ramp_s


def percentile(samples, q: float) -> float:
    """The q-quantile by rank: the smallest sample with at least q of the samples at or below."""
    ordered = sorted(samples)
    if not ordered:
        raise ValueError("no samples")
    return float(ordered[min(max(math.ceil(q * len(ordered)) - 1, 0), len(ordered) - 1)])


def write_packed_corpus(prefix: str, traffic: dict, seed: int, vocab: int, eos: int, num_tokens: int, builder_cls) -> int:
    """A Megatron ``.bin``/``.idx`` pair of documents whose lengths follow the traffic file's
    law (heavy-tailed) and whose tokens follow a Zipf law over the vocabulary, so that there
    is something to learn in a few steps. ``builder_cls`` is the program's indexed-dataset
    writer (the corpus format is the program's). Returns the number of documents."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab, dtype=np.float64)
    probabilities = (1.0 / ranks) / np.sum(1.0 / ranks)
    tokens = rng.choice(np.arange(1, vocab), size=num_tokens, p=probabilities)
    law = traffic["document_tokens"]
    mean_length = law["median"] * math.exp(law["sigma"] ** 2 / 2)
    count = max(int(num_tokens / mean_length), 1)
    lengths = rng.permutation(_lengths(count, law))
    builder = builder_cls(prefix + ".bin", dtype=np.uint16)
    start = documents = 0
    while start < num_tokens:
        length = int(lengths[documents % count])
        builder.add_item(np.append(tokens[start : start + length], eos))
        builder.end_document()
        start += length
        documents += 1
    builder.finalize(prefix + ".idx")
    return documents
