"""A federation of packed token sequences from a seed and a mix file.

Every client is a silo with its own vocabulary habits. Documents have a
lognormal length (``doc_length``: median and sigma, cut at the sequence
length) and are packed one after another, a separator id between them, into
sequences of ``sequence_length`` tokens, as pre-training pipelines pack them.
Token ids follow Zipf's law (``zipf_exponent``) over the ids the
configuration's vocabulary slice leaves after ``pad_id`` 0 and the separator
1: rank ``r`` of client ``c`` is id ``2 + (r - 1 + offset_c) mod M`` with
``offset_c`` drawn under ``rank_offset_max``, so the silos share a vocabulary
and differ in which of its common words each uses most (non-IID in the label
sense too: the label is the next token). ``x [N, T] int32``; ``y`` is the next token,
and ``pad_id`` after a sequence's last one, which the loss leaves out.

Client sizes come from the mix (``counts``), never from ``--seed``: every
seed meets the same shapes and the same amount of work, round for round.
The unigram law alone is learnable (entropy well under ``log`` of the
vocabulary), which is what the runner's loss checks rest on.
"""

from __future__ import annotations

import numpy as np

PAD, SEPARATOR, FIRST = 0, 1, 2


def generate(mix: dict, config: dict, seed: int):
    """``(x [N, T] int32, y [N, T] int32, parts {client: rows}, counts)``."""
    clients = int(mix["clients"])
    if mix["counts"]["law"] != "equal":
        raise ValueError(f"unknown counts law {mix['counts']['law']!r}")
    per_client = int(mix["counts"]["per_client"])
    counts = np.full(clients, per_client, np.int64)
    n, t = clients * per_client, int(mix["sequence_length"])
    words = int(config["classes"]) - FIRST
    rng = np.random.default_rng(seed)
    law = np.arange(1, words + 1, dtype=np.float64) ** -float(
        mix["zipf_exponent"])
    ranks = np.searchsorted(np.cumsum(law / law.sum()), rng.random((n, t)))
    ranks = np.minimum(ranks, words - 1)            # 0-based ranks
    offsets = rng.integers(0, int(mix["rank_offset_max"]), clients)
    x = (FIRST + (ranks + np.repeat(offsets, per_client)[:, None]) % words
         ).astype(np.int32)
    # document ends: more lengths than any sequence can hold, cumulated
    median, sigma = mix["doc_length"]["median"], mix["doc_length"]["sigma"]
    most = 8 + 4 * t // int(median)
    lengths = np.clip(rng.lognormal(np.log(median), sigma, (n, most)),
                      1, t).astype(np.int64)
    ends = np.cumsum(lengths + 1, axis=1) - 1       # a separator a document
    rows = np.repeat(np.arange(n), most)[ends.ravel() < t]
    x[rows, ends.ravel()[ends.ravel() < t]] = SEPARATOR
    y = np.concatenate([x[:, 1:], np.full((n, 1), PAD, np.int32)], axis=1)
    parts = {c: np.arange(c * per_client, (c + 1) * per_client)
             for c in range(clients)}
    return x, y, parts, counts
