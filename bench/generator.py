"""The one general traffic generator: every mix is a data file under
``bench/traffic/`` that this module reads.

Training mixes (``"kind": "train"``) yield batches of token rows.  Each
row belongs to one of ``topics`` topics; a topic is a random permutation
of the vocabulary's ranks, and a token is drawn from the topic by a
bounded Zipf law of exponent ``token_zipf`` over those ranks (1.0 is the
word-frequency law of natural text; 0 is uniform).  Topics are drawn by
a Zipf law of exponent ``topic_zipf`` over their popularity ranks, and
every ``drift_every`` steps two adjacent popularity ranks swap, so the
routing load drifts the way it does over a training run (0 = no drift).
Everything is drawn from the seed alone: the same seed gives the same
batches, and every row of every batch differs.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Iterator

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str, root: str = HERE) -> Dict:
    """The mix ``name``: ``<root>/traffic/<name>.json``."""
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _zipf_cdf(n: int, exponent: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** exponent
    c = np.cumsum(w)
    return c / c[-1]


class TopicStream:
    """Yields ``{"tokens": (global_batch, seq_len + 1) int32}``."""

    def __init__(self, mix: Dict, vocab_size: int, seed: int):
        self.mix = mix
        self.vocab = vocab_size
        self.rng = np.random.default_rng([seed, 0x7e5])
        n_topics = max(int(mix["topics"]), 1)
        self.perms = np.stack([self.rng.permutation(vocab_size)
                               for _ in range(n_topics)]).astype(np.int32)
        self.order = np.arange(n_topics)     # popularity rank -> topic
        self.token_cdf = _zipf_cdf(vocab_size, mix["token_zipf"])
        self.topic_cdf = _zipf_cdf(n_topics, mix["topic_zipf"])
        self.step = 0

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        return self

    def __next__(self) -> Dict[str, np.ndarray]:
        return self.next_batch()

    def next_batch(self) -> Dict[str, np.ndarray]:
        m = self.mix
        every = int(m.get("drift_every", 0))
        if every and self.step and self.step % every == 0 \
                and len(self.order) > 1:
            r = self.rng.integers(0, len(self.order) - 1)
            self.order[[r, r + 1]] = self.order[[r + 1, r]]
        self.step += 1
        b, s = m["global_batch"], m["seq_len"] + 1
        ranks = np.searchsorted(self.topic_cdf, self.rng.random(b))
        topics = self.order[np.minimum(ranks, len(self.order) - 1)]
        tok_ranks = np.minimum(
            np.searchsorted(self.token_cdf, self.rng.random((b, s))),
            self.vocab - 1)
        tokens = self.perms[topics[:, None], tok_ranks]
        return {"tokens": tokens.astype(np.int32)}
