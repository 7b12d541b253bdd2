"""Token traffic for the training cells, read from a mix file.

One generator serves every mix.  A mix file (``bench/mixes/<name>.json``)
gives its ``kind`` and parameters:

  packed_documents  Documents packed back to back into each sequence, the
                    last one cut at the sequence end.  Lengths are
                    log-normal (``doc_len.median``, ``doc_len.sigma``),
                    clipped to [``doc_len.min``, seq].  Each document takes
                    one of ``topics`` topics, uniformly; a topic draws its
                    tokens by Zipf(``zipf``) rank over a permutation of the
                    vocabulary of its own.
  zipf_bigram       The system's own synthetic stream
                    (``repro.data.synthetic.zipf_token_stream``), copied:
                    token ids drawn by Zipf(``zipf``) rank, and with
                    probability ``successor_p`` a token replaced by the
                    seeded successor of the token drawn before it.  No
                    documents.

For ``packed_documents`` the lengths come from ``length_seed`` of the mix,
so every run seed sees the same documents' sizes in the same places; the
run seed chooses the topics, their vocabularies and the tokens.  A batch is a function of (seed, step)
alone, so the same seed gives the same inputs whatever ran before.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def _doc_lengths(rng, seq: int, spec: dict) -> np.ndarray:
    """Lengths of the documents that fill ``seq`` tokens (the last cut)."""
    mu, sigma = np.log(spec["median"]), spec["sigma"]
    lo = spec.get("min", 1)
    out, total = [], 0
    while total < seq:
        n = int(np.clip(round(rng.lognormal(mu, sigma)), lo, seq))
        n = min(n, seq - total)
        out.append(n)
        total += n
    return np.asarray(out, np.int64)


class _Rows:
    """A batch of ``row(step, r)`` sequences of ``seq + 1`` tokens, and the
    Zipf(``zipf``) rank distribution both kinds draw from."""

    def __init__(self, mix: dict, seed: int, vocab: int, num_micro: int,
                 mb: int, seq: int):
        self.mix, self.seed, self.vocab = mix, int(seed), int(vocab)
        self.shape = (num_micro, mb)
        self.seq = seq
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        cdf = np.cumsum(ranks ** -float(mix["zipf"]))
        self.cdf = cdf / cdf[-1]

    def ranks(self, rng) -> np.ndarray:
        """``seq + 1`` Zipf ranks from 0."""
        r = np.searchsorted(self.cdf, rng.random(self.seq + 1), side="right")
        return np.minimum(r, self.vocab - 1)

    def __call__(self, step: int) -> Dict[str, np.ndarray]:
        m, b = self.shape
        rows = np.stack([self.row(step, r) for r in range(m * b)])
        rows = rows.reshape(m, b, self.seq + 1)
        return {"tokens": rows[..., :-1].copy(),
                "labels": rows[..., 1:].copy(),
                "label_mask": np.ones((m, b, self.seq), np.float32)}


class PackedDocuments(_Rows):
    def __init__(self, mix: dict, seed: int, *shape):
        super().__init__(mix, seed, *shape)
        rng = np.random.default_rng([self.seed, 0x70])
        self.perms = np.stack([rng.permutation(self.vocab).astype(np.int32)
                               for _ in range(int(mix["topics"]))])

    def documents(self, step: int, row: int):
        """(lengths, topics) of one sequence of ``seq + 1`` tokens."""
        lens = _doc_lengths(
            np.random.default_rng([int(self.mix["length_seed"]), step, row]),
            self.seq + 1, self.mix["doc_len"])
        rng = np.random.default_rng([self.seed, step, row])
        topics = rng.integers(0, self.perms.shape[0], size=len(lens))
        return lens, topics

    def row(self, step: int, row: int) -> np.ndarray:
        lens, topics = self.documents(step, row)
        ranks = self.ranks(np.random.default_rng([self.seed, step, row, 1]))
        topic_of = np.repeat(topics, lens)
        return self.perms[topic_of, ranks]


class ZipfBigram(_Rows):
    def __init__(self, mix: dict, seed: int, *shape):
        super().__init__(mix, seed, *shape)
        rng = np.random.default_rng([self.seed, 0x71])
        self.succ = rng.permutation(self.vocab).astype(np.int32)

    def row(self, step: int, row: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, step, row])
        base = self.ranks(rng).astype(np.int32)
        coin = rng.random(self.seq + 1) < float(self.mix["successor_p"])
        out = base.copy()
        out[1:][coin[1:]] = self.succ[base[:-1][coin[1:]]]
        return out


KINDS = {"packed_documents": PackedDocuments, "zipf_bigram": ZipfBigram}


def make(mix: dict, seed: int, vocab: int, num_micro: int, mb: int,
         seq: int):
    """A function step -> batch {tokens, labels, label_mask} ([m, b, s])."""
    return KINDS[mix["kind"]](mix, seed, vocab, num_micro, mb, seq)
