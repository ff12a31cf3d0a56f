"""Seeded synthetic token batches: the benchmark's copy of the trainer's
``data.pipeline.SyntheticTokens``, byte for byte the same stream.

Batch ``i`` is a pure function of ``(seed, i)``, and each row draws from
its own Philox counter, so any split of a batch over devices gives the
same rows. Tokens follow one of ``n_topics`` sparse unigram windows with
copy-previous and copy-8-back moves, so the loss has signal to follow.
"""

from __future__ import annotations

import dataclasses

import numpy as np


def _philox(seed: int, step: int):
    return np.random.Generator(np.random.Philox(key=seed, counter=step))


@dataclasses.dataclass(frozen=True)
class SyntheticTokens:
    vocab: int
    seq_len: int
    global_batch: int
    seed: int = 0
    n_topics: int = 16
    topic_vocab: int = 512

    def batch_slice(self, step: int, lo: int, hi: int) -> np.ndarray:
        """Rows ``[lo, hi)`` of global batch ``step``, int32."""
        tv = min(self.topic_vocab, self.vocab)
        out = np.empty((hi - lo, self.seq_len), np.int32)
        for r, i in enumerate(range(lo, hi)):
            rng = _philox(self.seed, step * (1 << 24) + i)
            topic = int(rng.integers(0, self.n_topics))
            off = (topic * tv) % max(self.vocab - tv, 1)
            toks = (rng.integers(0, tv, size=self.seq_len)
                    + off).astype(np.int32)
            u = rng.random(self.seq_len)
            for t in range(1, self.seq_len):
                if u[t] < 0.25:
                    toks[t] = toks[t - 1]
                elif t >= 8 and u[t] < 0.35:
                    toks[t] = toks[t - 8]
            out[r] = toks
        return out % self.vocab

    def batch(self, step: int) -> np.ndarray:
        return self.batch_slice(step, 0, self.global_batch)
