"""Synthetic corpora for the two benchmark applications.

The paper profiles on 8 GB of real text / mail logs; we generate
statistically similar synthetic streams sized for the host: Zipf-distributed
word ids for WordCount (natural-language-like skew matters — it skews the
shuffle partition fill), and fixed-width Exim transaction records with
realistic event multiplicity (each mail transaction logs ~2-6 lines).
"""

from __future__ import annotations

import numpy as np

from repro.mapreduce.apps import RECORD_WIDTH


def wordcount_corpus(
    n_tokens: int, vocab_size: int = 4096, *, zipf_a: float = 1.3, seed: int = 0
) -> np.ndarray:
    rng = np.random.default_rng(seed)
    # Zipf over a finite vocab via rejection-free inverse-CDF on ranks.
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    probs = ranks ** (-zipf_a)
    probs /= probs.sum()
    return rng.choice(vocab_size, size=n_tokens, p=probs).astype(np.int32)


def exim_mainlog(
    n_tokens: int, n_transactions: int = 1024, *, seed: int = 0
) -> np.ndarray:
    """Flat [txn_id, event_type, size]* stream of whole records: the
    ``n_tokens // 3`` records that fit in ``n_tokens`` tokens, so its
    length is ``n_tokens`` rounded down to a multiple of 3."""
    rng = np.random.default_rng(seed)
    n_records = n_tokens // RECORD_WIDTH + 1
    # Each transaction produces a burst of 2-6 consecutive events
    # (arrival, delivery attempts, completion) — like a real mainlog.
    txn_ids = []
    while len(txn_ids) < n_records:
        t = int(rng.integers(0, n_transactions))
        burst = int(rng.integers(2, 7))
        txn_ids.extend([t] * burst)
    txn = np.asarray(txn_ids[:n_records], dtype=np.int32)
    event = rng.integers(0, 8, size=n_records).astype(np.int32)
    size = rng.integers(200, 4000, size=n_records).astype(np.int32)
    stream = np.stack([txn, event, size], axis=1).reshape(-1)
    return stream[: n_tokens - n_tokens % RECORD_WIDTH].astype(np.int32)
