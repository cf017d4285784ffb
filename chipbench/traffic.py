"""The training traffic: a ring of distinct batches drawn from the seed.

One general generator reads a traffic file (``traffic/<name>.json``).  The
``zipf_copy`` documents are those of the program's ``SyntheticLM``
(``src/repro/data/pipeline.py``), copied here so that the benchmark owns
its inputs: tokens drawn from a Zipf distribution over the whole
vocabulary, and one span of each row copied later in the row.  Every
seed gets the same sizes; only the tokens differ.  The ring is made once
during set-up, so no sampling on the host falls inside the window.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def _zipf_copy_rows(rng: np.random.Generator, cdf: np.ndarray, rows: int,
                    n: int, copy_fraction: float) -> np.ndarray:
    toks = np.searchsorted(cdf, rng.random((rows, n)), side="right")
    toks = np.minimum(toks, len(cdf) - 1)
    span = int(n * copy_fraction)
    if span and n >= 2 * span + 1:
        for r in range(rows):
            src = rng.integers(0, n - 2 * span)
            dst = src + span + rng.integers(0, max(n - src - 2 * span, 1))
            toks[r, dst:dst + span] = toks[r, src:src + span]
    return toks.astype(np.int32)


def make_ring(traffic: Dict, vocab_size: int, seed: int
              ) -> List[Dict[str, np.ndarray]]:
    """``traffic["ring"]`` batches of ``{"inputs", "targets", "loss_mask"}``
    (host arrays, ``(batch, seq)``); batch ``i`` comes from the generator
    keyed by ``(seed, i)``."""
    if traffic["generator"] != "zipf_copy":
        raise ValueError(f"unknown traffic generator {traffic['generator']!r}")
    B, S = int(traffic["batch"]), int(traffic["seq"])
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    p = ranks ** -float(traffic["zipf_exponent"])
    cdf = np.cumsum(p / p.sum())
    ring = []
    for i in range(int(traffic["ring"])):
        rng = np.random.default_rng((int(seed), i))
        tok = _zipf_copy_rows(rng, cdf, B, S + 1,
                              float(traffic["copy_fraction"]))
        ring.append({"inputs": tok[:, :-1], "targets": tok[:, 1:],
                     "loss_mask": np.ones((B, S), np.float32)})
    return ring
