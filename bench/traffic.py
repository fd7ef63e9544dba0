"""The benchmark's traffic: training batches from a mix file and a seed.

A mix is a JSON file in ``bench/traffic/`` that this one generator reads:

    {"global_batch": 4, "seq_len": 2048, "kind": "synthetic",
     "zipf_a": 1.2, "bigram_weight": 0.7, "n_bigram_states": 64}

``synthetic`` tokens are Zipf-distributed ids with a planted bigram table,
so that a model has something to learn; ``uniform`` tokens are uniform ids.
Batch ``step`` is a pure function of (seed, step).  The arithmetic is that
of the program's own ``repro.data.pipeline.TokenPipeline``, kept here as
the yardstick: the program's pipeline must deliver these very batches.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

#: keys of a mix that go to the program's ``DataConfig``
DATA_KEYS = ("kind", "zipf_a", "bigram_weight", "n_bigram_states")


def batch_at(mix: dict, vocab: int, seed: int, step: int) -> Dict[str, np.ndarray]:
    """Tokens and next-token labels, both ``(global_batch, seq_len)`` int32."""
    B, S = mix["global_batch"], mix["seq_len"]
    rng = np.random.default_rng((seed * 1_000_003 + step) * 65_537)
    if mix["kind"] == "uniform":
        toks = rng.integers(0, vocab, size=(B, S + 1), dtype=np.int64)
    elif mix["kind"] == "synthetic":
        table = np.random.default_rng(seed).integers(
            0, vocab, size=(mix["n_bigram_states"],), dtype=np.int64)
        toks = np.minimum(rng.zipf(mix["zipf_a"], size=(B, S + 1)) - 1, vocab - 1)
        follow = rng.random((B, S + 1)) < mix["bigram_weight"]
        for t in range(1, S + 1):
            nxt = table[toks[:, t - 1] % mix["n_bigram_states"]]
            toks[:, t] = np.where(follow[:, t], nxt, toks[:, t])
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    return {"tokens": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:].astype(np.int32)}
