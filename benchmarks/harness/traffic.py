"""The one general traffic generator.  A traffic mix is a data file
under ``benchmarks/traffic/``; its ``kind`` picks the generator below and
everything else in it is a parameter.  All draws come from ``--seed``.
"""

from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """Independent streams of one seed (any size of whole number)."""
    return np.random.default_rng(
        [int(seed), int.from_bytes(stream.encode(), "little") % (1 << 63)])


def _heavy_tailed_lengths(rng, spec: dict, n: int) -> np.ndarray:
    """Pareto(alpha) document lengths in [min, max] tokens."""
    u = rng.random(n)
    lengths = spec["min"] * (1.0 - u) ** (-1.0 / spec["alpha"])
    return np.clip(lengths, spec["min"], spec["max"]).astype(np.int64)


def packed_documents(traffic: dict, seed: int, vocab_size: int) -> np.ndarray:
    """``[pool, rows, seq_len + 1]`` int32: documents of heavy-tailed
    length, each ``[bos] + uniform ids``, laid end to end and cut into
    full sequences (a document may straddle two), as a pre-training
    loader packs them.  Every row differs.  The extra position is the
    shifted target."""
    rng = rng_for(seed, "packed_documents")
    pool, rows, seq = traffic["pool_batches"], traffic["rows"], \
        traffic["seq_len"]
    need = pool * rows * (seq + 1)
    bos = traffic.get("bos_id", 0)
    out = np.empty(need, np.int32)
    filled = 0
    while filled < need:
        lengths = _heavy_tailed_lengths(rng, traffic["doc_len"], 4096)
        for n in lengths:
            n = int(min(n, need - filled))
            out[filled] = bos
            out[filled + 1:filled + n] = rng.integers(
                1, vocab_size, max(n - 1, 0), dtype=np.int32)
            filled += n
            if filled >= need:
                break
    return out.reshape(pool, rows, seq + 1)


GENERATORS = {"packed_documents": packed_documents}


def generate(traffic: dict, seed: int, **context):
    kind = traffic["kind"]
    if kind not in GENERATORS:
        raise ValueError(f"traffic kind {kind!r}: one of {sorted(GENERATORS)}")
    return GENERATORS[kind](traffic, seed, **context)
