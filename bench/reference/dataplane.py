"""The data plane's reference: the benchmark's own token grids, and the
exactly-once check of what the trainer consumed against them.

Every TGB a producer commits is ``TokenGenerator.grid(producer, seq)``, a
pure function of the run's seed. The check never asks the data plane what
it committed: it regenerates every grid the producers wrote and holds each
consumed grid to them byte for byte.
"""
from __future__ import annotations

import hashlib
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np


class TokenGenerator:
    """Seeded ``(global_batch, seq_len)`` int32 token grids.

    Token ids follow a Zipf law of exponent ``zipf_s`` over the vocabulary
    slice (rank r drawn with weight r**-s), with ranks mapped to ids by a
    permutation drawn from the seed. Every seed gives the same sizes; only
    the values differ.
    """

    def __init__(self, seed: int, vocab: int, global_batch: int,
                 seq_len: int, zipf_s: float):
        self.seed = int(seed)
        self.shape = (global_batch, seq_len)
        w = np.arange(1, vocab + 1, dtype=np.float64) ** -float(zipf_s)
        self._cdf = np.cumsum(w / w.sum())
        self._cdf[-1] = 1.0
        self._ids = np.random.default_rng([self.seed, 0]).permutation(
            vocab).astype(np.int32)

    def grid(self, producer: int, seq: int) -> np.ndarray:
        u = np.random.default_rng([self.seed, 1 + producer, seq]).random(
            self.shape)
        return self._ids[np.searchsorted(self._cdf, u, side="right")]


def digest(b: bytes) -> bytes:
    return hashlib.blake2b(b, digest_size=16).digest()


def check_consumed(gen: TokenGenerator, consumed: Sequence[bytes],
                   written: Mapping[int, int]) -> Dict[str, object]:
    """Hold the grids consumed at global steps 0, 1, 2, ... to the TGBs the
    producers wrote (``written[p]`` grids by producer ``p``).

    Counts, each of which must be 0:

    - ``unknown``: a consumed grid equal to no written TGB (a flipped byte,
      a torn or foreign grid);
    - ``duplicated``: a TGB consumed at two steps;
    - ``out_of_order``: a producer's TGB consumed other than right after its
      predecessor (a TGB dropped or reordered).

    ``ids`` names the TGB ``(producer, seq)`` behind each step (None where
    unknown).
    """
    index: Dict[bytes, Tuple[int, int]] = {}
    for p, n in written.items():
        for s in range(n):
            index[digest(gen.grid(p, s).tobytes())] = (p, s)
    seen = set()
    next_seq = {p: 0 for p in written}
    ids: List[Optional[Tuple[int, int]]] = []
    unknown = duplicated = out_of_order = 0
    for b in consumed:
        key = index.get(digest(b))
        if key is None or gen.grid(*key).tobytes() != b:
            unknown += 1
            ids.append(None)
            continue
        ids.append(key)
        if key in seen:
            duplicated += 1
        seen.add(key)
        p, s = key
        if s != next_seq[p]:
            out_of_order += 1
        next_seq[p] = s + 1
    return {"unknown": unknown, "duplicated": duplicated,
            "out_of_order": out_of_order, "ids": ids}
