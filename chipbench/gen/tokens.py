"""Seeded token streams for the cross-silo cells, made on the host in
bulk (vectorised, no per-token Python).

Each silo draws its ids i.i.d. from a Zipf law of exponent ``exponent``
over the ``vocab`` ids of the slice, under a permutation of its own (a
silo's frequent ids are not another's: non-IID silos, the paper's
heterogeneous data). The permutation depends on ``(seed, silo)``, the
tokens of a round on ``(seed, round, silo)``: the same triple always
gives the same tokens, whatever else is drawn.
"""
from __future__ import annotations

import functools

import numpy as np

#: stream tags, so that a silo's permutation and its rounds never share
#: a generator state
TAG_PERM, TAG_ROUND = 0x5EED_0001, 0x5EED_0002


@functools.lru_cache(maxsize=8)
def zipf_cdf(vocab: int, exponent: float) -> np.ndarray:
    """Cumulative probabilities of ranks 1..vocab under p(r) ~ r**-s."""
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -float(exponent)
    c = np.cumsum(p)
    return c / c[-1]


def _rng(*words: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(w) for w in words]))


@functools.lru_cache(maxsize=64)
def silo_ids(seed: int, silo: int, vocab: int) -> np.ndarray:
    """The id of each rank (0-based) for one silo."""
    return _rng(seed, TAG_PERM, silo).permutation(vocab).astype(np.int32)


def silo_tokens(seed: int, t: int, silo: int, shape: tuple, vocab: int,
                exponent: float) -> np.ndarray:
    """int32 ids of ``shape`` for silo ``silo`` in round ``t``."""
    u = _rng(seed, TAG_ROUND, t, silo).random(shape)
    ranks = np.searchsorted(zipf_cdf(vocab, exponent), u, side="right")
    return silo_ids(seed, silo, vocab)[np.minimum(ranks, vocab - 1)]


def round_tokens(seed: int, t: int, silos, shape: tuple, vocab: int,
                 exponent: float) -> np.ndarray:
    """(len(silos), *shape) int32: round ``t``'s tokens, one row per
    silo in the order given (a round's cohort slots)."""
    return np.stack([silo_tokens(seed, t, int(s), shape, vocab, exponent)
                     for s in silos])
