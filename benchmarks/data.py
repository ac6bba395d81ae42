"""Everything a run's data is made from: key names, the fill, the key order.

All of it is a pure function of (--seed, index), so the parent, every
generator process and the comparison recompute the same values without
passing them around.  --seed may exceed 2**31.
"""

from __future__ import annotations

import math

_M = (1 << 61) - 1


def mix(seed: int, i: int, j: int = 0) -> int:
    """A cheap 61-bit hash of (seed, i, j); no RNG state to carry."""
    x = (seed * 0x9E3779B97F4A7C15 + i * 0xBF58476D1CE4E5B9
         + j * 0x94D049BB133111EB + 0x2545F4914F6CDD1D) % _M
    x ^= x >> 29
    x = (x * 0xD6E8FEB86659FD93) % _M
    x ^= x >> 32
    return x


def key_name(fill: dict, i: int) -> str:
    return f"{fill['key_prefix']}{i:07d}"


def obj(fill: dict, i: int):
    return (key_name(fill, i), fill["type"], fill["bucket"])


def fill_elements(fill: dict, seed: int, i: int) -> list:
    return [f"{i}:{mix(seed, i, j) % (1 << 30)}"
            for j in range(fill["add_all_elements"])]


def fill_removed(fill: dict, seed: int, i: int):
    """The element the fill removes again from key i, or None."""
    if i % fill["remove_every"]:
        return None
    return min(fill_elements(fill, seed, i))


def fill_amount(seed: int, i: int) -> int:
    return 1 + mix(seed, i) % 99


def fill_value(fill: dict, seed: int, i: int):
    """What key i holds once the fill is acknowledged."""
    if fill["type"] == "counter_pn":
        return fill_amount(seed, i)
    gone = fill_removed(fill, seed, i)
    return {e for e in fill_elements(fill, seed, i) if e != gone}


def fill_batch(fill: dict, seed: int, lo: int, hi: int):
    """The transactions that fill keys lo..hi-1: a list of update lists."""
    ty, b = fill["type"], fill["bucket"]
    if ty == "counter_pn":
        return [[(key_name(fill, i), ty, b, ("increment",
                                             fill_amount(seed, i)))
                 for i in range(lo, hi)]]
    adds = [(key_name(fill, i), ty, b, ("add_all",
                                        fill_elements(fill, seed, i)))
            for i in range(lo, hi)]
    rms = [(key_name(fill, i), ty, b, ("remove", gone))
           for i in range(lo, hi)
           if (gone := fill_removed(fill, seed, i)) is not None]
    return [adds, rms] if rms else [adds]


class KeyOrder:
    """Popularity rank -> key index: the same distribution for every seed,
    laid over other rows (an affine permutation of 0..n-1)."""

    def __init__(self, seed: int, n: int):
        self.n = n
        a = mix(seed, 1) % n or 1
        while math.gcd(a, n) != 1:
            a += 1
        self.a, self.b = a, mix(seed, 2) % n

    def __call__(self, rank: int) -> int:
        return (self.a * rank + self.b) % self.n
