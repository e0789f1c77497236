"""What every generator shares: the clock, the seeded payloads, the ledger
that is also the plain reference, the open-loop schedule and percentiles.

Everything here is a function of the seed alone, so the same seed offers
the same operations in the same order.
"""
from __future__ import annotations

import time

import numpy as np

# one clock for due times, acknowledgements and the engine's own
# flight-recorder spans (time.monotonic is CLOCK_MONOTONIC on Linux)
clock = time.monotonic

_CHUNK = 4096
_U64 = np.uint64
_MASK64 = (1 << 64) - 1


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finaliser over a uint64 array (wraps mod 2^64)."""
    x = (x ^ (x >> _U64(30))) * _U64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> _U64(27))) * _U64(0x94D049BB133111EB)
    return x ^ (x >> _U64(31))


class Payloads:
    """The 16-byte commands of every group, made from the seed on demand.

    Row k of group g is key = k, value = mix(seed, g, k), both u64
    little-endian: keys are unique inside a group and every value is
    decided by the seed. Rows are built a chunk at a time so a closed
    loop may take as many as the system can commit."""

    def __init__(self, seed: int, groups: int) -> None:
        self._seed = seed
        self._chunks = [[] for _ in range(groups)]

    def _chunk(self, g: int, c: int):
        chunks = self._chunks[g]
        while len(chunks) <= c:
            k = np.arange(
                len(chunks) * _CHUNK, (len(chunks) + 1) * _CHUNK, dtype=_U64
            )
            salt = (self._seed * 0x9E3779B97F4A7C15 + g + 1) & _MASK64
            v = _mix64(k + _mix64(np.array([salt], _U64))[0])
            rows = np.stack([k, v], axis=1).astype("<u8")
            chunks.append((rows.tobytes(), k + v))
        return chunks[c]

    def cmds(self, g: int, lo: int, hi: int) -> list:
        out = []
        for k in range(lo, hi):
            blob = self._chunk(g, k // _CHUNK)[0]
            off = (k % _CHUNK) * 16
            out.append(blob[off:off + 16])
        return out

    @staticmethod
    def key(k: int) -> bytes:
        return k.to_bytes(8, "little")

    def value(self, g: int, k: int) -> bytes:
        blob = self._chunk(g, k // _CHUNK)[0]
        off = (k % _CHUNK) * 16 + 8
        return blob[off:off + 8]

    def sum64(self, g: int, rows: int) -> int:
        """Sum of both words of rows [0, rows) of group g, mod 2^64."""
        total = 0
        for c in range((rows + _CHUNK - 1) // _CHUNK):
            words = self._chunk(g, c)[1][:min(_CHUNK, rows - c * _CHUNK)]
            total += int(words.sum(dtype=_U64))  # array sums wrap mod 2^64
        return total & _MASK64


class CheckFailure(AssertionError):
    pass


class Ledger:
    """What the client was told, per group: rows submitted, writes
    acknowledged, and writes whose fate it was not told (a batch cut
    short by a leader change, a full queue or a timeout: they may still
    commit). It is also the plain reference: a group with nothing
    indeterminate holds exactly (rows submitted, sum64 of those rows)."""

    def __init__(self, payloads: Payloads, groups: int) -> None:
        self.payloads = payloads
        self.used = [0] * groups
        self.acked = [0] * groups
        self.indeterminate = [0] * groups
        # rows [0, readable[g]) are known acknowledged, so a read may ask
        # for them; it stops growing at the first batch cut short
        self.readable = [0] * groups

    def take(self, g: int, n: int):
        """Reserve the next n rows of group g: (lo, hi, commands)."""
        lo = self.used[g]
        self.used[g] = lo + n
        return lo, lo + n, self.payloads.cmds(g, lo, lo + n)

    def settle(self, g: int, lo: int, hi: int, completed: int, dropped: int):
        """Account one finished batch of rows [lo, hi)."""
        self.acked[g] += completed
        self.indeterminate[g] += dropped
        if not dropped and not self.indeterminate[g] and self.readable[g] == lo:
            self.readable[g] = hi

    def expected(self, g: int):
        """(count, sum64) where exact, else None."""
        if self.indeterminate[g]:
            return None
        return self.used[g], self.payloads.sum64(g, self.used[g])

    def check(self, g: int, where: str, got) -> None:
        want = self.expected(g)
        if want is not None:
            if tuple(got) != want:
                raise CheckFailure(
                    f"group {g + 1} {where}: read {got}, acknowledged {want}"
                )
            return
        lo, hi = self.acked[g], self.acked[g] + self.indeterminate[g]
        if not lo <= got[0] <= hi:
            raise CheckFailure(
                f"group {g + 1} {where}: read count {got[0]} outside "
                f"[{lo}, {hi}]"
            )


def conditioned_poisson(rng, n: int, t0: float, t1: float) -> np.ndarray:
    """n Poisson arrivals in [t0, t1): given their number, the arrival
    times of a Poisson process are sorted uniforms. Fixing n makes the
    amount of work the same for every seed."""
    return t0 + np.sort(rng.random(n)) * (t1 - t0)


def exact_share(rng, n: int, share: float) -> np.ndarray:
    """A seeded boolean array of length n with exactly round(share * n)
    True entries."""
    flags = np.zeros(n, bool)
    flags[:int(round(share * n))] = True
    rng.shuffle(flags)
    return flags


def percentile(values, p: float) -> float:
    """The value at rank ceil(p * n) of the sorted sample (nearest rank):
    no interpolation, so a tail is a latency some request really had."""
    if len(values) == 0:
        raise ValueError("percentile of an empty sample")
    s = np.sort(np.asarray(values, dtype=np.float64))
    rank = max(1, int(np.ceil(p * len(s))))
    return float(s[rank - 1])
