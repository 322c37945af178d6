"""Keys, values and update schedules of the benchmark's traffic, from --seed.

Every number is a counter-based hash of (seed, stream, index), written once
for NumPy and once for jax.numpy with the same uint32 arithmetic (`xp` is
either module). The harness makes a cell's data on the device in one jitted
call; the reference recomputes, on the host, only the keys it checks. The
seed changes which keys and values are drawn, never how many.

Key layout of the paper's deployments (`Strata`): the n resident keys are one
per stratum of `stride` consecutive keys. Offsets [0, stride/2) of a stratum
hold its resident key; [stride/2, stride) hold its "fresh" key (absent until
an update inserts it) and its "absent" key (never written). So a lookup of
any of the three has an answer known without materialising the key set.
"""

from __future__ import annotations

import dataclasses
import zlib

import numpy as np

# The largest user key of the system under test (2^30 - 2). A constant of
# the key domain, restated here so that the reference imports no program code.
MAX_USER_KEY = (1 << 30) - 2

_M1, _M2, _GOLD = 0x7FEB352D, 0x846CA68B, 0x9E3779B9
_MASK = 0xFFFFFFFF


def _mix_int(x: int) -> int:
    """The 32-bit finaliser below, on one Python int."""
    x &= _MASK
    x ^= x >> 16
    x = (x * _M1) & _MASK
    x ^= x >> 15
    x = (x * _M2) & _MASK
    return x ^ (x >> 16)


STREAMS = ("res", "fresh", "abs", "val", "ins", "reins", "q.res", "q.abs", "probe", "shift")


def stream_keys(seed: int) -> dict:
    """A 32-bit key for each named stream of one seed (any non-negative int).
    Jitted generators take them as arguments, so one program serves every
    seed."""
    keys = {}
    for stream in STREAMS:
        hi = _mix_int((seed >> 32) ^ zlib.crc32(stream.encode()))
        keys[stream] = _mix_int((seed & _MASK) ^ hi)
    return keys


def hash32(xp, idx, key: int):
    """uint32 hash of each index under a stream key."""
    x = (idx.astype(xp.uint32) * xp.uint32(_GOLD)) ^ xp.uint32(key)
    x = x ^ (x >> 16)
    x = x * xp.uint32(_M1)
    x = x ^ (x >> 15)
    x = x * xp.uint32(_M2)
    return x ^ (x >> 16)


def as_int32(xp, u):
    """Reinterpret uint32 bits as int32 (values span the whole int32 range)."""
    if xp is np:
        return u.view(np.int32)
    import jax

    return jax.lax.bitcast_convert_type(u, xp.int32)


def _int(xp):
    return np.int64 if xp is np else xp.int32


def hash_mod(xp, idx, key: int, m: int):
    return (hash32(xp, idx, key) % xp.uint32(m)).astype(_int(xp))


@dataclasses.dataclass(frozen=True)
class Strata:
    """n resident keys spread over the key domain, one per stratum."""

    n: int
    keys: dict  # stream_keys(seed), as ints or as traced uint32 scalars

    @property
    def stride(self) -> int:
        s = 2
        while 2 * s * self.n <= MAX_USER_KEY + 1:
            s *= 2
        if s < 4 or s * self.n > MAX_USER_KEY + 1:
            raise ValueError(f"{self.n} strata of at least 4 keys do not fit the key domain")
        return s

    def _k(self, stream: str):
        return self.keys[stream]

    def resident_offset(self, xp, j):
        return hash_mod(xp, j, self._k("res"), self.stride // 2)

    def fresh_offset(self, xp, j):
        return self.stride // 2 + hash_mod(xp, j, self._k("fresh"), self.stride // 2)

    def absent_offset(self, xp, j):
        half = self.stride // 2
        step = 1 + hash_mod(xp, j, self._k("abs"), max(half - 1, 1))
        return half + (self.fresh_offset(xp, j) - half + step) % half

    def resident(self, xp, j):
        return j * self.stride + self.resident_offset(xp, j)

    def fresh(self, xp, j):
        return j * self.stride + self.fresh_offset(xp, j)

    def absent(self, xp, j):
        return j * self.stride + self.absent_offset(xp, j)

    def bulk_value(self, xp, j):
        return as_int32(xp, hash32(xp, j, self._k("val")))

    def insert_value(self, xp, j):
        return as_int32(xp, hash32(xp, j, self._k("ins")))

    def reinsert_value(self, xp, j):
        return as_int32(xp, hash32(xp, j, self._k("reins")))

    def random_strata(self, xp, idx, stream: str):
        return hash_mod(xp, idx, self._k(stream), self.n)


@dataclasses.dataclass(frozen=True)
class Churn:
    """The update schedule: b-wide batches, `cycle` of them between cleanups.

    Batch i of an even cycle deletes h_d resident keys (strata l*G + 2i') and
    inserts h_i fresh keys (strata l*G + 2i' + 1), i' = (i + shift) mod cycle;
    batch i of an odd cycle deletes those fresh keys and re-inserts those
    resident keys. Every update therefore changes the state, each key is written
    once per cycle, and the live count returns to n after every two cycles.
    """

    strata: Strata
    b: int
    insert_share: float
    cycle: int

    @property
    def h_i(self) -> int:
        return int(round(self.b * self.insert_share))

    @property
    def h_d(self) -> int:
        return self.b - self.h_i

    @property
    def group(self) -> int:
        g = self.strata.n // max(self.h_i, self.h_d)
        if 2 * self.cycle > g:
            raise ValueError(f"a cycle of {self.cycle} batches does not fit {self.strata.n} strata")
        return g

    @property
    def shift(self):
        return self.strata.keys["shift"] % self.cycle

    def _rows(self, xp, i, lanes: int, parity: int):
        lane = xp.arange(lanes, dtype=_int(xp))
        i_eff = (xp.asarray(i, dtype=_int(xp)) + xp.asarray(self.shift, _int(xp))) % self.cycle
        return lane[None, :] * self.group + 2 * i_eff[:, None] + parity

    def batches(self, xp, i):
        """Batches i (1-D int array) of an even and of an odd cycle, each as
        (keys [len(i), b], values [len(i), b], is_delete [b]). Deletes come
        first in both; the ops that undo each other across two cycles sit in
        different halves of the batch, so a fault that drops a fixed half
        leaves a trace at every point of the schedule."""
        s = self.strata
        dj, ij = self._rows(xp, i, self.h_d, 0), self._rows(xp, i, self.h_i, 1)
        res, fresh = s.resident(xp, dj), s.fresh(xp, ij)
        even = (xp.concatenate([res, fresh], axis=1),
                xp.concatenate([xp.zeros_like(dj), s.insert_value(xp, ij)], axis=1),
                xp.arange(self.b) < self.h_d)
        odd = (xp.concatenate([fresh, res], axis=1),
               xp.concatenate([xp.zeros_like(ij), s.reinsert_value(xp, dj)], axis=1),
               xp.arange(self.b) < self.h_i)
        return even, odd

    def live_after(self, m: int) -> int:
        """Live keys after m update batches."""
        full, part = divmod(m, self.cycle)
        net = self.h_i - self.h_d
        live = self.strata.n + (full % 2) * self.cycle * net
        return live + part * (net if full % 2 == 0 else -net)

    def locate(self, j):
        """For strata j (int64): (is a delete row, is an insert row, batch
        index within the cycle) of the schedule."""
        l_, u = j // self.group, j % self.group
        touched = u < 2 * self.cycle
        kind, i_eff = u % 2, u // 2
        dele = touched & (kind == 0) & (l_ < self.h_d)
        ins = touched & (kind == 1) & (l_ < self.h_i)
        return dele, ins, (i_eff - self.shift) % self.cycle


def lookup_batch(xp, strata: Strata, call, width: int, resident_share: float):
    """Query batch number `call`: the first share of it resident keys of
    random strata, the rest absent keys of random strata."""
    idx = xp.asarray(call, _int(xp)) * width + xp.arange(width, dtype=_int(xp))
    n_res = int(round(width * resident_share))
    res = strata.resident(xp, strata.random_strata(xp, idx, "q.res"))
    absent = strata.absent(xp, strata.random_strata(xp, idx, "q.abs"))
    return xp.where(xp.arange(width) < n_res, res, absent)
