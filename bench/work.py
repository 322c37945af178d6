"""The least bytes each op of a cell must move, from its shapes and the
LSM's counter r, never from the program's own estimates.

Update (paper §3.2): a batch pushed into a counter with t trailing ones
merges b * 2^t elements (the batch and levels 0..t-1) into level t; a
cleanup merges every slot, levels and write buffer. Each merged element is
read once and written once: 8 bytes each way (key variable and value).

Lookup: each query reads its key, writes found and value (4 + 1 + 4 bytes),
and reads at least one key variable of every occupied run.
"""

from __future__ import annotations

MERGE_BYTES = 16
QUERY_BYTES = 9
PROBE_BYTES = 4


def trailing_ones(r: int) -> int:
    t = 0
    while r >> t & 1:
        t += 1
    return t


class LsmCounter:
    """The resident-batch counter r and write-buffer fill of one LSM (one
    shard), advanced as the benchmark sends it work; `merged` accumulates
    the elements merged."""

    def __init__(self, b: int, levels: int, r: int):
        self.b, self.levels, self.r, self.buffered, self.merged = b, levels, r, 0, 0

    def _push(self) -> None:
        self.merged += self.b << trailing_ones(self.r)
        self.r += 1

    def stage(self, lanes: int) -> None:
        """`lanes` real updates appended to the write buffer; only the
        oldest b flush once more than b are pending."""
        self.buffered += lanes
        if self.buffered > self.b:
            self._push()
            self.buffered -= self.b

    def cleanup(self, live: int) -> None:
        self.merged += self.b * (1 << self.levels)  # every level slot plus the buffer
        self.r, self.buffered = -(-live // self.b), 0

    @property
    def runs(self) -> int:
        return bin(self.r).count("1") + (self.buffered > 0)

    def update_bytes(self) -> int:
        return MERGE_BYTES * self.merged


def lookup_bytes(queries: int, runs: int) -> int:
    return queries * (QUERY_BYTES + PROBE_BYTES * runs)
