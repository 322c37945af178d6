"""The plain reference: what every answer of a cell must be, in NumPy.

It imports nothing of the program. Its semantics are the configurations'
guarantees: a key's state is its last write in arrival order (a delete
removes it), every acknowledged write is visible to the next read, and
values come back as the exact 32-bit integers written.

`churn_lookup` answers for the paper's deployments. A key's writes are
those the update schedule (`data.Churn`) made to it in the first `m`
batches, so its state is read from its last one without replaying the whole
stream.

`control=True` gives the control: the same reference holding its values in
the next narrower integer type (int16), which breaks the exact-value
guarantee. A comparison that the control passes cannot tell a wrong value.
"""

from __future__ import annotations

import numpy as np

from bench import data


def narrow(values: np.ndarray) -> np.ndarray:
    """Values held in int16, as the control holds them."""
    return np.asarray(values).astype(np.int16).astype(np.int32)


def churn_lookup(s: data.Strata, churn, q, m: int, control: bool = False):
    """(found, value) of each query key after the first m batches of the
    update schedule `churn` (None or m = 0: the bulk load alone). Absent keys
    read value 0."""
    q = np.asarray(q, np.int64)
    j = q // s.stride
    inside = (q >= 0) & (j < s.n)
    j = np.where(inside, j, 0)
    off = q - j * s.stride
    is_res = inside & (off == s.resident_offset(np, j))
    is_fresh = inside & (off == s.fresh_offset(np, j))
    if churn is None or m == 0:
        dele = ins = np.zeros(j.shape, bool)
        writes = np.zeros(j.shape, np.int64)
    else:
        dele, ins, i = churn.locate(j)
        writes = np.where(m > i, (m - i + churn.cycle - 1) // churn.cycle, 0)
    # A delete row's resident key: deleted by even cycles, re-inserted by odd.
    res_found = is_res & (~dele | (writes % 2 == 0))
    res_value = np.where(dele & (writes > 0), s.reinsert_value(np, j), s.bulk_value(np, j))
    # An insert row's fresh key: inserted by even cycles, deleted by odd.
    fresh_found = is_fresh & ins & (writes % 2 == 1)
    found = res_found | fresh_found
    value = np.where(res_found, res_value, np.where(fresh_found, s.insert_value(np, j), 0))
    return found, narrow(value) if control else value.astype(np.int32)


def mismatches(found, value, want_found, want_value) -> int:
    """Lanes whose (found, value) differs from the reference; the value of an
    absent key is not compared."""
    found, want_found = np.asarray(found, bool), np.asarray(want_found, bool)
    value = np.where(found, np.asarray(value), 0)
    want_value = np.where(want_found, np.asarray(want_value), 0)
    return int(((found != want_found) | (value != want_value)).sum())
