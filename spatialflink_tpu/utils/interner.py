"""String object-id <-> int32 interning.

Device kernels key dedup/top-k by integer object ids; the host keeps the
string mapping (the reference keys state by the raw ``objID`` string).

The native decode (``streams/bulk.py``) also keeps a hash -> id index here:
the 64-bit FNV-1a hash of each normalised id it has interned, so a chunk's
known ids resolve in numpy without materialising a string. The index is
derived state: it is never checkpointed, :meth:`IdInterner.from_list`
starts it empty, and misses rebuild it (a miss interns its string, which
returns the existing id when the string is already known)."""

from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

import numpy as np


class IdInterner:
    def __init__(self) -> None:
        self._to_int: Dict[str, int] = {}
        self._to_str: List[str] = []
        # hash -> id index: sorted (uint64 hashes, int32 ids) runs with
        # disjoint keys, each at most half the size of the one before it
        # (the logarithmic method), so an insert costs O(log n) amortised
        # and a lookup one searchsorted per run
        self._runs: List[Tuple[np.ndarray, np.ndarray]] = []

    def intern(self, obj_id: str) -> int:
        idx = self._to_int.get(obj_id)
        if idx is None:
            idx = len(self._to_str)
            self._to_int[obj_id] = idx
            self._to_str.append(obj_id)
        return idx

    def intern_many(self, obj_ids: Iterable[str]) -> List[int]:
        """:meth:`intern` over ``obj_ids`` in order (new ids are assigned in
        that order), without a method call per id."""
        to_int, to_str = self._to_int, self._to_str
        out = []
        for s in obj_ids:
            idx = to_int.get(s)
            if idx is None:
                idx = to_int[s] = len(to_str)
                to_str.append(s)
            out.append(idx)
        return out

    def lookup(self, idx: int) -> str:
        return self._to_str[idx]

    def __len__(self) -> int:
        return len(self._to_str)

    def lookup_hashes(self, hashes: np.ndarray
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """Resolve ``hashes`` (uint64) through the hash index: returns the
        int32 ids (-1 where unknown) and the positions of the misses."""
        ids = np.full(hashes.shape[0], -1, np.int32)
        miss = np.arange(hashes.shape[0])
        for keys, vals in self._runs:
            q = hashes[miss]
            pos = np.minimum(np.searchsorted(keys, q), keys.shape[0] - 1)
            hit = keys[pos] == q
            ids[miss[hit]] = vals[pos[hit]]
            miss = miss[~hit]
            if not miss.shape[0]:
                break
        return ids, miss

    def index_hashes(self, hashes: np.ndarray, ids: np.ndarray) -> None:
        """Add ascending, not yet indexed ``hashes`` with their ``ids``."""
        if not hashes.shape[0]:
            return
        runs = self._runs
        runs.append((np.asarray(hashes, np.uint64), np.asarray(ids, np.int32)))
        while len(runs) > 1 and runs[-2][0].shape[0] <= 2 * runs[-1][0].shape[0]:
            bk, bv = runs.pop()
            ak, av = runs.pop()
            n = ak.shape[0] + bk.shape[0]
            pos = np.searchsorted(ak, bk) + np.arange(bk.shape[0])
            keep = np.ones(n, bool)
            keep[pos] = False
            keys = np.empty(n, np.uint64)
            vals = np.empty(n, np.int32)
            keys[pos], vals[pos] = bk, bv
            keys[keep], vals[keep] = ak, av
            runs.append((keys, vals))

    def to_list(self) -> List[str]:
        """Id-ordered strings for checkpointing (index == interned id)."""
        return list(self._to_str)

    @classmethod
    def from_list(cls, ids: List[str]) -> "IdInterner":
        out = cls()
        out.intern_many(str(s) for s in ids)
        return out
