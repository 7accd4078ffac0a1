"""YCSB core workload, write-only (Poplar paper §6.2; YCSB core properties).

One table of ``rows`` records; the key of record ``i`` is ``user`` followed
by ``i`` zero-padded to ten digits; each record holds ``fields`` fields of
``field_bytes`` bytes, stored as one value.  A transaction overwrites all
fields of one key (a blind write: it reads nothing).  Keys are drawn
Zipfian (Gray et al., YCSB's generator, rank ``r`` with weight
``1 / r^theta``, rank 0 the hottest) or uniformly, as the traffic says.
Values come from a seeded pool of distinct random values.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from harness.probes import Tagged
from harness.reference import Spec

VALUE_POOL = 4096
LOAD_BLOCK = 1 << 16


def key_of(i: int) -> str:
    return f"user{i:010d}"


class Zipfian:
    """Item ``r`` in ``[0, n)`` with probability proportional to
    ``1 / (r + 1)^theta`` (Gray et al., "Quickly Generating Billion-Record
    Synthetic Databases"; the closed form YCSB uses)."""

    def __init__(self, n: int, theta: float, rng: np.random.Generator):
        self.n, self.rng = n, rng
        self.zetan = float(np.sum(np.arange(1, n + 1, dtype=np.float64)
                                  ** -theta))
        self.zeta2 = 1.0 + 2.0 ** -theta
        self.alpha = 1.0 / (1.0 - theta)
        self.eta = ((1.0 - (2.0 / n) ** (1.0 - theta))
                    / (1.0 - self.zeta2 / self.zetan))

    def sample(self, size: int) -> np.ndarray:
        u = self.rng.random(size)
        uz = u * self.zetan
        spread = self.n * (self.eta * u - self.eta + 1.0) ** self.alpha
        idx = np.where(uz < 1.0, 0,
                       np.where(uz < self.zeta2, 1, spread.astype(np.int64)))
        return np.minimum(idx.astype(np.int64), self.n - 1)


class Schema:
    """Loader and client of the YCSB write-only deployment ``cfg``."""

    def __init__(self, cfg: Dict, rows: Optional[int] = None):
        self.rows = int(rows if rows is not None else cfg["rows"])
        self.value_bytes = int(cfg["fields"]) * int(cfg["field_bytes"])
        # the table is allocated once at this many rows, so the width of the
        # fused round's first-writer table never changes during a run
        self.capacity = 1 << (self.rows + cfg.get("insert_headroom", 0)
                              ).bit_length()
        # framed bytes per write lane: record header + key + value
        self.lane_bytes_min = 29 + 8 + len(key_of(0)) + self.value_bytes
        self.accesses = (1, 1)       # (min, max) keys a transaction touches
        self.writes = (1, 1)         # (min, max) keys it writes

    def load(self, table, seed: int) -> None:
        rng = np.random.default_rng([seed, 1])
        vb = self.value_bytes
        for lo in range(0, self.rows, LOAD_BLOCK):
            hi = min(self.rows, lo + LOAD_BLOCK)
            blob = rng.bytes((hi - lo) * vb)
            for j in range(hi - lo):
                table.insert(key_of(lo + j), blob[j * vb:(j + 1) * vb])

    def clients(self, traffic: Dict, table, seed: int) -> "Source":
        return Source(self, traffic["txn"], seed)

    def consistency(self, image) -> int:
        return 0                      # blind writes: nothing to relate


class Source:
    """Transactions in a fixed order drawn from the seed."""

    def __init__(self, schema: Schema, txn: Dict, seed: int):
        rng = np.random.default_rng([seed, 2])
        self.rows = schema.rows
        pool = rng.bytes(VALUE_POOL * schema.value_bytes)
        vb = schema.value_bytes
        self.pool = [pool[i * vb:(i + 1) * vb] for i in range(VALUE_POOL)]
        self.rng = rng
        self.keys: List[np.ndarray] = []   # record of each tag's key ...
        self.vals: List[np.ndarray] = []   # ... and pool value
        self.n = 0
        self._flat = None
        dist = txn["key_dist"]
        if dist == "zipfian":
            self.zipf = Zipfian(self.rows, float(txn["theta"]), rng)
        elif dist == "uniform":
            self.zipf = None
        else:
            raise ValueError(f"unknown key_dist {dist!r}")

    def take(self, n: int, homes=None) -> List[TxnSpec]:
        """The next ``n`` transactions (``homes``, a client's home, plays no
        part in YCSB)."""
        if self.zipf is not None:
            keys = self.zipf.sample(n)
        else:
            keys = self.rng.integers(0, self.rows, n)
        vals = self.rng.integers(0, VALUE_POOL, n)
        self.keys.append(keys)
        self.vals.append(vals)
        base, self.n = self.n, self.n + n
        pool = self.pool
        return [Tagged(writes=[(key_of(k), pool[v])], tag=base + j)
                for j, (k, v) in enumerate(zip(keys.tolist(), vals.tolist()))]

    def spec(self, tag: int) -> Spec:
        """The transaction handed over under ``tag`` (a retry hands over the
        same one)."""
        if self._flat is None or len(self._flat[0]) != self.n:
            self._flat = (np.concatenate(self.keys), np.concatenate(self.vals))
        return Spec(writes=[(key_of(int(self._flat[0][tag])),
                             self.pool[int(self._flat[1][tag])])])
