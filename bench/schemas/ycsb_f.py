"""YCSB core workload F (``workloads/workloadf``): read, read-modify-write.

One table of ``rows`` records, keyed and shaped as the write-only schema
(:mod:`schemas.ycsb`: key ``user`` + ten digits, ``fields`` fields of
``field_bytes`` bytes stored as one value).  Each transaction is one YCSB
operation on one Zipfian (or uniform) key:

* a **read** (``read_proportion``) returns the whole record
  (``readallfields``); it writes nothing, so it logs no record and commits
  once the version it read is durable (``ssn <= CSN``);
* a **read-modify-write** (``rmw_proportion``) reads the whole record,
  replaces one field, chosen uniformly, with a value from a seeded pool
  (``writeallfields`` false), and writes the whole record back as its
  post-image (value logging).

Each transaction is a closure that reads its record when it is called, and
the scheduler calls it again on every retry, so a retry re-reads.  The read
takes the table's mutex, which the executor holds through validation and
write-back, so a closure never sees a value and an SSN from different
writes.  Each call hands over a spec under a tag of its own and keeps what
it read and wrote under that tag, for the reference.

The loaded bytes of record ``i`` are words ``[128 i, 128 i + 125)`` of one
Philox stream keyed by the seed: the load draws them a block at a time, and
:meth:`Schema.loaded` draws only the records it is asked for.
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Optional, Sequence

import numpy as np

from harness.probes import Tagged
from harness.reference import Spec
from schemas.ycsb import Zipfian, key_of

VALUE_POOL = 4096
LOAD_BLOCK = 1 << 16
ROW_WORDS = 128          # u64 words of the stream per record (1 KiB)


class Schema:
    """Loader and clients of the YCSB-F deployment ``cfg``."""

    def __init__(self, cfg: Dict, rows: Optional[int] = None):
        self.rows = int(rows if rows is not None else cfg["rows"])
        self.fields = int(cfg["fields"])
        self.field_bytes = int(cfg["field_bytes"])
        self.value_bytes = self.fields * self.field_bytes
        if self.value_bytes > ROW_WORDS * 8:
            raise ValueError("a record is wider than its stream window")
        self.read_proportion = float(cfg["read_proportion"])
        rmw = float(cfg["rmw_proportion"])
        if abs(self.read_proportion + rmw - 1.0) > 1e-9:
            raise ValueError("read and read-modify-write must make the mix")
        self.capacity = 1 << (self.rows + cfg.get("insert_headroom", 0)
                              ).bit_length()
        # framed bytes per write lane: record header + key + value
        self.lane_bytes_min = 29 + 8 + len(key_of(0)) + self.value_bytes
        # lanes BatchOCC flattens per transaction: a read is one read lane,
        # a read-modify-write a read lane and a write lane
        self.accesses = (1, 2)
        self.writes = (0, 1)

    def _stream(self, seed: int, first_row: int) -> np.random.Philox:
        key = np.random.SeedSequence([seed, 1]).generate_state(2, np.uint64)
        # one counter step is four u64 words
        return np.random.Philox(key=key, counter=first_row * ROW_WORDS // 4)

    def _records(self, seed: int, lo: int, hi: int) -> np.ndarray:
        """Loaded bytes of records ``lo .. hi - 1``, one row each."""
        words = self._stream(seed, lo).random_raw((hi - lo) * ROW_WORDS)
        return words.view(np.uint8).reshape(hi - lo, -1)[:, :self.value_bytes]

    def load(self, table, seed: int) -> None:
        for lo in range(0, self.rows, LOAD_BLOCK):
            hi = min(self.rows, lo + LOAD_BLOCK)
            recs = self._records(seed, lo, hi)
            for j in range(hi - lo):
                table.insert(key_of(lo + j), recs[j].tobytes())

    def loaded(self, seed: int, keys) -> Dict[str, bytes]:
        """The loaded value of each of ``keys`` that the load holds, from
        the seed alone (no table), drawing only those records."""
        out = {}
        for k in keys:
            i = int(k[len("user"):])
            if i < self.rows:
                out[k] = self._records(seed, i, i + 1)[0].tobytes()
        return out

    def clients(self, traffic: Dict, table, seed: int) -> "Source":
        return Source(self, traffic["txn"], table, seed)

    def consistency(self, image) -> int:
        return 0                # single-record operations: nothing to relate


class Source:
    """Read and read-modify-write closures in a fixed order drawn from the
    seed."""

    def __init__(self, schema: Schema, txn: Dict, table, seed: int):
        rng = np.random.default_rng([seed, 2])
        self.s = schema
        self.table = table
        self.rng = rng
        fb = schema.field_bytes
        pool = rng.bytes(VALUE_POOL * fb)
        self.pool = [pool[i * fb:(i + 1) * fb] for i in range(VALUE_POOL)]
        dist = txn["key_dist"]
        if dist == "zipfian":
            self.zipf = Zipfian(schema.rows, float(txn["theta"]), rng)
        elif dist == "uniform":
            self.zipf = None
        else:
            raise ValueError(f"unknown key_dist {dist!r}")
        self.keep = True                 # keep each attempt for the reference
        self.built: Dict[int, tuple] = {}
        self._tags = itertools.count()

    def _hand_over(self, key: str, got, writes: tuple) -> Tagged:
        """The spec of one attempt, kept under its tag as plain tuples."""
        tag = next(self._tags)
        value, ssn = got
        if self.keep:
            self.built[tag] = (key, ssn, value, writes)
        return Tagged(reads=[key], writes=list(writes), observed=[ssn],
                      tag=tag)

    def spec(self, tag: int) -> Spec:
        key, ssn, value, writes = self.built[tag]
        return Spec(reads=(key,), writes=writes, observed=(ssn,),
                    values=(value,))

    def take(self, n: int, homes: Optional[Sequence[int]] = None) -> List:
        """The next ``n`` operations (``homes``, a client's home, plays no
        part in YCSB)."""
        r = self.rng
        if self.zipf is not None:
            keys = self.zipf.sample(n)
        else:
            keys = r.integers(0, self.s.rows, n)
        rmw = r.random(n) >= self.s.read_proportion
        fields = r.integers(0, self.s.fields, n)
        vals = r.integers(0, VALUE_POOL, n)
        return [self.rmw(k, f, v) if m else self.read(k)
                for k, m, f, v in zip(keys.tolist(), rmw.tolist(),
                                      fields.tolist(), vals.tolist())]

    def read(self, i: int):
        table, key = self.table, key_of(i)

        def build() -> Tagged:
            with table.mutex:
                got = table.get(key)
            return self._hand_over(key, got, ())

        return build

    def rmw(self, i: int, field: int, val: int):
        table, key = self.table, key_of(i)
        lo = field * self.s.field_bytes
        hi = lo + self.s.field_bytes
        new = self.pool[val]

        def build() -> Tagged:
            with table.mutex:
                got = table.get(key)
            old = got[0]
            return self._hand_over(key, got,
                                   ((key, old[:lo] + new + old[hi:]),))

        return build
