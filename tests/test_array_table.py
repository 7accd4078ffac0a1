"""The columnar tuple store (`repro.db.ArrayTable`).

Two properties:

* no attribute of a loaded table is a container that CPython's cyclic
  collector walks: every full collection would otherwise visit one pointer
  per row (10M at YCSB's size), whatever the path the rows came in by;
* the key columns read back what went in: ``key_of``, ``key_bytes_for``,
  ``items()`` and ``to_dict()`` equal a plain dict built from the same
  inserts, non-utf-8 key bytes (the surrogateescape index) and rows added
  past the initial capacity included.
"""

import gc
import random

import numpy as np
import pytest

from repro.db import ArrayTable, Table


def _key(i: int) -> str:
    return "user%06d" % i


def _load_insert(n):
    t = ArrayTable(capacity=n)
    for i in range(n):
        t.insert(_key(i), b"v%d" % i)
    return t


def _load_rows_for(n):
    t = ArrayTable(capacity=n)
    t.rows_for([_key(i) for i in range(n)])
    return t


def _load_rows_for_bytes(n):
    t = ArrayTable(capacity=n)
    t.rows_for_bytes([_key(i).encode() + b"\xff" for i in range(n)])
    return t


def _load_upsert_bytes(n):
    t = ArrayTable(capacity=n)
    t.upsert_bytes(
        [_key(i).encode() + b"\x80" for i in range(n)],
        np.array([b"v%d" % i for i in range(n)], dtype=object),
        np.arange(1, n + 1, dtype=np.int64),
    )
    return t


def _load_from_table(n):
    src = Table()
    for i in range(n):
        src.insert(_key(i), b"v%d" % i).ssn = i + 1
    return ArrayTable.from_table(src)


def _load_grow(n):
    t = ArrayTable(capacity=1)
    for i in range(n):
        t.insert(_key(i), b"v%d" % i)
    return t


@pytest.mark.parametrize(
    "load",
    [_load_insert, _load_rows_for, _load_rows_for_bytes, _load_upsert_bytes,
     _load_from_table, _load_grow],
    ids=["insert", "rows_for", "rows_for_bytes", "upsert_bytes", "from_table",
         "grow"],
)
def test_loaded_table_holds_no_collector_tracked_container(load):
    n = 3000
    t = load(n)
    assert len(t) == n
    for name, v in vars(t).items():
        # the mutex is tracked on CPython 3.12 but refers to nothing but its
        # type, so a collection visits no row through it; anything else must
        # be invisible to the collector
        assert not gc.is_tracked(v) or gc.get_referents(v) == [type(v)], name


def _case_utf8(t, ref):
    for i in range(40):
        k = "k-é-%d" % i
        t.insert(k, b"val%d" % i)
        ref.insert(k, k.encode(), b"val%d" % i, 0)
    # rows_for inserts the missing keys as (b"", 0) and finds the others
    keys = ["k-é-%d" % i for i in range(30, 60)]
    t.rows_for(keys)
    for k in keys:
        ref.insert_missing(k, k.encode())


def _case_rows_for_bytes(t, ref):
    kbs = [b"plain-%d" % i for i in range(20)] + [
        bytes([0xff, 0xfe, i]) for i in range(20)
    ] + [b"caf\xe9-%d" % i for i in range(10)]
    rows = t.rows_for_bytes(kbs + kbs[::3])        # repeats find their row
    assert rows[: len(kbs)].tolist() == list(range(len(kbs)))
    for kb in kbs:
        ref.insert_missing(kb.decode("utf-8", "surrogateescape"), kb)


def _case_upsert_bytes(t, ref):
    t.insert("seed", b"s")
    ref.insert("seed", b"seed", b"s", 0)
    kbs = [b"\x80\x81-%d" % i for i in range(30)] + [b"seed"]
    vals = np.array([b"a%d" % i for i in range(len(kbs))], dtype=object)
    t.upsert_bytes(kbs, vals, np.full(len(kbs), 5, dtype=np.int64))
    for kb, v in zip(kbs, vals):
        ref.insert(kb.decode("utf-8", "surrogateescape"), kb, v, 5)
    # the guard: an older ssn does not land, a newer one does
    older = np.array([b"old"] * 2, dtype=object)
    t.upsert_bytes(kbs[:2], older, np.array([3, 3], dtype=np.int64))
    newer = np.array([b"new"] * 2, dtype=object)
    t.upsert_bytes(kbs[2:4], newer, np.array([9, 9], dtype=np.int64))
    for kb in kbs[2:4]:
        ref.insert(kb.decode("utf-8", "surrogateescape"), kb, b"new", 9)


def _case_past_capacity(t, ref):
    rng = random.Random(7)
    for i in range(200):
        if i % 3 == 0:
            k = "grow-%d" % i
            t.insert(k, b"g%d" % i)
            ref.insert(k, k.encode(), b"g%d" % i, 0)
        elif i % 3 == 1:
            kb = b"\xc3(-%d" % i                     # invalid utf-8
            t.rows_for_bytes([kb])
            ref.insert_missing(kb.decode("utf-8", "surrogateescape"), kb)
        else:
            kb = bytes(rng.randrange(256) for _ in range(6)) + b"%d" % i
            t.upsert_bytes([kb], np.array([b"u"], dtype=object),
                           np.array([i], dtype=np.int64))
            ref.insert(kb.decode("utf-8", "surrogateescape"), kb, b"u", i)


class _Ref:
    """Rows in arrival order: index string, exact key bytes, value, ssn."""

    def __init__(self):
        self.row = {}
        self.rows = []

    def insert(self, k, kb, value, ssn):
        if k not in self.row:
            self.row[k] = len(self.rows)
            self.rows.append([k, kb, b"", 0])
        self.rows[self.row[k]][2:] = [value, ssn]

    def insert_missing(self, k, kb):
        if k not in self.row:
            self.insert(k, kb, b"", 0)


@pytest.mark.parametrize("rows_as", ["list", "ndarray"])
@pytest.mark.parametrize(
    "case",
    [_case_utf8, _case_rows_for_bytes, _case_upsert_bytes,
     _case_past_capacity],
    ids=["utf8", "rows_for_bytes", "upsert_bytes", "past_capacity"],
)
def test_key_columns_match_dict_reference(case, rows_as):
    t, ref = ArrayTable(capacity=4), _Ref()
    case(t, ref)
    n = len(ref.rows)
    assert len(t) == t.n == n
    for r, (k, kb, _, _) in enumerate(ref.rows):
        assert t.key_of(r) == k
        assert t.row_of(k) == r
    picks = random.Random(n).choices(range(n), k=2 * n)   # repeats, any order
    rows = picks if rows_as == "list" else np.array(picks, dtype=np.int64)
    assert t.key_bytes_for(rows) == [ref.rows[r][1] for r in picks]
    assert t.key_bytes_for(rows[:0]) == []
    assert sorted(t.items()) == sorted((k, v, s) for k, _, v, s in ref.rows)
    assert t.to_dict() == {kb: (v, s) for _, kb, v, s in ref.rows}
