"""The TPC-C generator: cardinalities, row widths, NURand ranges, the
New-Order/Payment mix and the consistency rule."""

import json
import os
import struct

import numpy as np
import pytest

import _paths  # noqa: F401
from harness.spec import Bench

CFG = json.load(open(os.path.join(_paths.BENCH, "configs", "tpcc_w20.json")))
TPCC = Bench(_paths.ROOT).schema("tpcc")


class Table:
    """The slice of ``ArrayTable``'s interface the schema uses."""

    def __init__(self):
        import threading
        self.rows = {}
        self.mutex = threading.Lock()

    def insert(self, k, v):
        self.rows[k] = (v, 0)

    def get(self, k):
        return self.rows.get(k)


@pytest.fixture(scope="module")
def loaded():
    s = TPCC.Schema(CFG, warehouses=2, customers=30, items=500)
    t = Table()
    s.load(t, seed=2**31 + 5)
    return s, t


def test_full_size_cardinalities():
    s = TPCC.Schema(CFG)
    assert (s.W, s.D, s.C, s.I) == (20, 10, 3000, 100_000)
    assert s.rows == 20 + 200 + 600_000 + 100_000 + 2_000_000
    assert s.capacity >= s.rows + CFG["insert_headroom"]


def test_loaded_cardinalities(loaded):
    s, t = loaded
    count = {}
    for k in t.rows:
        count[k[0]] = count.get(k[0], 0) + 1
    assert count == {"W": 2, "D": 20, "C": 600, "I": 500, "S": 1000}
    assert len(t.rows) == s.rows


@pytest.mark.parametrize("prefix,table", [
    ("W", "warehouse"), ("D", "district"), ("C", "customer"), ("I", "item"),
    ("S", "stock")])
def test_loaded_row_widths(loaded, prefix, table):
    _, t = loaded
    widths = {len(v) for k, (v, _) in t.rows.items() if k[0] == prefix}
    assert widths == {CFG["row_bytes"][table]}


def test_loaded_fields(loaded):
    _, t = loaded
    d = t.get("D2.10")[0]
    assert struct.unpack_from("<qi", d, 4) == (3_000_000, 3001)
    assert struct.unpack_from("<q", t.get("W1")[0], 4)[0] == 30_000_000
    price = struct.unpack_from("<i", t.get("I500")[0], 0)[0]
    assert 100 <= price <= 10_000


def test_nurand_ranges():
    s = TPCC.Schema(CFG)
    src = TPCC.Source(s, Table(), seed=11)
    c = [src.nurand(1023, src.c_id_c, 1, 3000) for _ in range(20_000)]
    i = [src.nurand(8191, src.ol_i_c, 1, 100_000) for _ in range(20_000)]
    assert min(c) >= 1 and max(c) <= 3000
    assert min(i) >= 1 and max(i) <= 100_000
    # non-uniform: the most drawn customer id is far above the mean count
    assert np.bincount(c).max() > 5 * len(c) / 3000


def test_mix_and_transaction_shapes(loaded):
    s, t = loaded
    src = TPCC.Source(s, t, seed=3)
    specs = [f() for f in src.take(4000)]
    new_orders = [x for x in specs if x.writes[0][0].startswith("D")]
    payments = [x for x in specs if x.writes[0][0].startswith("W")]
    assert len(new_orders) + len(payments) == 4000
    assert abs(len(new_orders) / 4000 - 0.5) < 0.03
    for x in new_orders:
        ol = (len(x.writes) - 3) // 2
        assert 5 <= ol <= 15
        assert len(x.reads) == 3 + 2 * ol == len(x.observed)
        widths = [len(v) for _, v in x.writes]
        assert widths == ([95] + [306] * ol + [24, 8] + [54] * ol)
    for x in payments:
        assert [len(v) for _, v in x.writes] == [89, 95, 655, 46]
        assert len(x.reads) == 3
    remote = sum(x.writes[2][0].split(".")[0] != "C" + x.writes[0][0][1:]
                 for x in payments)
    assert 0.10 < remote / len(payments) < 0.20


def test_closed_loop_homes_bind_warehouses(loaded):
    s, t = loaded
    src = TPCC.Source(s, t, seed=4)
    for home, f in zip(range(40), src.take(40, homes=range(40))):
        key = f().reads[0]
        assert key == f"W{home % s.W + 1}"


def test_new_order_reads_then_writes_next_order_id(loaded):
    s, t = loaded
    src = TPCC.Source(s, t, seed=5)
    spec = src.new_order(1)()
    d_key, d_val = spec.writes[0]
    before = struct.unpack_from("<i", t.get(d_key)[0], 12)[0]
    assert struct.unpack_from("<i", d_val, 12)[0] == before + 1
    assert spec.writes[-1][0].startswith(f"L1.{d_key.split('.')[1]}.{before}.")


def _image(rows):
    return {k.encode(): (v, 1) for k, v in rows.items()}


def test_consistency_accepts_a_sound_image():
    d = bytes(4) + struct.pack("<qi", 3_000_000 + 500, 3003) + bytes(79)
    w = bytes(4) + struct.pack("<q", 30_000_500) + bytes(77)
    rows = {"W1": w, "D1.1": d}
    for o in (3001, 3002):
        rows[f"O1.1.{o}"] = struct.pack("<iqiii", 1, 0, 0, 2, 1)
        rows[f"N1.1.{o}"] = struct.pack("<ihh", o, 1, 1)
        rows[f"L1.1.{o}.1"] = bytes(54)
        rows[f"L1.1.{o}.2"] = bytes(54)
    assert TPCC.Schema(CFG).consistency(_image(rows)) == 0
    # a lost order, a lost line and a YTD out of step are each a violation
    bad = dict(rows)
    del bad["O1.1.3002"]
    assert TPCC.Schema(CFG).consistency(_image(bad)) > 0
    bad = dict(rows)
    del bad["L1.1.3001.2"]
    assert TPCC.Schema(CFG).consistency(_image(bad)) == 1
    bad = dict(rows, W1=bytes(4) + struct.pack("<q", 30_000_400) + bytes(77))
    assert TPCC.Schema(CFG).consistency(_image(bad)) == 1
