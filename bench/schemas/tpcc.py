"""TPC-C v5.11 New-Order (§2.4) and Payment (§2.5) over the flat key space.

Rows keep the specification's widths; the fields the two transactions read
or change sit little-endian at the front of each row, the rest is seeded
filler.  Ids are 1-based as in the specification.

=============  ==================  =====  ===============================
table          key                 bytes  leading fields
=============  ==================  =====  ===============================
WAREHOUSE      ``W<w>``              89   i32 tax (1e-4), i64 ytd (cents)
DISTRICT       ``D<w>.<d>``          95   i32 tax, i64 ytd, i32 next_o_id
CUSTOMER       ``C<w>.<d>.<c>``     655   i32 discount, i64 balance,
                                          i64 ytd_payment, i32 payment_cnt
ITEM           ``I<i>``              82   i32 price (cents)
STOCK          ``S<w>.<i>``         306   i32 quantity, ytd, order_cnt,
                                          remote_cnt
ORDER          ``O<w>.<d>.<o>``      24   i32 c_id, i64 entry_d, i32
                                          carrier_id, ol_cnt, all_local
NEW-ORDER      ``N<w>.<d>.<o>``       8   i32 o_id, i16 d_id, i16 w_id
ORDER-LINE     ``L<w>.<d>.<o>.<n>``  54   i32 i_id, supply_w_id, quantity,
                                          i64 amount, delivery_d
HISTORY        ``H<tag>``            46   i32 c_id, i16 c_d_id, c_w_id,
                                          d_id, w_id, i64 date, amount
=============  ==================  =====  ===============================

Each client transaction is a closure that reads its rows when it is called,
and the scheduler calls it again on every retry, so a retry re-reads.  The
reads take the table's mutex, which the executor holds through validation
and write-back, so a closure never sees a value and an SSN from different
writes.  Each call hands over a spec under a tag of its own and keeps, under
that tag, what it read (keys, SSNs, values) and wrote, for the reference.
"""

from __future__ import annotations

import itertools
import struct
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from harness.probes import Tagged
from harness.reference import Spec

LOAD_BLOCK = 1 << 16
FIRST_O_ID = 3001          # §4.3.3.1: 3,000 orders per district are loaded
W_YTD0 = 30_000_000        # 300,000.00
D_YTD0 = 3_000_000         # 30,000.00

_i = struct.Struct("<i")
_q = struct.Struct("<q")


def w_key(w): return f"W{w}"
def d_key(w, d): return f"D{w}.{d}"
def c_key(w, d, c): return f"C{w}.{d}.{c}"
def i_key(i): return f"I{i}"
def s_key(w, i): return f"S{w}.{i}"
def o_key(w, d, o): return f"O{w}.{d}.{o}"
def no_key(w, d, o): return f"N{w}.{d}.{o}"
def ol_key(w, d, o, n): return f"L{w}.{d}.{o}.{n}"


def _put(v: bytes, off: int, st: struct.Struct, x: int) -> bytes:
    return v[:off] + st.pack(x) + v[off + st.size:]


def _rows(rng: np.random.Generator, n: int, width: int,
          fields: Sequence[Tuple[int, str, np.ndarray]]) -> bytes:
    """``n`` rows of ``width`` bytes: filler, then each ``(offset, dtype,
    values)`` field written little-endian."""
    arr = np.frombuffer(rng.bytes(n * width), np.uint8).reshape(n, width).copy()
    for off, dt, vals in fields:
        col = np.asarray(vals, dtype=dt)
        arr[:, off:off + col.itemsize] = col.view(np.uint8).reshape(
            n, col.itemsize)
    return arr.tobytes()


class Schema:
    """Loader, clients and consistency rule of the TPC-C deployment ``cfg``."""

    def __init__(self, cfg: Dict, warehouses: Optional[int] = None,
                 customers: Optional[int] = None, items: Optional[int] = None):
        self.cfg = cfg
        self.W = int(warehouses or cfg["warehouses"])
        self.D = int(cfg["districts_per_warehouse"])
        self.C = int(customers or cfg["customers_per_district"])
        self.I = int(items or cfg["items"])
        self.width = {k: int(v) for k, v in cfg["row_bytes"].items()}
        self.rows = (self.W + self.W * self.D + self.W * self.D * self.C
                     + self.I + self.W * self.I)
        self.capacity = 1 << (self.rows + int(cfg["insert_headroom"])
                              ).bit_length()
        lo, hi = cfg["order_lines"]
        # keys a transaction touches: Payment 3 reads + 4 writes; New-Order
        # 3 + 2*ol reads and 3 + 2*ol writes
        self.accesses = (7, 6 + 4 * hi)
        self.writes = (4, 3 + 2 * hi)
        # framed bytes per write lane, least over the two transactions
        # (a New-Order with the most lines)
        self.lane_bytes_min = 150

    # --- load -----------------------------------------------------------------
    def _blocks(self, seed: int) -> Iterator[Tuple[List[str], bytes, int]]:
        """The loaded rows from the seed: ``(keys, rows, width)`` blocks."""
        rng = np.random.default_rng([seed, 1])
        W, D, C, I = self.W, self.D, self.C, self.I
        wd = self.width
        yield [w_key(w) for w in range(1, W + 1)], _rows(
            rng, W, wd["warehouse"], [
                (0, "<i4", rng.integers(0, 2001, W)),
                (4, "<i8", np.full(W, W_YTD0))]), wd["warehouse"]
        n = W * D
        yield ([d_key(w, d) for w in range(1, W + 1) for d in range(1, D + 1)],
               _rows(rng, n, wd["district"], [
                   (0, "<i4", rng.integers(0, 2001, n)),
                   (4, "<i8", np.full(n, D_YTD0)),
                   (12, "<i4", np.full(n, FIRST_O_ID))]), wd["district"])
        for w in range(1, W + 1):
            for d in range(1, D + 1):
                yield ([c_key(w, d, c) for c in range(1, C + 1)],
                       _rows(rng, C, wd["customer"], [
                           (0, "<i4", rng.integers(0, 5001, C)),
                           (4, "<i8", np.full(C, -1000)),
                           (12, "<i8", np.full(C, 1000)),
                           (20, "<i4", np.ones(C))]), wd["customer"])
        for lo in range(0, I, LOAD_BLOCK):
            m = min(I, lo + LOAD_BLOCK) - lo
            yield ([i_key(i) for i in range(lo + 1, lo + m + 1)],
                   _rows(rng, m, wd["item"], [
                       (0, "<i4", rng.integers(100, 10001, m)),
                       (4, "<i4", rng.integers(1, 10001, m))]), wd["item"])
        for w in range(1, W + 1):
            for lo in range(0, I, LOAD_BLOCK):
                m = min(I, lo + LOAD_BLOCK) - lo
                yield ([s_key(w, i) for i in range(lo + 1, lo + m + 1)],
                       _rows(rng, m, wd["stock"], [
                           (0, "<i4", rng.integers(10, 101, m)),
                           (4, "<i4", np.zeros(m)),
                           (8, "<i4", np.zeros(m)),
                           (12, "<i4", np.zeros(m))]), wd["stock"])

    def load(self, table, seed: int) -> None:
        for keys, blob, width in self._blocks(seed):
            for j, k in enumerate(keys):
                table.insert(k, blob[j * width:(j + 1) * width])

    def loaded(self, seed: int, keys) -> Dict[str, bytes]:
        """The loaded value of each of ``keys`` that the load holds, from
        the seed alone (no table)."""
        want = set(keys)
        out = {}
        for ks, blob, width in self._blocks(seed):
            for j, k in enumerate(ks):
                if k in want:
                    out[k] = blob[j * width:(j + 1) * width]
        return out

    def clients(self, traffic: Dict, table, seed: int) -> "Source":
        return Source(self, table, seed)

    # --- consistency (TPC-C §3.3.2, conditions 1-4 over the image) ------------
    def consistency(self, image) -> int:
        """Violations of: W_YTD = sum of its districts' D_YTD (1);
        D_NEXT_O_ID - 1 = max O_ID = max NO_O_ID, with the district's orders
        and new-orders exactly ``FIRST_O_ID .. D_NEXT_O_ID - 1`` (2, 3); and
        every order's ``O_OL_CNT`` order-lines present (4)."""
        d_ytd: Dict[int, int] = {}
        next_o: Dict[Tuple[int, int], int] = {}
        w_ytd: Dict[int, int] = {}
        orders: Dict[Tuple[int, int], Dict[int, int]] = {}
        new_orders: Dict[Tuple[int, int], set] = {}
        lines: Dict[Tuple[int, int, int], int] = {}
        for kb, (val, _) in image.items():
            k = kb.decode()
            t, rest = k[0], k[1:].split(".")
            if t == "W":
                w_ytd[int(rest[0])] = _q.unpack_from(val, 4)[0]
            elif t == "D":
                w, d = int(rest[0]), int(rest[1])
                d_ytd[w] = d_ytd.get(w, 0) + _q.unpack_from(val, 4)[0] - D_YTD0
                next_o[(w, d)] = _i.unpack_from(val, 12)[0]
            elif t == "O":
                w, d, o = map(int, rest)
                orders.setdefault((w, d), {})[o] = _i.unpack_from(val, 16)[0]
            elif t == "N":
                w, d, o = map(int, rest)
                new_orders.setdefault((w, d), set()).add(o)
            elif t == "L":
                w, d, o, _n = map(int, rest)
                lines[(w, d, o)] = lines.get((w, d, o), 0) + 1
        bad = 0
        for w in set(w_ytd) | set(d_ytd):
            bad += w_ytd.get(w, W_YTD0) - W_YTD0 != d_ytd.get(w, 0)
        for wd in set(next_o) | set(orders) | set(new_orders):
            want = set(range(FIRST_O_ID, next_o.get(wd, FIRST_O_ID)))
            bad += set(orders.get(wd, {})) != want
            bad += new_orders.get(wd, set()) != want
            for o, cnt in orders.get(wd, {}).items():
                bad += lines.get((wd[0], wd[1], o), 0) != cnt
        return bad


class Source:
    """New-Order and Payment closures in a fixed order drawn from the seed."""

    def __init__(self, schema: Schema, table, seed: int):
        cfg = schema.cfg
        self.s = schema
        self.table = table
        self.rng = np.random.default_rng([seed, 2])
        # NURand run constants C (§2.1.6), drawn once per run
        self.c_id_a = int(cfg["nurand"]["c_id"])
        self.ol_i_a = int(cfg["nurand"]["ol_i_id"])
        self.c_id_c = int(self.rng.integers(0, self.c_id_a + 1))
        self.ol_i_c = int(self.rng.integers(0, self.ol_i_a + 1))
        self.p_new_order = float(cfg["mix"]["new_order"])
        self.ol_lo, self.ol_hi = cfg["order_lines"]
        self.remote_supply = float(cfg["remote_supply"])
        self.remote_payment = float(cfg["remote_payment"])
        self.tag = 0
        self.keep = True                 # keep each attempt for the reference
        self.built: Dict[int, Tuple] = {}
        self._tags = itertools.count()

    def _hand_over(self, reads, got, writes) -> Tagged:
        """The spec of one attempt, kept under its tag as plain tuples."""
        tag = next(self._tags)
        observed = [ssn for _, ssn in got]
        if self.keep:
            self.built[tag] = (reads, tuple(observed),
                               tuple(v for v, _ in got), tuple(writes))
        return Tagged(reads=list(reads), writes=writes, observed=observed,
                      tag=tag)

    def spec(self, tag: int) -> Spec:
        reads, observed, values, writes = self.built[tag]
        return Spec(reads=reads, writes=writes, observed=observed,
                    values=values)

    def nurand(self, a: int, c: int, x: int, y: int) -> int:
        r = self.rng
        return (((int(r.integers(0, a + 1)) | int(r.integers(x, y + 1))) + c)
                % (y - x + 1)) + x

    def _other_w(self, w: int) -> int:
        o = int(self.rng.integers(1, self.s.W))
        return o + (o >= w)

    def take(self, n: int, homes: Optional[Sequence[int]] = None) -> List:
        out = []
        for j in range(n):
            w = (int(homes[j]) % self.s.W + 1 if homes is not None
                 else int(self.rng.integers(1, self.s.W + 1)))
            if self.rng.random() < self.p_new_order:
                out.append(self.new_order(w))
            else:
                out.append(self.payment(w))
        return out

    def new_order(self, w: int):
        s, r = self.s, self.rng
        d = int(r.integers(1, s.D + 1))
        c = self.nurand(self.c_id_a, self.c_id_c, 1, s.C)
        cnt = int(r.integers(self.ol_lo, self.ol_hi + 1))
        items: List[int] = []
        while len(items) < cnt:           # distinct items within one order
            i = self.nurand(self.ol_i_a, self.ol_i_c, 1, s.I)
            if i not in items:
                items.append(i)
        supply = [self._other_w(w) if s.W > 1 and r.random() < self.remote_supply
                  else w for _ in items]
        qty = [int(q) for q in r.integers(1, 11, cnt)]
        entry_d = int(r.integers(1, 1 << 40))
        table = self.table
        wk, dk, ck = w_key(w), d_key(w, d), c_key(w, d, c)
        ik = [i_key(i) for i in items]
        sk = [s_key(sw, i) for sw, i in zip(supply, items)]
        reads = tuple([wk, dk, ck] + ik + sk)
        all_local = int(all(sw == w for sw in supply))

        def build() -> Tagged:
            with table.mutex:
                got = [table.get(k) for k in reads]
            dv = got[1][0]
            o = _i.unpack_from(dv, 12)[0]
            writes = [(dk, _put(dv, 12, _i, o + 1))]
            lines = []
            for n, (i, sw, q) in enumerate(zip(items, supply, qty)):
                price = _i.unpack_from(got[3 + n][0], 0)[0]
                sv = got[3 + cnt + n][0]
                sq, sytd, socnt, srem = struct.unpack_from("<iiii", sv, 0)
                sq = sq - q if sq - q >= 10 else sq - q + 91
                sv = struct.pack("<iiii", sq, sytd + q, socnt + 1,
                                 srem + (sw != w)) + sv[16:]
                writes.append((sk[n], sv))
                lines.append((ol_key(w, d, o, n + 1),
                              struct.pack("<iiiqq", i, sw, q, q * price, 0)
                              + bytes(s.width["order_line"] - 28)))
            writes.append((o_key(w, d, o), struct.pack(
                "<iqiii", c, entry_d, 0, cnt, all_local)))
            writes.append((no_key(w, d, o), struct.pack("<ihh", o, d, w)))
            writes += lines
            return self._hand_over(reads, got, writes)

        return build

    def payment(self, w: int):
        s, r = self.s, self.rng
        d = int(r.integers(1, s.D + 1))
        if s.W > 1 and r.random() < self.remote_payment:
            cw, cd = self._other_w(w), int(r.integers(1, s.D + 1))
        else:
            cw, cd = w, d
        c = self.nurand(self.c_id_a, self.c_id_c, 1, s.C)
        amount = int(r.integers(100, 500_001))
        date = int(r.integers(1, 1 << 40))
        self.tag += 1
        hk = f"H{self.tag}"
        table = self.table
        wk, dk, ck = w_key(w), d_key(w, d), c_key(cw, cd, c)
        reads = (wk, dk, ck)
        h_row = (struct.pack("<ihhhhqq", c, cd, cw, d, w, date, amount)
                 + bytes(s.width["history"] - 28))

        def build() -> Tagged:
            with table.mutex:
                got = [table.get(k) for k in reads]
            wv, dv, cv = (g[0] for g in got)
            wv = _put(wv, 4, _q, _q.unpack_from(wv, 4)[0] + amount)
            dv = _put(dv, 4, _q, _q.unpack_from(dv, 4)[0] + amount)
            bal, ytd = struct.unpack_from("<qq", cv, 4)
            cnt = _i.unpack_from(cv, 20)[0]
            cv = (cv[:4] + struct.pack("<qqi", bal - amount, ytd + amount,
                                       cnt + 1) + cv[24:])
            return self._hand_over(
                reads, got, [(wk, wv), (dk, dv), (ck, cv), (hk, h_row)])

        return build
