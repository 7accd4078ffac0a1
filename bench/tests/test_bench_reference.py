"""The plain reference agrees with the program's log format, and the
warm-up covers every kernel shape a cut can make."""

import random

import numpy as np
import pytest

import _paths  # noqa: F401
from harness import cell, reference


def _txn(rng):
    from repro.core.txn import Txn

    t = Txn(tid=rng.randrange(1, 1 << 40))
    t.ssn = rng.randrange(1, 1 << 40)
    t.write_set = [(f"k{rng.randrange(10**6)}", rng.randbytes(rng.randrange(0, 300)))
                   for _ in range(rng.randrange(0, 6))]
    if rng.random() < 0.5:
        t.read_set = [("r", 1)]
    return t


def test_encode_matches_the_programs_frames():
    rng = random.Random(5)
    for _ in range(200):
        t = _txn(rng)
        flags = reference.HAS_READS if t.read_set else 0
        assert reference.encode(
            t.ssn, t.tid, flags,
            [(k.encode(), v) for k, v in t.write_set]) == t.encode()


def test_decode_stops_at_a_torn_or_corrupt_frame():
    rng = random.Random(6)
    blobs = [_txn(rng).encode() for _ in range(20)]
    whole = b"".join(blobs)
    frames, used = reference.decode(whole + blobs[0][:-3])
    assert len(frames) == 20 and used == len(whole)
    bad = bytearray(whole)
    bad[len(blobs[0]) + 12] ^= 1
    frames, used = reference.decode(bytes(bad))
    assert len(frames) == 1 and used == len(blobs[0])


def test_recover_applies_the_rsne_rule():
    F = reference.Frame
    dev0 = [F(0, 0, 5, 1, 0, [(b"a", b"1")]),
            F(0, 0, 9, 2, reference.HAS_READS, [(b"a", b"2")])]
    dev1 = [F(0, 0, 7, 3, reference.HAS_READS, [(b"b", b"3")])]
    image, rsne, n = reference.recover([dev0, dev1])
    assert rsne == 7 and n == 3
    # ssn 9 has reads and lies above RSNe: not replayed
    assert image == {b"a": (b"1", 5), b"b": (b"3", 7)}


def _brute(accesses, writes, max_cut, fused_min):
    """Every (kernel call) shape, from every way to fill a cut."""
    seg, fused = set(), set()
    for n in range(1, max_cut + 1):
        for a in range(n * accesses[0], n * accesses[1] + 1):
            if a < fused_min:
                seg.add((cell._pow2(a), cell._pow2(n), "max"))
                for w in range(n * writes[0], min(n * writes[1], a) + 1):
                    seg.add((cell._pow2(w), cell._pow2(w), "min"))
            else:
                for k in range(accesses[0], accesses[1] + 1):
                    if k + (n - 1) * accesses[0] <= a <= n * k:
                        fused.add((cell._bucket(n), cell._bucket(k, 1)))
    return seg, fused


@pytest.mark.parametrize("accesses,writes,max_cut,fused_min", [
    ((1, 1), (1, 1), 256, 2048), ((1, 1), (1, 1), 4096, 2048),
    ((7, 66), (4, 33), 32, 2048), ((2, 9), (1, 4), 40, 64)])
def test_warm_shapes_cover_every_cut(accesses, writes, max_cut, fused_min):
    got = cell.warm_shapes(accesses, writes, max_cut, fused_min)
    seg, fused = _brute(accesses, writes, max_cut, fused_min)
    assert seg <= set(got["seg"])
    assert fused <= set(got["fused"])


def test_ycsb_zipf_warms_the_eighteen_cut_shapes():
    got = cell.warm_shapes((1, 1), (1, 1), 256, 2048)
    assert len(got["seg"]) == 18 and not got["fused"]


def test_scan_ladder_reaches_a_sealed_segment():
    ring = 30 * 1024 * 1024
    assert cell.scan_ladder(1310720, ring, 1051) == [
        1024, 2048, 4096, 8192, 16384, 32768]
    assert cell.scan_ladder(1310720, ring, 150)[-1] == 262144
    assert np.all(np.diff(cell.scan_ladder(1310720, ring, 150)) > 0)


@pytest.mark.parametrize("seen,bad", [(b"v1", 0), (b"v0", 1), (b"zz", 1)])
def test_values_read_are_held_to_the_version_observed(seen, bad):
    """A read that observed SSN 1 of ``k`` must have seen the value that
    committed with SSN 1, not the loaded one nor any other."""
    S = reference.Spec
    w = S(writes=[("k", b"v1")])
    r = S(reads=["k"], observed=[1], values=[seen], writes=[("j", b"x")])
    streams = [reference.encode(1, 1, 0, [(b"k", b"v1")])
               + reference.encode(2, 1 + reference.TID_STRIDE,
                                  reference.HAS_READS, [(b"j", b"x")])]
    cuts = [reference.Cut([0], [w], [0], [(0, 1, 1)], []),
            reference.Cut([1], [r], [0],
                          [(0, 2, 1 + reference.TID_STRIDE)], [])]
    v = reference.replay_cuts(cuts, 1, streams, b"", loaded={"k": b"v0"})
    assert v.checks["read_value_mismatch"] == bad
    assert v.checks["outcome_mismatch"] == v.checks["ssn_mismatch"] == 0
    assert v.checks["log_frame_mismatch"] == 0
