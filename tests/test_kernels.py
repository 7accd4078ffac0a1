"""Per-kernel shape sweeps of the OLTP kernels: Pallas (interpret=True) and
the compiled fused entry points vs the ref.py oracles, exact in int32."""

import numpy as np
import pytest

from repro.kernels.batch_occ import NO_WRITER
from repro.kernels.bucketing import bucket, pad_i32, stack_i32
from repro.kernels.ops import (fused_replay_apply, fused_replay_scan,
                               fused_validate_sequence, occ_seg_reduce,
                               ssn_scatter_max)
from repro.kernels.ref import scatter_max_ref, seg_reduce_ref
from repro.kernels.scatter_max import NO_POS


@pytest.mark.parametrize(
    "n_slots,n_writes,block_s,block_w,ckpt_frac",
    [
        (64, 256, 128, 128, 0.0),     # single slot block, padded writes
        (300, 1000, 128, 256, 0.3),   # unaligned sizes, checkpoint image
        (1000, 300, 256, 128, 0.9),   # more slots than writes
        (17, 5, 128, 128, 0.5),       # tiny
    ],
)
def test_ssn_scatter_max_vs_ref(n_slots, n_writes, block_s, block_w, ckpt_frac):
    """SSN-guarded scatter-max vs the sequential numpy oracle, with duplicate
    keys, duplicate SSNs (tie -> smallest position), and checkpoint slots
    that must win their SSN ties (pos -1)."""
    rng = np.random.default_rng(n_slots * 7 + n_writes)
    image_ssn = np.full(n_slots, -1, np.int32)
    image_pos = np.full(n_slots, NO_POS, np.int32)
    ckpt = rng.random(n_slots) < ckpt_frac
    image_ssn[ckpt] = rng.integers(0, 50, ckpt.sum())
    image_pos[ckpt] = -1

    key = rng.integers(0, n_slots, n_writes).astype(np.int32)
    ssn = rng.integers(0, 60, n_writes).astype(np.int32)   # dense: many ties
    pos = np.arange(n_writes, dtype=np.int32)

    out_ssn, out_pos = ssn_scatter_max(
        image_ssn, image_pos, key, ssn, pos,
        block_s=block_s, block_w=block_w, interpret=True,
    )
    ref_ssn, ref_pos = scatter_max_ref(image_ssn, image_pos, key, ssn, pos)
    np.testing.assert_array_equal(np.asarray(out_ssn), ref_ssn)
    np.testing.assert_array_equal(np.asarray(out_pos), ref_pos)


def test_ssn_scatter_max_empty_writes_is_identity():
    image_ssn = np.arange(8, dtype=np.int32)
    image_pos = np.full(8, -1, np.int32)
    out_ssn, out_pos = ssn_scatter_max(
        image_ssn, image_pos,
        np.empty(0, np.int32), np.empty(0, np.int32), np.empty(0, np.int32),
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(out_ssn), image_ssn)
    np.testing.assert_array_equal(np.asarray(out_pos), image_pos)


@pytest.mark.parametrize("op", ["max", "min"])
@pytest.mark.parametrize(
    "n_slots,n_items,block_s,block_w",
    [
        (64, 256, 128, 128),    # single slot block, padded items
        (300, 1000, 128, 256),  # unaligned sizes
        (1000, 37, 256, 128),   # sparse: most slots empty
        (5, 3, 128, 128),       # tiny
    ],
)
def test_occ_seg_reduce_vs_ref(op, n_slots, n_items, block_s, block_w):
    """Batched-OCC segmented reduce (base-SSN max / first-writer min) vs the
    sequential oracle, including empty slots (identity sentinels)."""
    rng = np.random.default_rng(n_slots * 13 + n_items + (op == "min"))
    key = rng.integers(0, n_slots, n_items).astype(np.int32)
    val = rng.integers(0, 500, n_items).astype(np.int32)
    out = occ_seg_reduce(key, val, n_slots=n_slots, op=op,
                         block_s=block_s, block_w=block_w, interpret=True)
    ref = seg_reduce_ref(key, val, n_slots, op)
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_occ_seg_reduce_empty_items():
    out = occ_seg_reduce(np.empty(0, np.int32), np.empty(0, np.int32),
                         n_slots=7, op="max", interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.full(7, -1, np.int32))
    out = occ_seg_reduce(np.empty(0, np.int32), np.empty(0, np.int32),
                         n_slots=7, op="min", interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.full(7, np.iinfo(np.int32).max, np.int32)
    )


# --- compiled XLA twins of the OLTP kernels ----------------------------------

def _xla_twin_inputs(n_slots, n_writes, ckpt_frac, seed, pad_lanes=0):
    rng = np.random.default_rng(seed)
    image_ssn = np.full(n_slots, -1, np.int32)
    image_pos = np.full(n_slots, NO_POS, np.int32)
    ckpt = rng.random(n_slots) < ckpt_frac
    image_ssn[ckpt] = rng.integers(0, 50, ckpt.sum())
    image_pos[ckpt] = -1
    key = rng.integers(0, n_slots, n_writes).astype(np.int32)
    ssn = rng.integers(0, 60, n_writes).astype(np.int32)   # dense: many ties
    pos = np.arange(n_writes, dtype=np.int32)
    if pad_lanes:
        # padding lanes target the overflow slot with reduction identities —
        # they must not influence any real slot
        key = np.concatenate([key, np.full(pad_lanes, n_slots, np.int32)])
        ssn = np.concatenate([ssn, np.full(pad_lanes, -1, np.int32)])
        pos = np.concatenate([pos, np.full(pad_lanes, NO_POS, np.int32)])
    return image_ssn, image_pos, key, ssn, pos


@pytest.mark.parametrize("n_slots,n_writes,ckpt_frac,pad_lanes", [
    (64, 256, 0.0, 0),
    (300, 1000, 0.3, 24),    # checkpoint ties + overflow padding lanes
    (1000, 300, 0.9, 1),
    (17, 5, 0.5, 3),
])
def test_scatter_max_xla_equals_pallas_and_ref(n_slots, n_writes, ckpt_frac,
                                               pad_lanes):
    """The compiled XLA twin == the Pallas kernel (interpret) == the
    sequential oracle, including overflow-slot padding lanes the twin must
    drop."""
    from repro.kernels.scatter_max import ssn_scatter_max_xla

    image_ssn, image_pos, key, ssn, pos = _xla_twin_inputs(
        n_slots, n_writes, ckpt_frac, seed=n_slots + n_writes,
        pad_lanes=pad_lanes)
    out_ssn, out_pos = ssn_scatter_max_xla(image_ssn, image_pos,
                                           key, ssn, pos, n_slots)
    ref_ssn, ref_pos = scatter_max_ref(image_ssn, image_pos,
                                       key[: n_writes], ssn[: n_writes],
                                       pos[: n_writes])
    np.testing.assert_array_equal(np.asarray(out_ssn)[:n_slots], ref_ssn)
    np.testing.assert_array_equal(np.asarray(out_pos)[:n_slots], ref_pos)
    k_ssn, k_pos = ssn_scatter_max(image_ssn, image_pos, key[: n_writes],
                                   ssn[: n_writes], pos[: n_writes],
                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(out_ssn)[:n_slots], np.asarray(k_ssn))
    np.testing.assert_array_equal(np.asarray(out_pos)[:n_slots], np.asarray(k_pos))


def test_fused_replay_entry_points_match_ref():
    """The jitted fused entry points (stacked single-transfer layouts used by
    the recovery/replica hot paths) == the sequential oracle."""
    n_slots, n_writes, pad = 256, 900, 124
    image_ssn, image_pos, key, ssn, pos = _xla_twin_inputs(
        n_slots, n_writes, 0.4, seed=99, pad_lanes=pad)
    ref_ssn, ref_pos = scatter_max_ref(image_ssn, image_pos, key[:n_writes],
                                       ssn[:n_writes], pos[:n_writes])
    scan = np.stack([key, ssn, pos])
    image = np.stack([image_ssn, image_pos])
    out_ssn, out_pos = fused_replay_apply(image, scan)
    np.testing.assert_array_equal(np.asarray(out_ssn)[:n_slots], ref_ssn)
    np.testing.assert_array_equal(np.asarray(out_pos)[:n_slots], ref_pos)

    # fused_replay_scan: same reduction against an empty image
    empty_ssn = np.full(n_slots, -1, np.int32)
    empty_pos = np.full(n_slots, NO_POS, np.int32)
    ref2_ssn, ref2_pos = scatter_max_ref(empty_ssn, empty_pos, key[:n_writes],
                                         ssn[:n_writes], pos[:n_writes])
    s_ssn, s_pos = fused_replay_scan(scan, n_slots=n_slots)
    np.testing.assert_array_equal(np.asarray(s_ssn)[:n_slots], ref2_ssn)
    np.testing.assert_array_equal(np.asarray(s_pos)[:n_slots], ref2_pos)


def _validate_brute(acc, a_len, n_txn, k):
    """Per-transaction python walk of the §4.2/§4.4 rules."""
    row, pos, iw, obs, ssn_now, locked = (acc[i].astype(np.int64)
                                          for i in range(6))
    fw = {}
    for t in range(n_txn):
        for j in range(int(a_len[t])):
            lane = t * k + j
            if iw[lane]:
                r = int(row[lane])
                fw[r] = min(fw.get(r, 1 << 31), int(pos[lane]))
    survive = np.zeros(n_txn, bool)
    bases = np.zeros(n_txn, np.int64)
    for t in range(n_txn):
        ok, base = True, 0
        for j in range(int(a_len[t])):
            lane = t * k + j
            base = max(base, int(ssn_now[lane]))
            if fw.get(int(row[lane]), 1 << 31) < int(pos[lane]):
                ok = False        # someone earlier in the batch writes it
            if obs[lane] >= 0 and ssn_now[lane] != obs[lane]:
                ok = False        # driver-observed SSN went stale
            if locked[lane]:
                ok = False
        survive[t] = ok
        bases[t] = base
    return survive, bases


@pytest.mark.parametrize("n_txn,k,cap,lock_frac", [
    (8, 1, 64, 0.0),
    (64, 4, 128, 0.2),      # ragged a_len, locked tuples
    (128, 2, 64, 0.0),      # conflict-heavy: cap << lanes
])
def test_validate_sequence_xla_vs_brute(n_txn, k, cap, lock_frac):
    from repro.kernels.batch_occ import validate_sequence_xla

    rng = np.random.default_rng(n_txn * 31 + k)
    lanes = n_txn * k
    acc = np.empty((6, lanes), np.int32)
    acc[0] = rng.integers(0, cap, lanes)
    acc[1] = rng.permutation(lanes)
    acc[2] = rng.integers(0, 2, lanes)
    ssn = rng.integers(1, 40, lanes).astype(np.int32)
    acc[3] = np.where(rng.random(lanes) < 0.5, ssn, -1)
    acc[4] = ssn
    acc[5] = (rng.random(lanes) < lock_frac).astype(np.int32)
    # stale observations for some read lanes
    stale = rng.random(lanes) < 0.15
    acc[3] = np.where(stale & (acc[3] >= 0), acc[3] + 1, acc[3])
    a_len = rng.integers(1, k + 1, n_txn).astype(np.int32)

    out_sv, out_b = validate_sequence_xla(acc, a_len, n_txn, k, cap)
    ref_sv, ref_b = _validate_brute(acc, a_len, n_txn, k)
    np.testing.assert_array_equal(np.asarray(out_sv), ref_sv)
    np.testing.assert_array_equal(np.asarray(out_b, np.int64), ref_b)


# --- the fused entry points across the bucket ladder ---------------------------

# Lane counts the served path feeds the fused entry points: below the smallest
# rung, exactly a rung, a rung plus one, the largest rung a latency cut of
# <= 256 single-key transactions reaches, the fused round's engagement
# threshold (``BatchOCC.fused_min_lanes``), and a 4,096-transaction cut.
LADDER_LANES = (3, 64, 65, 256, 2048, 4096)
LADDER_ENTRIES = ("occ_seg_reduce_max", "occ_seg_reduce_min",
                  "fused_validate_sequence", "fused_replay_scan",
                  "fused_replay_apply")


def _ladder_occ_seg_reduce(rng, lanes, op):
    """Padded as ``BatchOCC`` pads it: items and slots each to a power of
    two, pad items keyed ``-1`` with the reduction identity as value."""
    n_keys = max(1, lanes // 3)
    key = rng.integers(0, n_keys, lanes)
    val = rng.integers(0, 1 << 20, lanes)
    n_items = bucket(lanes, min_size=1)
    out = occ_seg_reduce(pad_i32(key, n_items, -1),
                         pad_i32(val, n_items, -1 if op == "max" else NO_WRITER),
                         n_slots=bucket(n_keys, min_size=1), op=op)
    np.testing.assert_array_equal(np.asarray(out)[:n_keys],
                                  seg_reduce_ref(key, val, n_keys, op))


def _ladder_validate_sequence(rng, lanes):
    """Padded as ``BatchOCC._fused_round`` pads it: a dense ``(n_txn, k)``
    layout with ragged access counts, transactions to a bucket, padded
    transactions at ``a_len = 0``; compared with the brute-force walk."""
    k = 2
    n_active = -(-lanes // k)
    n_txn = bucket(n_active)
    a_len = rng.integers(1, k + 1, n_active).astype(np.int32)
    rows = max(8, lanes // 2)                       # hot rows: real conflicts
    dense = n_txn * k
    acc = np.zeros((6, dense), np.int32)
    acc[0] = rng.integers(0, rows, dense)
    acc[1] = np.arange(dense)                       # txn-major batch position
    acc[2] = rng.integers(0, 2, dense)
    ssn = rng.integers(1, 1 << 20, dense).astype(np.int32)
    acc[4] = ssn
    obs = np.where(rng.random(dense) < 0.5, ssn, -1)
    acc[3] = np.where((rng.random(dense) < 0.1) & (obs >= 0), obs + 1, obs)
    acc[5] = rng.random(dense) < 0.05
    survive, bases = fused_validate_sequence(acc, pad_i32(a_len, n_txn, 0),
                                             n_txn=n_txn, k=k,
                                             cap=bucket(rows))
    ref_sv, ref_b = _validate_brute(acc, a_len, n_active, k)
    np.testing.assert_array_equal(np.asarray(survive)[:n_active], ref_sv)
    np.testing.assert_array_equal(np.asarray(bases, np.int64)[:n_active], ref_b)
    assert np.asarray(survive)[n_active:].all()     # padded txns are vacuous


def _ladder_replay(rng, lanes, preloaded):
    """Padded as recovery pads it: lanes to a bucket with overflow-slot
    identity lanes ``(S, -1, NO_POS)``; slots to a bucket (scan) or the
    image's own bucket (apply, with checkpoint entries at pos -1)."""
    n_slots = max(1, lanes // 2)
    slot = rng.integers(0, n_slots, lanes)
    ssn = rng.integers(0, max(4, lanes // 4), lanes)   # dense: many ties
    pos = np.arange(lanes)
    n_lanes = bucket(lanes)
    if preloaded:
        s_pad = bucket(n_slots)
        image_ssn = np.full(n_slots, -1, np.int32)
        image_pos = np.full(n_slots, NO_POS, np.int32)
        ckpt = rng.random(n_slots) < 0.3
        image_ssn[ckpt] = rng.integers(0, max(4, lanes // 4), ckpt.sum())
        image_pos[ckpt] = -1
        image = stack_i32([image_ssn, image_pos], s_pad, fills=(-1, int(NO_POS)))
        scan = stack_i32([slot, ssn, pos], n_lanes,
                         fills=(s_pad, -1, int(NO_POS)))
        out_ssn, out_pos = fused_replay_apply(image, scan)
    else:
        s_pad = 2 * n_lanes                         # recovery's hash slots
        image_ssn = np.full(n_slots, -1, np.int32)
        image_pos = np.full(n_slots, NO_POS, np.int32)
        scan = stack_i32([slot, ssn, pos], n_lanes,
                         fills=(s_pad, -1, int(NO_POS)))
        out_ssn, out_pos = fused_replay_scan(scan, n_slots=s_pad)
    ref_ssn, ref_pos = scatter_max_ref(image_ssn, image_pos, slot, ssn, pos)
    out_ssn, out_pos = np.asarray(out_ssn), np.asarray(out_pos)
    np.testing.assert_array_equal(out_ssn[:n_slots], ref_ssn)
    np.testing.assert_array_equal(out_pos[:n_slots], ref_pos)
    # slots no real lane maps to keep the empty identity: padding won nothing
    assert (out_ssn[n_slots:s_pad] == -1).all()
    assert (out_pos[n_slots:s_pad] == NO_POS).all()


@pytest.mark.parametrize("lanes", LADDER_LANES)
@pytest.mark.parametrize("entry", LADDER_ENTRIES)
def test_fused_entry_points_on_the_ladder(entry, lanes):
    """Every fused entry point the chip runs, at the padded shapes the cells
    step through, equals its sequential oracle on the real lanes."""
    rng = np.random.default_rng(lanes * 7 + LADDER_ENTRIES.index(entry))
    if entry.startswith("occ_seg_reduce"):
        _ladder_occ_seg_reduce(rng, lanes, entry.rsplit("_", 1)[1])
    elif entry == "fused_validate_sequence":
        _ladder_validate_sequence(rng, lanes)
    else:
        _ladder_replay(rng, lanes, preloaded=entry == "fused_replay_apply")
