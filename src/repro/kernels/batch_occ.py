"""Segmented reduce kernel (Pallas / TPU) — batched OCC conflict detection.

The batched forward path (paper §4.2/§4.4, `repro.db.batch`) has one hot
array step, used twice per validation round:

* **base-SSN max** (Algorithm 1 lines 1–4, batched): per *transaction*, the
  max tuple SSN over its accesses — a segmented max keyed by txn id;
* **first-writer min** (intra-batch WW/RW conflicts): per *tuple*, the
  smallest batch position among the transactions that write it — a
  segmented min keyed by (compacted) tuple row id.  A transaction survives
  the round iff every tuple it touches has ``first_writer_pos >= its own
  position`` (first-come-wins).

Both are the same primitive: ``out[k] = reduce(val[i] for i where
key[i] == k)``.  This kernel evaluates it with a one-hot
compare-and-reduce, scatter_max style:

* the grid is ``(slot_blocks, item_blocks)`` — slot blocks are independent
  ("parallel"); item blocks accumulate sequentially ("arbitrary") into the
  output, so the slot vector stays resident in VMEM while the item stream
  is blocked through;
* within an item block the per-slot reduction is a masked ``jnp.max`` /
  ``jnp.min`` over the ``(BW, BS)`` one-hot membership matrix (VPU-shaped,
  no serial scatter);
* cross-block the merge is the associative max/min join, so any block
  order is correct.

Sentinels: padded items use ``key = -1`` which matches no slot; empty
slots come back as ``SEG_MAX_INIT`` (-1) for ``op="max"`` and ``NO_WRITER``
(int32 max) for ``op="min"`` — exactly the "no writer in batch" value the
validator wants.  Values are int32 (SSNs are dense counters, positions are
batch indices); the caller falls back to its numpy twin when a batch
exceeds the range, same contract as the recovery kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


SEG_MAX_INIT = np.int32(-1)
NO_WRITER = np.int32(np.iinfo(np.int32).max)

DEFAULT_BLOCK_S = 128
DEFAULT_BLOCK_W = 128


def _kernel(key_ref, val_ref, out_ref, *, block_s: int, is_min: bool):
    sb = pl.program_id(0)
    ib = pl.program_id(1)
    init = NO_WRITER if is_min else SEG_MAX_INIT

    @pl.when(ib == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref[...], init)

    slots = sb * block_s + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
    key = key_ref[...].reshape(-1, 1)          # (BW, 1)
    val = val_ref[...].reshape(-1, 1)

    m = key == slots                           # (BW, BS) one-hot membership
    if is_min:
        blk = jnp.min(jnp.where(m, val, NO_WRITER), axis=0, keepdims=True)
        out_ref[...] = jnp.minimum(out_ref[...], blk)
    else:
        blk = jnp.max(jnp.where(m, val, SEG_MAX_INIT), axis=0, keepdims=True)
        out_ref[...] = jnp.maximum(out_ref[...], blk)


def _pad_to(a: jax.Array, n: int, fill) -> jax.Array:
    if a.shape[0] == n:
        return a
    return jnp.concatenate([a, jnp.full((n - a.shape[0],), fill, a.dtype)])


def validate_sequence_xla(
    acc: jax.Array,     # (6, n_txn*k) int32: row, pos, iswrite, obs, ssn_now, locked
    a_len: jax.Array,   # (n_txn,) int32 true access count per txn (0 = padding)
    n_txn: int,         # txn bucket (rows of the dense layout)
    k: int,             # access bucket (lanes per txn)
    cap: int,           # row-capacity bucket (first-writer scatter width)
):
    """Fused validate→sequence round for the batched OCC executor
    (`repro.db.batch.BatchOCC`, ``mode="pallas"``), compiled on any backend.

    The batch arrives as ONE stacked int32 transfer in a dense bucket-padded
    ``(n_txn, k)`` layout — every transaction's accesses padded to ``k``
    lanes — so the two segmented reductions of the numpy path (per-txn
    survive-AND and base-SSN max) become plain masked reshape-reduces, and
    the only scatter left is the per-row first-writer min.  Lanes beyond a
    transaction's true access count (``a_len``) are masked: they pass
    validation vacuously, contribute ``0`` to the base-SSN max, and scatter
    the min-identity ``NO_WRITER`` so they can never claim a first-writer
    slot.  Returns ``(survive, bases)``, both ``(n_txn,)``; entries past the
    true transaction count are vacuous (``a_len = 0``).
    """
    row, pos, iswrite, obs, ssn_now, locked = (acc[i] for i in range(6))
    lane = jax.lax.broadcasted_iota(jnp.int32, (n_txn, k), 1)
    valid = (lane < a_len.reshape(n_txn, 1)).reshape(-1)

    w_pos = jnp.where((iswrite != 0) & valid, pos, NO_WRITER)
    fw = jnp.full(cap, NO_WRITER, jnp.int32).at[row].min(
        w_pos, mode="promise_in_bounds"
    )[row]
    ok = (fw >= pos) & ((obs < 0) | (ssn_now == obs)) & (locked == 0)
    survive = (ok | ~valid).reshape(n_txn, k).all(axis=1)
    bases = jnp.where(valid, ssn_now, 0).reshape(n_txn, k).max(axis=1)
    return survive, bases


def seg_reduce(
    key_id: jax.Array,   # (W,) int32 slot id per item (>= 0)
    val: jax.Array,      # (W,) int32 value per item
    n_slots: int,
    *,
    op: str = "max",
    block_s: int = DEFAULT_BLOCK_S,
    block_w: int = DEFAULT_BLOCK_W,
    interpret: bool = False,
) -> jax.Array:
    """Segmented ``max``/``min`` of ``val`` grouped by ``key_id`` into
    ``n_slots`` dense slots.  Slots with no member come back as
    ``SEG_MAX_INIT`` (max) / ``NO_WRITER`` (min)."""
    assert op in ("max", "min"), op
    is_min = op == "min"
    init = NO_WRITER if is_min else SEG_MAX_INIT
    w = key_id.shape[0]
    if n_slots == 0:
        return jnp.empty(0, jnp.int32)
    if w == 0:
        return jnp.full(n_slots, init, jnp.int32)
    sp = -(-n_slots // block_s) * block_s
    wp = -(-w // block_w) * block_w

    key = _pad_to(key_id.astype(jnp.int32), wp, -1).reshape(1, wp)
    val_p = _pad_to(val.astype(jnp.int32), wp, init).reshape(1, wp)

    grid = (sp // block_s, wp // block_w)
    slot_spec = pl.BlockSpec((1, block_s), lambda i, j: (0, i))
    item_spec = pl.BlockSpec((1, block_w), lambda i, j: (0, j))

    out = pl.pallas_call(
        functools.partial(_kernel, block_s=block_s, is_min=is_min),
        grid=grid,
        in_specs=[item_spec, item_spec],
        out_specs=slot_spec,
        out_shape=jax.ShapeDtypeStruct((1, sp), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(key, val_p)
    return out[0, :n_slots]
