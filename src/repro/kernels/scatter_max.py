"""SSN-guarded scatter-max kernel (Pallas / TPU) — batched log replay.

Recovery's inner loop (paper §5) is, per log write ``(key, value, ssn)``::

    if ssn > image[key].ssn: image[key] = (value, ssn)

i.e. a scatter-max over SSNs with the *argmax payload* (which write won)
carried along — the Thomas write rule that makes Poplar's replay order-free.
This kernel applies a whole batch of writes against the recovered image in
one pass:

* slots are the dense key ids of the recovered image (built host-side from
  the checkpoint ∪ log key vocabulary);
* the grid is ``(slot_blocks, write_blocks)`` — slot blocks are independent
  ("parallel"); write blocks accumulate sequentially ("arbitrary") into the
  output, flash-attention style, so the image stays resident in VMEM while
  the write stream is blocked through;
* within a write block the winner per slot is found with a one-hot
  compare-and-reduce (VPU-shaped, no serial scatter): ``blk_ssn`` is the
  block's max SSN per slot and ``blk_pos`` the *earliest* log position among
  that max — ties between equal SSNs resolve to the first write in replay
  order, matching the scalar oracle's strict ``>`` guard;
* cross-block (and vs. the checkpoint image) the merge is the associative
  ``(max ssn, then min pos)`` lattice join, so any block order is correct.

Sentinels: a slot with no value has ``ssn = -1`` and ``pos = NO_POS``; a
checkpoint-provided slot has ``pos = -1`` (smaller than every log position,
so the checkpoint wins SSN ties exactly like the scalar guard). Padded
writes use ``key = -1`` which matches no slot.

``ssn`` / ``pos`` are int32: the engine's SSNs are dense counters (one per
logged record), so 2^31 records per recovery batch is far beyond any log
this replays; the caller (``recovery.replay_columnar``) checks the range and
falls back to its equivalent numpy reduction when a batch exceeds it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NO_POS = np.int32(np.iinfo(np.int32).max)

DEFAULT_BLOCK_S = 128
DEFAULT_BLOCK_W = 128


def _kernel(img_ssn_ref, img_pos_ref, key_ref, ssn_ref, pos_ref,
            out_ssn_ref, out_pos_ref, *, block_s: int):
    sb = pl.program_id(0)
    wb = pl.program_id(1)

    @pl.when(wb == 0)
    def _init():
        out_ssn_ref[...] = img_ssn_ref[...]
        out_pos_ref[...] = img_pos_ref[...]

    slots = sb * block_s + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
    key = key_ref[...].reshape(-1, 1)          # (BW, 1)
    ssn = ssn_ref[...].reshape(-1, 1)
    pos = pos_ref[...].reshape(-1, 1)

    m = key == slots                           # (BW, BS) one-hot membership
    blk_ssn = jnp.max(jnp.where(m, ssn, -1), axis=0, keepdims=True)   # (1, BS)
    blk_pos = jnp.min(
        jnp.where(m & (ssn == blk_ssn), pos, NO_POS), axis=0, keepdims=True
    )

    run_ssn = out_ssn_ref[...]
    run_pos = out_pos_ref[...]
    better = blk_ssn > run_ssn
    tie = blk_ssn == run_ssn
    out_ssn_ref[...] = jnp.where(better, blk_ssn, run_ssn)
    out_pos_ref[...] = jnp.where(
        better, blk_pos, jnp.where(tie, jnp.minimum(run_pos, blk_pos), run_pos)
    )


def _pad_to(a: jax.Array, n: int, fill) -> jax.Array:
    if a.shape[0] == n:
        return a
    return jnp.concatenate([a, jnp.full((n - a.shape[0],), fill, a.dtype)])


def ssn_scatter_max_xla(
    image_ssn: jax.Array,   # (S,) int32, -1 = empty slot
    image_pos: jax.Array,   # (S,) int32, -1 = checkpoint value, NO_POS = empty
    key_id: jax.Array,      # (W,) int32 slot id per write; id == S is ignored
    ssn: jax.Array,         # (W,) int32 SSN per write (-1 for padded lanes)
    pos: jax.Array,         # (W,) int32 replay position (NO_POS for padding)
    n_slots: int,
):
    """Compiled twin of :func:`ssn_scatter_max` for backends without a
    Pallas lowering (CPU/GPU): the same ``(max ssn, then min pos)`` merge
    lattice expressed as two native XLA scatters instead of the one-hot
    grid, so ``mode="pallas"`` compiles everywhere.

    Scatters accept ids in ``[0, n_slots]`` — the extra slot ``n_slots`` is
    the overflow lane bucket padding routes to (its result is dropped), so
    padded lanes need no branch.  Padded ``ssn = -1`` loses every max
    against real SSNs (≥ 0) and the image init, and padded ``pos = NO_POS``
    loses every min, so padding cannot win a slot (property-tested in
    ``tests/test_bucketing.py``).
    """
    ext_ssn = jnp.concatenate([image_ssn, jnp.full((1,), -1, jnp.int32)])
    ext_pos = jnp.concatenate([image_pos, jnp.full((1,), NO_POS, jnp.int32)])
    out_ssn = ext_ssn.at[key_id].max(ssn, mode="promise_in_bounds")
    cand = ssn == out_ssn[key_id]
    cpos = jnp.where(cand, pos, NO_POS)
    keep = image_ssn == out_ssn[:n_slots]       # image still (co-)maximal?
    base = jnp.concatenate(
        [jnp.where(keep, image_pos, NO_POS), jnp.full((1,), NO_POS, jnp.int32)]
    )
    out_pos = base.at[key_id].min(cpos, mode="promise_in_bounds")
    return out_ssn[:n_slots], out_pos[:n_slots]


def ssn_scatter_max(
    image_ssn: jax.Array,   # (S,) int32, -1 = empty slot
    image_pos: jax.Array,   # (S,) int32, -1 = checkpoint value, NO_POS = empty
    key_id: jax.Array,      # (W,) int32 dense key id per write
    ssn: jax.Array,         # (W,) int32 SSN per write (>= 0)
    pos: jax.Array,         # (W,) int32 replay position per write (>= 0)
    *,
    block_s: int = DEFAULT_BLOCK_S,
    block_w: int = DEFAULT_BLOCK_W,
    interpret: bool = False,
):
    """Apply a batch of SSN-guarded writes; returns ``(new_ssn, new_pos)``,
    both (S,): the winning SSN per slot and the position of the winning
    write (-1 if the checkpoint value stands, NO_POS if the slot is empty).
    """
    s = image_ssn.shape[0]
    w = key_id.shape[0]
    if s == 0 or w == 0:
        return image_ssn, image_pos
    sp = -(-s // block_s) * block_s
    wp = -(-w // block_w) * block_w

    img_ssn = _pad_to(image_ssn.astype(jnp.int32), sp, -1).reshape(1, sp)
    img_pos = _pad_to(image_pos.astype(jnp.int32), sp, NO_POS).reshape(1, sp)
    key = _pad_to(key_id.astype(jnp.int32), wp, -1).reshape(1, wp)
    ssn_p = _pad_to(ssn.astype(jnp.int32), wp, -1).reshape(1, wp)
    pos_p = _pad_to(pos.astype(jnp.int32), wp, NO_POS).reshape(1, wp)

    grid = (sp // block_s, wp // block_w)
    slot_spec = pl.BlockSpec((1, block_s), lambda i, j: (0, i))
    write_spec = pl.BlockSpec((1, block_w), lambda i, j: (0, j))

    out_ssn, out_pos = pl.pallas_call(
        functools.partial(_kernel, block_s=block_s),
        grid=grid,
        in_specs=[slot_spec, slot_spec, write_spec, write_spec, write_spec],
        out_specs=[slot_spec, slot_spec],
        out_shape=[
            jax.ShapeDtypeStruct((1, sp), jnp.int32),
            jax.ShapeDtypeStruct((1, sp), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(img_ssn, img_pos, key, ssn_p, pos_p)
    return out_ssn[0, :s], out_pos[0, :s]
