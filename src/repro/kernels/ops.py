"""jit'd public wrappers for the Pallas kernels.

On TPU the Pallas kernels run compiled (interpret=False); on CPU (this
container) they execute in interpret mode — same kernel body,
Python-evaluated — so correctness is CI-testable without hardware.
``interpret=None`` selects automatically from the default backend; the
probe result is cached once per process and ``REPRO_FORCE_INTERPRET=1``
overrides it so CI can exercise the interpret path deterministically.

The fused entry points below (:func:`fused_replay_scan`,
:func:`fused_replay_apply`, :func:`fused_validate_sequence`) run *compiled*
XLA twins of the kernel bodies (``scatter_max.ssn_scatter_max_xla`` /
``batch_occ.validate_sequence_xla``) on every backend, TPU included: no
caller passes ``use_pallas=True``.  Their callers pad inputs to the
power-of-two bucket ladder (``kernels/bucketing.py``), keeping the jit
cache bounded; :func:`fused_cache_sizes` exposes the per-op compile counts
that the shape-stability tests and ``benchmarks/fig_kernels.py`` assert on.
"""

from __future__ import annotations

import functools
import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .batch_occ import seg_reduce as _seg_reduce_raw
from .batch_occ import validate_sequence_xla as _validate_sequence_xla
from .bucketing import jit_cache_size
from .flash_attention import flash_attention_fwd
from .rwkv6 import rwkv6_chunked
from .scatter_max import ssn_scatter_max as _ssn_scatter_max_raw
from .scatter_max import ssn_scatter_max_xla as _ssn_scatter_max_xla
from .ssm_scan import ssm_scan_chunked


_CHECKOUT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..")
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for a run and return its
    directory.  Called by entry points (``chip_smoke.py``, the benchmark
    harness), never on import.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX.  Otherwise the
    cache lives at the fixed ``<checkout>/.jax_cache``: the directory is part
    of the cache key, so a per-run name would never hit.  The minimum compile
    time drops to 0 because the OLTP kernels compile in well under JAX's 1 s
    default and would otherwise never be cached.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


@functools.lru_cache(maxsize=1)
def _default_interpret() -> bool:
    """One-time backend probe: interpret unless a TPU can compile the Pallas
    lowering.  ``REPRO_FORCE_INTERPRET=1`` pins interpret mode regardless
    (read once, at first kernel use — like the probe itself)."""
    if os.environ.get("REPRO_FORCE_INTERPRET", "") not in ("", "0"):
        return True
    return jax.default_backend() != "tpu"


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return _default_interpret()


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None, block_q: int = 128,
                    block_k: int = 128, interpret: Optional[bool] = None):
    """q (B,Hq,S,D); k/v (B,Hkv,T,D) -> (B,Hq,S,D)."""
    return flash_attention_fwd(
        q, k, v, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=_auto_interpret(interpret),
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_scan(x, dt, decay, bmat, cmat, *, chunk: int = 64,
             interpret: Optional[bool] = None):
    """Chunked selective scan: returns (y, final_state)."""
    return ssm_scan_chunked(
        x, dt, decay, bmat, cmat, chunk=chunk, interpret=_auto_interpret(interpret)
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6(r, k, v, w, u, *, chunk: int = 32, interpret: Optional[bool] = None):
    """Chunked wkv6: returns (y, final_state)."""
    return rwkv6_chunked(r, k, v, w, u, chunk=chunk, interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_s", "block_w", "interpret"))
def ssn_scatter_max(image_ssn, image_pos, key_id, ssn, pos, *,
                    block_s: int = 128, block_w: int = 128,
                    interpret: Optional[bool] = None):
    """SSN-guarded scatter-max batch replay (recovery §5):
    returns (winning ssn per slot, winning write position per slot)."""
    return _ssn_scatter_max_raw(
        image_ssn, image_pos, key_id, ssn, pos,
        block_s=block_s, block_w=block_w, interpret=_auto_interpret(interpret),
    )


@functools.partial(jax.jit, static_argnames=("n_slots", "op", "block_s",
                                             "block_w", "interpret"))
def occ_seg_reduce(key_id, val, *, n_slots: int, op: str = "max",
                   block_s: int = 128, block_w: int = 128,
                   interpret: Optional[bool] = None):
    """Segmented max/min for the batched OCC validator (§4.2/§4.4): per-txn
    base-SSN max (``op="max"`` keyed by txn id) and per-tuple first-writer
    position (``op="min"`` keyed by compacted row id)."""
    return _seg_reduce_raw(
        key_id, val, n_slots, op=op,
        block_s=block_s, block_w=block_w, interpret=_auto_interpret(interpret),
    )


# --- fused OLTP entry points (compiled on every backend) ----------------------

@functools.partial(jax.jit, static_argnames=("n_slots", "use_pallas"))
def fused_replay_scan(scan, *, n_slots: int, use_pallas: bool = False):
    """Fused hash-slot last-writer-wins scan — the device half of the
    compiled replay path (`repro.core.recovery`).

    ``scan`` is one stacked ``(3, N)`` int32 transfer: slot id, SSN, replay
    position per write lane, bucket-padded to ``N`` with the identity lanes
    ``(n_slots, -1, NO_POS)`` (the overflow slot).  Returns the winning
    ``(ssn, pos)`` per slot under the ``(max ssn, then min pos)`` lattice —
    the host resolves slot hash spills exactly afterwards.

    ``use_pallas`` routes through the Pallas one-hot kernel (TPU); the
    default is the XLA scatter twin, which compiles on CPU/GPU.
    """
    slot, ssn, pos = scan[0], scan[1], scan[2]
    image_ssn = jnp.full(n_slots, -1, jnp.int32)
    image_pos = jnp.full(n_slots, jnp.int32(2**31 - 1), jnp.int32)
    if use_pallas:
        return _ssn_scatter_max_raw(
            image_ssn, image_pos, slot, ssn, pos, interpret=False
        )
    return _ssn_scatter_max_xla(image_ssn, image_pos, slot, ssn, pos, n_slots)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def fused_replay_apply(image, scan, *, use_pallas: bool = False):
    """Like :func:`fused_replay_scan` but against a *preloaded* image — the
    compiled guarded apply of ``replay_columnar``/the replica applier, where
    the checkpoint (or the carried table watermark) seeds the per-slot
    ``(ssn, pos)`` state.  ``image`` is one stacked ``(2, S)`` int32 transfer
    (ssn row, pos row — empty slots ``(-1, NO_POS)``); ``scan`` is the
    ``(3, N)`` lane transfer with padding lanes pointing at the overflow
    slot ``S``.  Both dims arrive bucket-padded, so the jit cache is bounded
    by ladder pairs."""
    if use_pallas:
        return _ssn_scatter_max_raw(
            image[0], image[1], scan[0], scan[1], scan[2], interpret=False
        )
    return _ssn_scatter_max_xla(
        image[0], image[1], scan[0], scan[1], scan[2], image.shape[1]
    )


@functools.partial(jax.jit, static_argnames=("n_txn", "k", "cap"))
def fused_validate_sequence(acc, a_len, *, n_txn: int, k: int, cap: int):
    """Fused validate→sequence pass for ``BatchOCC`` rounds: one stacked
    ``(6, n_txn*k)`` int32 transfer in, ``(survive, bases)`` out — see
    ``batch_occ.validate_sequence_xla`` for the layout and masking rules."""
    return _validate_sequence_xla(acc, a_len, n_txn, k, cap)


def fused_cache_sizes() -> Dict[str, int]:
    """Compiled-specialization counts of the fused OLTP entry points — with
    bucket padding these stay ≤ the bucket-ladder size no matter how many
    distinct batch shapes stream through (asserted in
    ``tests/test_bucketing.py``)."""
    return {
        "fused_replay_scan": jit_cache_size(fused_replay_scan),
        "fused_replay_apply": jit_cache_size(fused_replay_apply),
        "fused_validate_sequence": jit_cache_size(fused_validate_sequence),
        "ssn_scatter_max": jit_cache_size(ssn_scatter_max),
        "occ_seg_reduce": jit_cache_size(occ_seg_reduce),
    }
