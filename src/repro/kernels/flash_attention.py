"""Flash attention forward kernel (Pallas / TPU).

TPU adaptation of the FlashAttention insight (online softmax, O(S) memory):
instead of CUDA shared-memory staging, tiling is expressed as BlockSpecs —
each grid step pipelines one (block_q x d) query tile and one (block_k x d)
KV tile HBM→VMEM; softmax statistics (m, l) and the output accumulator live
in VMEM scratch across the sequential kv grid dimension.  Block shapes are
MXU-aligned (multiples of 128 on the contraction/lane dims).

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks) with the kv dimension
innermost & sequential ("arbitrary"), accumulating into scratch; the output
tile is written on the last kv step.  GQA is handled in the k/v index_maps
(kv_head = q_head * n_kv // n_q).  Causal/sliding-window masking is applied
in-kernel; fully-masked kv blocks are skipped with ``pl.when`` (the compute
saving the `triangular` jnp path gets by construction).

Numerics: fp32 accumulation regardless of input dtype (MXU native).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: Optional[int],
            block_q: int, block_k: int, num_kv_blocks: int,
            softcap: Optional[float]):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # block-level skip: entire kv block after the causal frontier, or entirely
    # before the sliding window
    run = jnp.asarray(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window is not None:
        run = jnp.logical_and(run, k_start + block_k - 1 >= q_start - window + 1)

    @pl.when(run)
    def _body():
        q = q_ref[...].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[...].astype(jnp.float32)                  # (bk, d)
        v = v_ref[...].astype(jnp.float32)                  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                                   # (bq, bk)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        if window is not None:
            mask = jnp.logical_and(mask, q_pos - k_pos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                 # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                              # (bq, bk)
        l_new = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finish():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,      # (B, Hq, S, D)
    k: jax.Array,      # (B, Hkv, T, D)
    v: jax.Array,      # (B, Hkv, T, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    b, hq, s, d = q.shape
    _, hkv, t, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    nq, nk = s // block_q, t // block_k
    scale = 1.0 / math.sqrt(d)

    grid = (b, hq, nq, nk)

    q_spec = pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, block_k, d), lambda bi, hi, qi, ki: (bi, hi * hkv // hq, ki, 0)
    )
    o_spec = pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0))

    kernel = functools.partial(
        _kernel,
        scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_kv_blocks=nk, softcap=softcap,
    )

    # wrap refs to drop the leading singleton block dims inside the kernel
    def body(q_ref, k_ref, v_ref, o_ref, m, l, acc):
        kernel(q_ref.at[0, 0], k_ref.at[0, 0], v_ref.at[0, 0], o_ref.at[0, 0], m, l, acc)

    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # m: running max
            pltpu.VMEM((block_q, 1), jnp.float32),    # l: running denominator
            pltpu.VMEM((block_q, d), jnp.float32),    # acc: output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
