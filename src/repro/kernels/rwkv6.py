"""Chunked wkv6 kernel (Pallas / TPU) — RWKV6 "Finch" recurrence.

    y_t = r_t (S_{t-1} + u ⊙ k_t^T v_t);   S_t = diag(w_t) S_{t-1} + k_t^T v_t

TPU adaptation: per-chunk block form with exact in-chunk decay tensors.
With Λ = cumsum(log w) (≤ 0, per k-channel) and Λ̄_t = Λ_t - log w_t
(exclusive cumsum):

    y_state[t]  = (r_t ⊙ exp(Λ̄_t)) · S_prev
    A[t,s]      = Σ_k r_tk k_sk exp(Λ̄_tk - Λ_sk)   (s < t)
    A[t,t]      = Σ_k r_tk u_k k_tk
    y[t]        = y_state[t] + Σ_s A[t,s] v_s
    S_new       = diag(exp(Λ_last)) S_prev + Σ_s (k_s ⊙ exp(Λ_last - Λ_s))^T v_s

All decay exponents are differences of log-cumsums with the *later* index
minus the earlier ⇒ every exponent ≤ 0 ⇒ numerically stable at any chunk
size (no exp overflow — unlike the factored r·exp(Λ) @ (k·exp(-Λ))^T form).
The (C, C, K) in-chunk decay tensor lives in VMEM (chunk 32, K 64 ⇒ 256 KB).

Grid: (B·H, num_chunks), chunk dim sequential with (K, V) state in VMEM.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_CHUNK = 32


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_out_ref, state_scr,
            *, chunk: int, num_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    r = r_ref[...].astype(jnp.float32)     # (C, K)
    k = k_ref[...].astype(jnp.float32)     # (C, K)
    v = v_ref[...].astype(jnp.float32)     # (C, V)
    w = w_ref[...].astype(jnp.float32)     # (C, K) in (0, 1)
    u = u_ref[...].astype(jnp.float32)     # (1, K)

    lw = jnp.cumsum(jnp.log(jnp.maximum(w, 1e-30)), axis=0)       # (C, K)
    lw_excl = lw - jnp.log(jnp.maximum(w, 1e-30))                 # (C, K)

    s_prev = state_scr[...]                                        # (K, V)

    # state contribution
    rd = r * jnp.exp(lw_excl)                                      # (C, K)
    y_state = jax.lax.dot_general(rd, s_prev, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # (C, V)

    # in-chunk attention matrix A (C, C): strict lower triangle + u diagonal
    rel = lw_excl[:, None, :] - lw[None, :, :]                     # (C, C, K)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = (t_idx > s_idx)[:, :, None]
    decay = jnp.where(tri, jnp.exp(rel), 0.0)                      # (C, C, K)
    a_lower = jnp.sum(r[:, None, :] * k[None, :, :] * decay, axis=2)
    a_diag = jnp.sum(r * u * k, axis=1)                            # (C,)
    a = a_lower + jnp.where(t_idx == s_idx, a_diag[:, None], 0.0)
    y_intra = jax.lax.dot_general(a, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    y_ref[...] = (y_state + y_intra).astype(y_ref.dtype)

    # state update
    lw_last = lw[chunk - 1:chunk, :]                               # (1, K)
    k_scaled = k * jnp.exp(lw_last - lw)                           # (C, K)
    s_new = jnp.exp(lw_last).reshape(-1, 1) * s_prev + jax.lax.dot_general(
        k_scaled, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    state_scr[...] = s_new

    @pl.when(ci == num_chunks - 1)
    def _finish():
        s_out_ref[...] = s_new.astype(s_out_ref.dtype)


def rwkv6_chunked(
    r: jax.Array,      # (B, H, S, K)
    k: jax.Array,      # (B, H, S, K)
    v: jax.Array,      # (B, H, S, V)
    w: jax.Array,      # (B, H, S, K)
    u: jax.Array,      # (H, K)
    *,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,H,S,V), final state (B,H,K,V))."""
    b, h, s, kd = r.shape
    vd = v.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    bh = b * h

    rf = r.reshape(bh, s, kd)
    kf = k.reshape(bh, s, kd)
    vf = v.reshape(bh, s, vd)
    wf = w.reshape(bh, s, kd)

    grid = (bh, nc)
    rk_spec = pl.BlockSpec((1, chunk, kd), lambda i, c: (i, c, 0))
    v_spec = pl.BlockSpec((1, chunk, vd), lambda i, c: (i, c, 0))
    u_spec = pl.BlockSpec((1, kd), lambda i, c: (i % h, 0))
    y_spec = pl.BlockSpec((1, chunk, vd), lambda i, c: (i, c, 0))
    st_spec = pl.BlockSpec((1, kd, vd), lambda i, c: (i, 0, 0))

    kernel = functools.partial(_kernel, chunk=chunk, num_chunks=nc)

    def body(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_out_ref, state_scr):
        kernel(r_ref.at[0], k_ref.at[0], v_ref.at[0], w_ref.at[0], u_ref,
               y_ref.at[0], s_out_ref.at[0], state_scr)

    y, s_fin = pl.pallas_call(
        body,
        grid=grid,
        in_specs=[rk_spec, rk_spec, v_spec, rk_spec, u_spec],
        out_specs=[y_spec, st_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, vd), v.dtype),
            jax.ShapeDtypeStruct((bh, kd, vd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((kd, vd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(rf, kf, vf, wf, u)
    return y.reshape(b, h, s, vd), s_fin.reshape(b, h, kd, vd)
