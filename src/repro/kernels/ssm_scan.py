"""Chunked selective-scan kernel (Pallas / TPU) — mamba-style SSM.

TPU adaptation: the recurrence  h_t = a_t h_{t-1} + dt_t·(x_t ⊗ B_t),
y_t = C_t·h_t  is reorganized into the SSD block form so each chunk becomes
MXU matmuls instead of a length-S serial scan:

  within a chunk (all decays a ∈ (0,1), log-cumsums stay ≤ 0 ⇒ stable):
    y_state[t] = exp(Λ_t) · (C_t · S_prev)            Λ = cumsum(log a)
    y_intra[t] = Σ_{s≤t} exp(Λ_t - Λ_s) (C_t·B_s) u_s     u = dt ⊙ x
    S_new      = exp(Λ_last) S_prev + Σ_s exp(Λ_last - Λ_s) u_s ⊗ B_s

Grid: (B·H, num_chunks); the chunk dimension is sequential ("arbitrary")
with the (P, N) state in VMEM scratch.  This removes the O(S) HBM
round-trips of the naive per-step scan (the hymba/rwkv baseline pathology
in EXPERIMENTS §Perf).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_CHUNK = 64


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, s_out_ref, state_scr,
            *, chunk: int, num_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[...].astype(jnp.float32)        # (C, P)
    dt = dt_ref[...].astype(jnp.float32)      # (C, 1)
    a = a_ref[...].astype(jnp.float32)        # (C, 1)
    bm = b_ref[...].astype(jnp.float32)       # (C, N)
    cm = c_ref[...].astype(jnp.float32)       # (C, N)

    la = jnp.cumsum(jnp.log(jnp.maximum(a, 1e-30)), axis=0)   # (C, 1), <= 0
    u = dt * x                                                  # (C, P)

    s_prev = state_scr[...]                                     # (P, N)

    # state contribution: exp(la_t) * (C_t . S_prev)
    cs = jax.lax.dot_general(cm, s_prev, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (C, P)
    y_state = jnp.exp(la) * cs

    # intra-chunk: M[t,s] = exp(la_t - la_s) (C_t . B_s), lower-triangular
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (C, C)
    rel = la - la.reshape(1, chunk)                               # (C, C) via broadcast
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    m = jnp.where(t_idx >= s_idx, jnp.exp(rel) * cb, 0.0)
    y_intra = jax.lax.dot_general(m, u, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # (C, P)

    y_ref[...] = (y_state + y_intra).astype(y_ref.dtype)

    # state update: S_new = exp(la_last) S_prev + sum_s exp(la_last - la_s) u_s ⊗ B_s
    la_last = la[chunk - 1:chunk, :]                              # (1, 1)
    scaled_u = u * jnp.exp(la_last - la)                          # (C, P)
    s_new = jnp.exp(la_last) * s_prev + jax.lax.dot_general(
        scaled_u, bm, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                                             # (P, N)
    state_scr[...] = s_new

    @pl.when(ci == num_chunks - 1)
    def _finish():
        s_out_ref[...] = s_new.astype(s_out_ref.dtype)


def ssm_scan_chunked(
    x: jax.Array,       # (B, H, S, P)
    dt: jax.Array,      # (B, H, S)
    decay: jax.Array,   # (B, H, S)   a_t = exp(-exp(A) dt_t) in (0,1)
    bmat: jax.Array,    # (B, S, N)
    cmat: jax.Array,    # (B, S, N)
    *,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,H,S,P), final state (B,H,P,N))."""
    b, h, s, p = x.shape
    n = bmat.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    bh = b * h

    xf = x.reshape(bh, s, p)
    dtf = dt.reshape(bh, s, 1)
    af = decay.reshape(bh, s, 1)

    grid = (bh, nc)
    x_spec = pl.BlockSpec((1, chunk, p), lambda i, c: (i, c, 0))
    s1_spec = pl.BlockSpec((1, chunk, 1), lambda i, c: (i, c, 0))
    bc_spec = pl.BlockSpec((1, chunk, n), lambda i, c: (i // h, c, 0))
    y_spec = pl.BlockSpec((1, chunk, p), lambda i, c: (i, c, 0))
    st_spec = pl.BlockSpec((1, p, n), lambda i, c: (i, 0, 0))

    kernel = functools.partial(_kernel, chunk=chunk, num_chunks=nc)

    def body(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, s_out_ref, state_scr):
        kernel(x_ref.at[0], dt_ref.at[0], a_ref.at[0], b_ref.at[0], c_ref.at[0],
               y_ref.at[0], s_out_ref.at[0], state_scr)

    y, s_fin = pl.pallas_call(
        body,
        grid=grid,
        in_specs=[x_spec, s1_spec, s1_spec, bc_spec, bc_spec],
        out_specs=[y_spec, st_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, p), x.dtype),
            jax.ShapeDtypeStruct((bh, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xf, dtf, af, bmat, cmat)
    return y.reshape(b, h, s, p), s_fin.reshape(b, h, p, n)
