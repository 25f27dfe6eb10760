"""Mamba2 SSD — Pallas TPU kernel, chunked scan.

Grid: (B, H, S/chunk); the chunk axis is sequential ("arbitrary") and the
running inter-chunk state [P, N] lives in VMEM scratch across chunk steps —
the TPU version of the paper's per-block claim-then-run loop, with the
sequential state handoff playing the synchronization-cost role.  The chunk
length is the ParallelFor block size (repro.core.autotune.ssd_chunk_size):
larger chunks mean fewer scan handoffs but more quadratic-in-chunk work.

VMEM per step: x[q,P] + B/C[q,N] + decay [q,q] f32 + state [P,N] f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import autotune


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref,
                y_ref, state_out_ref, state_ref, *, q: int, nc: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)            # [q, P]
    dt = dt_ref[0, 0].astype(jnp.float32)          # [q, 1]
    a = a_ref[0, 0]                                # scalar f32
    b = b_ref[0, 0].astype(jnp.float32)            # [q, N]
    c = c_ref[0, 0].astype(jnp.float32)            # [q, N]

    da = dt * a                                    # [q, 1]
    cum = jnp.cumsum(da, axis=0)                   # [q, 1]

    # intra-chunk: scores[i,j] = (C_i.B_j) * exp(cum_i - cum_j) for i >= j
    diff = cum - cum.reshape(1, q)                 # [q, q]
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    l_mat = jnp.where(ii >= jj, jnp.exp(diff), 0.0)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [q, q]
    y = jax.lax.dot_general(cb * l_mat, x * dt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [q, P]

    # inter-chunk: y += (C * exp(cum)) @ state^T   (state [P, N])
    state = state_ref[...]
    y = y + jax.lax.dot_general(c * jnp.exp(cum), state,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)

    # state update: state' = state * exp(cum[-1]) + x^T @ (B * decay * dt)
    decay_states = jnp.exp(cum[q - 1] - cum)       # [q, 1]
    contrib = jax.lax.dot_general(x, b * (decay_states * dt),
                                  (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # [P,N]
    state_ref[...] = state * jnp.exp(cum[q - 1]) + contrib

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(c_idx == nc - 1)
    def _emit_state():
        state_out_ref[0, 0] = state_ref[...]


def ssd_fwd(
    x: jax.Array,      # [B, S, H, P]
    dt: jax.Array,     # [B, S, H]   (post-softplus)
    a: jax.Array,      # [H]         (negative)
    b_in: jax.Array,   # [B, S, G, N]
    c_in: jax.Array,   # [B, S, G, N]
    *,
    chunk: int,
    interpret: bool = False,
):
    """Returns (y [B,S,H,P], final_state [B,H,P,N])."""
    bsz, s, h, p = x.shape
    g, n = b_in.shape[2], b_in.shape[3]
    # largest divisor of S <= the tuned chunk (halving collapsed to tiny
    # chunks on non-power-of-two sequence lengths)
    q = autotune.fit_block(s, chunk)
    nc = s // q

    xt = x.transpose(0, 2, 1, 3)                       # [B, H, S, P]
    dtt = dt.transpose(0, 2, 1)[..., None]             # [B, H, S, 1]
    at = jnp.asarray(a, jnp.float32).reshape(h, 1)     # [H, 1]
    # group -> head broadcast handled by the index map (h // (H/G))
    bt = b_in.transpose(0, 2, 1, 3)                    # [B, G, S, N]
    ct = c_in.transpose(0, 2, 1, 3)
    rep = h // g

    kernel = functools.partial(_ssd_kernel, q=q, nc=nc)
    y, final_state = pl.pallas_call(
        kernel,
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, q, p), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1), lambda b_, h_, c_: (h_, 0)),
            pl.BlockSpec((1, 1, q, n),
                         lambda b_, h_, c_: (b_, h_ // rep, c_, 0)),
            pl.BlockSpec((1, 1, q, n),
                         lambda b_, h_, c_: (b_, h_ // rep, c_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, p), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b_, h_, c_: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="mamba_ssd_fwd",
    )(xt, dtt, at, bt, ct)
    return y.transpose(0, 2, 1, 3), final_state


# ---------------------------------------------------------------------------
# Quantized variant: the activation stream x arrives int8/fp8 with one
# scale per (token, head) vector over P.  x is dequantized at load — it
# feeds two contractions (intra-chunk y and the state update) under
# different per-row weightings, so unlike attention there is no single
# post-matmul point to fold the scale into; the DMA win (x is the widest
# stream at P >= N) is what quantization buys here.
# ---------------------------------------------------------------------------


def _ssd_quant_kernel(x_ref, xs_ref, dt_ref, a_ref, b_ref, c_ref,
                      y_ref, state_out_ref, state_ref, *, q: int, nc: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    xs = xs_ref[0, 0].astype(jnp.float32)          # [q, 1]
    x = x_ref[0, 0].astype(jnp.float32) * xs       # [q, P] dequantized
    dt = dt_ref[0, 0].astype(jnp.float32)          # [q, 1]
    a = a_ref[0, 0]                                # scalar f32
    b = b_ref[0, 0].astype(jnp.float32)            # [q, N]
    c = c_ref[0, 0].astype(jnp.float32)            # [q, N]

    da = dt * a                                    # [q, 1]
    cum = jnp.cumsum(da, axis=0)                   # [q, 1]

    diff = cum - cum.reshape(1, q)                 # [q, q]
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    l_mat = jnp.where(ii >= jj, jnp.exp(diff), 0.0)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [q, q]
    y = jax.lax.dot_general(cb * l_mat, x * dt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [q, P]

    state = state_ref[...]
    y = y + jax.lax.dot_general(c * jnp.exp(cum), state,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)

    decay_states = jnp.exp(cum[q - 1] - cum)       # [q, 1]
    contrib = jax.lax.dot_general(x, b * (decay_states * dt),
                                  (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # [P,N]
    state_ref[...] = state * jnp.exp(cum[q - 1]) + contrib

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(c_idx == nc - 1)
    def _emit_state():
        state_out_ref[0, 0] = state_ref[...]


def ssd_fwd_quantized(
    x_q: jax.Array,      # [B, S, H, P] int8/fp8
    x_scale: jax.Array,  # [B, S, H, 1]
    dt: jax.Array,       # [B, S, H]   (post-softplus)
    a: jax.Array,        # [H]         (negative)
    b_in: jax.Array,     # [B, S, G, N]
    c_in: jax.Array,     # [B, S, G, N]
    *,
    chunk: int,
    interpret: bool = False,
):
    """Returns (y [B,S,H,P] in b_in's dtype, final_state [B,H,P,N])."""
    bsz, s, h, p = x_q.shape
    g, n = b_in.shape[2], b_in.shape[3]
    q = autotune.fit_block(s, chunk)
    nc = s // q

    xt = x_q.transpose(0, 2, 1, 3)                     # [B, H, S, P]
    xst = x_scale.transpose(0, 2, 1, 3)                # [B, H, S, 1]
    dtt = dt.transpose(0, 2, 1)[..., None]             # [B, H, S, 1]
    at = jnp.asarray(a, jnp.float32).reshape(h, 1)     # [H, 1]
    bt = b_in.transpose(0, 2, 1, 3)                    # [B, G, S, N]
    ct = c_in.transpose(0, 2, 1, 3)
    rep = h // g

    kernel = functools.partial(_ssd_quant_kernel, q=q, nc=nc)
    y, final_state = pl.pallas_call(
        kernel,
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, q, p), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1), lambda b_, h_, c_: (h_, 0)),
            pl.BlockSpec((1, 1, q, n),
                         lambda b_, h_, c_: (b_, h_ // rep, c_, 0)),
            pl.BlockSpec((1, 1, q, n),
                         lambda b_, h_, c_: (b_, h_ // rep, c_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, p), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b_, h_, c_: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, s, p), b_in.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="mamba_ssd_fwd_quantized",
    )(xt, xst, dtt, at, bt, ct)
    return y.transpose(0, 2, 1, 3), final_state
