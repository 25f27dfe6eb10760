"""Grouped expert matmul — Pallas TPU kernel.

The MoE hot loop after dispatch: for each expert e, multiply its capacity
buffer x[e] [C, d] by its weights w[e] [d, f].  Grid
(E, C/bc, f/bf, d/bd) with the contraction axis sequential and an f32 VMEM
accumulator — a textbook MXU-tiled matmul batched over experts.  The tile
sizes are ParallelFor block sizes: bc too small wastes grid dispatches (the
per-claim L), too large overflows VMEM; defaults come from the cost model's
candidate ranking in ops.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import autotune


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, nd: int):
    kd = pl.program_id(3)

    @pl.when(kd == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0].astype(jnp.float32)      # [bc, bd]
    w = w_ref[0].astype(jnp.float32)      # [bd, bf]
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kd == nd - 1)
    def _finalize():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def gmm(
    x: jax.Array,      # [E, C, d]
    w: jax.Array,      # [E, d, f]
    *,
    block_c: int = 128,
    block_f: int = 128,
    block_d: int = 128,
    interpret: bool = False,
) -> jax.Array:
    e, c, d = x.shape
    f = w.shape[2]
    # largest divisors <= the tuned tiles (halving collapsed to degenerate
    # 1-wide tiles on non-power-of-two extents)
    bc = autotune.fit_block(c, block_c)
    bf = autotune.fit_block(f, block_f)
    bd = autotune.fit_block(d, block_d)
    nc, nf, nd = c // bc, f // bf, d // bd

    return pl.pallas_call(
        functools.partial(_gmm_kernel, nd=nd),
        grid=(e, nc, nf, nd),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e_, i, j, kd: (e_, i, kd)),
            pl.BlockSpec((1, bd, bf), lambda e_, i, j, kd: (e_, kd, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e_, i, j, kd: (e_, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="moe_gmm",
    )(x, w)


# ---------------------------------------------------------------------------
# Quantized variant: int8/fp8 expert weights with one scale per (expert,
# output column).  The scale is constant along the contraction axis d, so
# applying it once to the finished accumulator is exact — the hot loop
# stays a pure quantized matmul and the dequant costs one [bc, bf]
# multiply per output tile.
# ---------------------------------------------------------------------------


def _gmm_quant_kernel(x_ref, w_ref, ws_ref, o_ref, acc_ref, *, nd: int):
    kd = pl.program_id(3)

    @pl.when(kd == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0].astype(jnp.float32)      # [bc, bd]
    w = w_ref[0].astype(jnp.float32)      # [bd, bf] quantized
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kd == nd - 1)
    def _finalize():
        ws = ws_ref[0].astype(jnp.float32)    # [1, bf]
        o_ref[0] = (acc_ref[...] * ws).astype(o_ref.dtype)


def gmm_quantized(
    x: jax.Array,        # [E, C, d]
    w_q: jax.Array,      # [E, d, f] int8/fp8
    w_scale: jax.Array,  # [E, 1, f]
    *,
    block_c: int = 128,
    block_f: int = 128,
    block_d: int = 128,
    interpret: bool = False,
) -> jax.Array:
    e, c, d = x.shape
    f = w_q.shape[2]
    bc = autotune.fit_block(c, block_c)
    bf = autotune.fit_block(f, block_f)
    bd = autotune.fit_block(d, block_d)
    nc, nf, nd = c // bc, f // bf, d // bd

    return pl.pallas_call(
        functools.partial(_gmm_quant_kernel, nd=nd),
        grid=(e, nc, nf, nd),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e_, i, j, kd: (e_, i, kd)),
            pl.BlockSpec((1, bd, bf), lambda e_, i, j, kd: (e_, kd, j)),
            pl.BlockSpec((1, 1, bf), lambda e_, i, j, kd: (e_, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e_, i, j, kd: (e_, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="moe_gmm_quantized",
    )(x, w_q, w_scale)
