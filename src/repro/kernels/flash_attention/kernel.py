"""Flash attention forward — Pallas TPU kernel.

Grid: (B, Hq, Sq/block_q, Skv/block_k); the last axis is sequential
("arbitrary") so the running-softmax state lives in VMEM scratch across KV
steps.  block_q/block_k are the paper's ParallelFor block size, selected by
repro.core.autotune.attention_block_sizes (MXU-aligned, VMEM-budgeted).

VMEM working set per grid step:
    q[bq,d] + k[bk,d] + v[bk,d] (input dtype) + acc[bq,d] + m/l[bq] (f32)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
               causal: bool, sq: int, skv: int, bq: int, bk: int,
               nk: int):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal block skipping: a KV block strictly above the diagonal band
    # contributes nothing — skip its MXU work entirely (the ParallelFor
    # analogue of not claiming iterations that are known to be empty).
    run = (j * bk <= i * bq + bq - 1 + (skv - sq)) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)           # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)           # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (1.0 / np.sqrt(q.shape[-1]))          # [bq, bk]

        if causal:
            qpos = i * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0) + (skv - sq)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)

        m_prev = m_ref[...]                            # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                         # [bq, bk]
        corr = jnp.exp(m_prev - m_new)                 # [bq, 1]
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(l)).astype(jnp.float32)


def flash_attention_fwd(
    q: jax.Array,      # [B, Sq, Hq, D]
    k: jax.Array,      # [B, Skv, Hkv, D]
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int,
    block_k: int,
    interpret: bool = False,
) -> jax.Array:
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    bq, bk = min(block_q, sq), min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    nq, nk = sq // bq, skv // bk

    # layout: [B, H, S, D] so the blocked dims are the MXU-friendly tail
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _fa_kernel, causal=causal, sq=sq, skv=skv, bq=bq, bk=bk, nk=nk)

    out, lse = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, i, j: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, i, j: (b_, h // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h, i, j: (b_, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_fwd",
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse[..., 0]


# ---------------------------------------------------------------------------
# Multi-buffered forward: explicit DMA/compute pipelining.
#
# The classic kernel above leans on Pallas's implicit pipeline: one KV block
# per grid step, the compiler double-buffers the BlockSpec copies.  This
# variant owns the KV stream instead: K/V stay in HBM (memory_space=ANY) and
# the kernel DMAs block j+depth-1 into a VMEM ring of ``num_buffers`` slots
# while the MXU works on block j — the per-KV-block grid dispatch (the
# paper's per-claim FAA analogue) collapses into a semaphore wait, and the
# exposed DMA latency shrinks with depth.  The per-block f32 math is copied
# from ``_fa_kernel`` verbatim, so the outputs are bit-identical.
# ---------------------------------------------------------------------------


def _fa_pipelined_kernel(q_ref, k_hbm, v_hbm, o_ref, lse_ref,
                         acc_ref, m_ref, l_ref, k_buf, v_buf, sem, *,
                         causal: bool, sq: int, skv: int, bq: int, bk: int,
                         nk: int, num_buffers: int, g: int):
    b_ = pl.program_id(0)
    h = pl.program_id(1)
    i = pl.program_id(2)
    hkv = h // g
    nb = num_buffers

    # causal trip count: the last KV block intersecting the diagonal band.
    # Same predicate as the classic kernel's ``run`` — blocks with
    # j*bk <= i*bq + bq - 1 + (skv - sq) form a contiguous prefix.
    if causal:
        bound = i * bq + bq - 1 + (skv - sq)
        nk_run = jnp.clip(jnp.floor_divide(bound, bk) + 1, 0, nk)
    else:
        nk_run = nk

    def kv_copy(blk, slot):
        start = blk * bk
        return (
            pltpu.make_async_copy(
                k_hbm.at[b_, hkv, pl.ds(start, bk), :],
                k_buf.at[slot], sem.at[0, slot]),
            pltpu.make_async_copy(
                v_hbm.at[b_, hkv, pl.ds(start, bk), :],
                v_buf.at[slot], sem.at[1, slot]),
        )

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    # prologue: blocks 0..nb-2 in flight before any compute
    for slot in range(nb - 1):
        @pl.when(slot < nk_run)
        def _start(slot=slot):
            ck, cv = kv_copy(slot, slot)
            ck.start()
            cv.start()

    q = q_ref[0, 0].astype(jnp.float32)               # [bq, d]

    def body(j, carry):
        nxt = j + nb - 1

        @pl.when(nxt < nk_run)
        def _prefetch():
            ck, cv = kv_copy(nxt, jax.lax.rem(nxt, nb))
            ck.start()
            cv.start()

        slot = jax.lax.rem(j, nb)
        ck, cv = kv_copy(j, slot)
        ck.wait()
        cv.wait()
        k = k_buf[slot].astype(jnp.float32)           # [bk, d]
        v = v_buf[slot].astype(jnp.float32)           # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (1.0 / np.sqrt(q.shape[-1]))          # [bq, bk]

        if causal:
            qpos = i * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0) + (skv - sq)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)

        m_prev = m_ref[...]                            # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                         # [bq, bk]
        corr = jnp.exp(m_prev - m_new)                 # [bq, 1]
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, nk_run, body, 0)

    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
    lse_ref[0, 0] = (m_ref[...] + jnp.log(l)).astype(jnp.float32)


def flash_attention_fwd_pipelined(
    q: jax.Array,      # [B, Sq, Hq, D]
    k: jax.Array,      # [B, Skv, Hkv, D]
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int,
    block_k: int,
    num_buffers: int = 2,
    vmem_limit: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Forward with an explicit ``num_buffers``-deep KV staging ring.

    Bit-identical to :func:`flash_attention_fwd` (same per-block f32 math,
    same accumulation order).  ``vmem_limit`` is handed to the Mosaic
    compiler as its VMEM budget on backends that honor it; depth
    feasibility against the budget is the *caller's* job
    (``autotune.fit_buffer_depth`` — ops.py falls back to depth 1).
    """
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    bq, bk = min(block_q, sq), min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    assert num_buffers >= 1, num_buffers
    nq, nk = sq // bq, skv // bk
    nb = min(num_buffers, nk)   # depth beyond the block count is dead VMEM

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _fa_pipelined_kernel, causal=causal, sq=sq, skv=skv, bq=bq, bk=bk,
        nk=nk, num_buffers=nb, g=g)

    params = dict(dimension_semantics=("parallel", "parallel", "parallel"))
    if vmem_limit is not None:
        params["vmem_limit_bytes"] = int(vmem_limit)

    out, lse = pl.pallas_call(
        kernel,
        grid=(b, hq, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i: (b_, h, i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h, i: (b_, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((nb, bk, d), kt.dtype),
            pltpu.VMEM((nb, bk, d), vt.dtype),
            pltpu.SemaphoreType.DMA((2, nb)),
        ],
        compiler_params=pltpu.CompilerParams(**params),
        interpret=interpret,
        name="flash_attention_fwd_pipelined",
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse[..., 0]


# ---------------------------------------------------------------------------
# Quantized forward: int8/fp8 K/V with per-(token, head) scales.
#
# K/V arrive as quantized values plus one scale per KV row; the kernel never
# materializes the dequantized block.  The scale is constant along the
# contraction axis, so it factors out of both matmuls: scores are
# (q . k_q) * ks^T and the output accumulates (p * vs^T) . v_q — the MXU
# sees narrow operands, the scales ride on the cheap elementwise side.
# Same running-softmax state and block skipping as ``_fa_kernel``.
# ---------------------------------------------------------------------------


def _fa_quant_kernel(q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref, lse_ref,
                     acc_ref, m_ref, l_ref, *,
                     causal: bool, sq: int, skv: int, bq: int, bk: int,
                     nk: int):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    run = (j * bk <= i * bq + bq - 1 + (skv - sq)) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)           # [bk, d] quantized
        v = v_ref[0, 0].astype(jnp.float32)
        ks = ks_ref[0, 0].astype(jnp.float32)         # [bk, 1]
        vs = vs_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        # per-row K scale factors out of the contraction: apply to scores
        s = s * ks.reshape(1, bk)
        s = s * (1.0 / np.sqrt(q.shape[-1]))          # [bq, bk]

        if causal:
            qpos = i * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0) + (skv - sq)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)

        m_prev = m_ref[...]                            # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                         # [bq, bk]
        corr = jnp.exp(m_prev - m_new)                 # [bq, 1]
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        # per-row V scale rides on p (elementwise) so the p @ v matmul
        # keeps its narrow operand
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p * vs.reshape(1, bk), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(l)).astype(jnp.float32)


def flash_attention_fwd_quantized(
    q: jax.Array,        # [B, Sq, Hq, D]
    k_q: jax.Array,      # [B, Skv, Hkv, D] int8/fp8
    k_scale: jax.Array,  # [B, Skv, Hkv, 1]
    v_q: jax.Array,
    v_scale: jax.Array,
    *,
    causal: bool = True,
    block_q: int,
    block_k: int,
    interpret: bool = False,
) -> jax.Array:
    """Flash forward over a quantized KV stream; output matches the
    dequantized-f32 oracle to f32 rounding (the scale placement is exact
    arithmetic, not an approximation).  Forward-only: the quantized cache
    is an inference artifact, gradients flow through the float path."""
    b, sq, hq, d = q.shape
    skv, hkv = k_q.shape[1], k_q.shape[2]
    g = hq // hkv
    bq, bk = min(block_q, sq), min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    nq, nk = sq // bq, skv // bk

    qt = q.transpose(0, 2, 1, 3)
    kt = k_q.transpose(0, 2, 1, 3)
    vt = v_q.transpose(0, 2, 1, 3)
    kst = k_scale.transpose(0, 2, 1, 3)
    vst = v_scale.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _fa_quant_kernel, causal=causal, sq=sq, skv=skv, bq=bq, bk=bk, nk=nk)

    out, lse = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, i, j: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, 1), lambda b_, h, i, j: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, i, j: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, 1), lambda b_, h, i, j: (b_, h // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h, i, j: (b_, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_fwd_quantized",
    )(qt, kt, kst, vt, vst)
    return out.transpose(0, 2, 1, 3), lse[..., 0]


# ---------------------------------------------------------------------------
# backward — standard flash recompute: dq kernel + dkv kernel
# ---------------------------------------------------------------------------

def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                      dq_ref, acc_ref, *, causal, sq, skv, bq, bk, nk):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = (j * bk <= i * bq + bq - 1 + (skv - sq)) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)         # [bq, d]
        lse = lse_ref[0, 0]                           # [bq, 1]
        dd = dd_ref[0, 0]                             # [bq, 1]
        scale = 1.0 / np.sqrt(q.shape[-1])
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = i * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0) + (skv - sq)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        p = jnp.exp(s - lse)                          # [bq, bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dd) * scale                    # [bq, bk]
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                       dk_ref, dv_ref, dk_acc, dv_acc, *,
                       causal, sq, skv, bq, bk, nq):
    j = pl.program_id(2)   # kv block
    i = pl.program_id(3)   # q block (sequential)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = (j * bk <= i * bq + bq - 1 + (skv - sq)) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        dd = dd_ref[0, 0]
        scale = 1.0 / np.sqrt(q.shape[-1])
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = i * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0) + (skv - sq)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        p = jnp.exp(s - lse)                          # [bq, bk]
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bk, d]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dd) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bk, d]

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(
    q, k, v, out, lse, do, *, causal: bool, block_q: int, block_k: int,
    interpret: bool = False,
):
    """Returns (dq, dk, dv). lse: [B, Hq, Sq] from the forward."""
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    bq, bk = min(block_q, sq), min(block_k, skv)
    nq, nk = sq // bq, skv // bk

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)
    dd = jnp.sum(dot.astype(jnp.float32)
                 * out.transpose(0, 2, 1, 3).astype(jnp.float32),
                 axis=-1, keepdims=True)              # [B, Hq, Sq, 1]
    lse4 = lse[..., None]                             # [B, Hq, Sq, 1]

    # dq kernel: grid (b, hq, nq, nk) — q indexed by axis 2, kv by axis 3
    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, causal=causal, sq=sq, skv=skv,
                          bq=bq, bk=bk, nk=nk),
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, i, j: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, i, j: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h, i, j: (b_, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(qt, kt, vt, dot, lse4, dd)

    # dk/dv kernel: grid (b, hq, nk, nq) — per-q-head partials, grouped-
    # summed to kv heads afterwards (GQA)
    dkq, dvq = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, causal=causal, sq=sq, skv=skv,
                          bq=bq, bk=bk, nq=nq),
        grid=(b, hq, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, j, i: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, j, i: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, j, i: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, j, i: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h, j, i: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h, j, i: (b_, h, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, j, i: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, j, i: (b_, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, skv, d), k.dtype),
            jax.ShapeDtypeStruct((b, hq, skv, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(qt, kt, vt, dot, lse4, dd)

    dk = dkq.reshape(b, hkv, g, skv, d).sum(axis=2).transpose(0, 2, 1, 3)
    dv = dvq.reshape(b, hkv, g, skv, d).sum(axis=2).transpose(0, 2, 1, 3)
    return dq.transpose(0, 2, 1, 3), dk.astype(k.dtype), dv.astype(v.dtype)
