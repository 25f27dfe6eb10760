"""Flash-decode — Pallas TPU kernel with split-K partial softmax.

This kernel is the cleanest on-device embodiment of the paper's ParallelFor:
N = kv_len cache rows are split into ``num_splits`` blocks; each split is an
independent worker producing a partial (m, l, acc); a cheap combine merges
them.  More splits = more parallelism but more combine overhead (the paper's
FAA-cost term L) — ``num_splits`` is chosen by
repro.core.autotune.decode_split_k.

Grid: (B, Hkv, num_splits).  All G = Hq/Hkv query heads of one KV head are
processed together (q tile [G, D] keeps the MXU busy; G=1..128 across the
assigned archs).  kv_len arrives via scalar prefetch.

Note on TPU layout: the per-split stats outputs are [..., G] with G < 128;
on real hardware Mosaic pads the lane dim — acceptable since stats are tiny
next to the KV stream.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import autotune

NEG_INF = -1e30


def _decode_kernel(kv_len_ref, q_ref, k_ref, v_ref,
                   o_ref, m_ref, l_ref, *, split_size: int, d: int):
    b = pl.program_id(0)
    s_idx = pl.program_id(2)
    kv_len = kv_len_ref[b]

    q = q_ref[0, 0].astype(jnp.float32)           # [G, D]
    k = k_ref[0, 0].astype(jnp.float32)           # [ss, D]
    v = v_ref[0, 0].astype(jnp.float32)           # [ss, D]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * (1.0 / np.sqrt(d))                    # [G, ss]
    pos = s_idx * split_size + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    s = jnp.where(pos < kv_len, s, NEG_INF)

    m = jnp.max(s, axis=1, keepdims=True)         # [G, 1]
    # all-masked split: exp(NEG_INF - NEG_INF) would be 1 — guard with m>-inf
    safe_m = jnp.maximum(m, -1e29)
    p = jnp.where(m > NEG_INF / 2, jnp.exp(s - safe_m), 0.0)
    l = jnp.sum(p, axis=1, keepdims=True)         # [G, 1]
    acc = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[0, 0, 0] = acc
    m_ref[0, 0, 0] = m
    l_ref[0, 0, 0] = l


def decode_attention_fwd(
    q: jax.Array,        # [B, Hq, D]
    k: jax.Array,        # [B, S, Hkv, D]
    v: jax.Array,
    kv_len: jax.Array,   # [B] int32
    *,
    num_splits: int,
    interpret: bool = False,
) -> jax.Array:
    b, hq, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    # largest divisor of S <= the tuned split count (halving collapsed to
    # 1 split on non-power-of-two cache lengths)
    ns = autotune.fit_block(s, num_splits)
    ss = s // ns

    qt = q.reshape(b, hkv, g, d)
    kt = k.transpose(0, 2, 1, 3)   # [B, Hkv, S, D]
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_decode_kernel, split_size=ss, d=d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, ns),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h, j, *_: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, ss, d), lambda b_, h, j, *_: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, ss, d), lambda b_, h, j, *_: (b_, h, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, g, d),
                         lambda b_, h, j, *_: (b_, h, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, g, 1),
                         lambda b_, h, j, *_: (b_, h, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, g, 1),
                         lambda b_, h, j, *_: (b_, h, j, 0, 0)),
        ],
    )
    o_part, m_part, l_part = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, ns, g, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, ns, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, ns, g, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
        name="flash_decode",
    )(kv_len.astype(jnp.int32), qt, kt, vt)

    # ---- combine partial softmaxes (the per-split "FAA" cost) ----
    m_glob = jnp.max(m_part, axis=2, keepdims=True)          # [B,Hkv,1,G,1]
    w = jnp.exp(m_part - m_glob)
    l_glob = jnp.sum(l_part * w, axis=2)                     # [B,Hkv,G,1]
    o = jnp.sum(o_part * w, axis=2) / jnp.maximum(l_glob, 1e-30)
    return o.reshape(b, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Multi-buffered variant: explicit DMA/compute pipelining over the splits.
#
# The split-K kernel above parallelizes splits across the grid; this one
# walks them sequentially inside one grid step (B, Hkv) and overlaps the
# split j+depth-1 KV fetch with compute on split j through a
# ``num_buffers``-deep VMEM ring.  It writes the SAME per-split partials
# (o, m, l) as the classic kernel — the external partial-softmax combine is
# shared verbatim — so the final output is bit-identical.
# ---------------------------------------------------------------------------


def _decode_pipelined_kernel(kv_len_ref, q_ref, k_hbm, v_hbm,
                             o_ref, m_ref, l_ref, k_buf, v_buf, sem, *,
                             split_size: int, d: int, num_splits: int,
                             num_buffers: int):
    b = pl.program_id(0)
    h = pl.program_id(1)
    kv_len = kv_len_ref[b]
    nb = num_buffers

    def kv_copy(blk, slot):
        start = blk * split_size
        return (
            pltpu.make_async_copy(
                k_hbm.at[b, h, pl.ds(start, split_size), :],
                k_buf.at[slot], sem.at[0, slot]),
            pltpu.make_async_copy(
                v_hbm.at[b, h, pl.ds(start, split_size), :],
                v_buf.at[slot], sem.at[1, slot]),
        )

    for slot in range(min(nb - 1, num_splits)):
        ck, cv = kv_copy(slot, slot)
        ck.start()
        cv.start()

    q = q_ref[0, 0].astype(jnp.float32)           # [G, D]

    def body(j, carry):
        nxt = j + nb - 1

        @pl.when(nxt < num_splits)
        def _prefetch():
            ck, cv = kv_copy(nxt, jax.lax.rem(nxt, nb))
            ck.start()
            cv.start()

        slot = jax.lax.rem(j, nb)
        ck, cv = kv_copy(j, slot)
        ck.wait()
        cv.wait()
        k = k_buf[slot].astype(jnp.float32)       # [ss, D]
        v = v_buf[slot].astype(jnp.float32)       # [ss, D]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (1.0 / np.sqrt(d))                # [G, ss]
        pos = j * split_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < kv_len, s, NEG_INF)

        m = jnp.max(s, axis=1, keepdims=True)     # [G, 1]
        safe_m = jnp.maximum(m, -1e29)
        p = jnp.where(m > NEG_INF / 2, jnp.exp(s - safe_m), 0.0)
        l = jnp.sum(p, axis=1, keepdims=True)
        acc = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        o_ref[0, 0, j] = acc
        m_ref[0, 0, j] = m
        l_ref[0, 0, j] = l
        return carry

    jax.lax.fori_loop(0, num_splits, body, 0)


def decode_attention_fwd_pipelined(
    q: jax.Array,        # [B, Hq, D]
    k: jax.Array,        # [B, S, Hkv, D]
    v: jax.Array,
    kv_len: jax.Array,   # [B] int32
    *,
    num_splits: int,
    num_buffers: int = 2,
    vmem_limit: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Split-K decode with an explicit KV staging ring — bit-identical to
    :func:`decode_attention_fwd` (identical per-split partials, identical
    combine)."""
    b, hq, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    ns = autotune.fit_block(s, num_splits)
    ss = s // ns
    nb = min(max(1, num_buffers), ns)

    qt = q.reshape(b, hkv, g, d)
    kt = k.transpose(0, 2, 1, 3)   # [B, Hkv, S, D]
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _decode_pipelined_kernel, split_size=ss, d=d, num_splits=ns,
        num_buffers=nb)
    params = dict(dimension_semantics=("parallel", "parallel"))
    if vmem_limit is not None:
        params["vmem_limit_bytes"] = int(vmem_limit)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h, *_: (b_, h, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, ns, g, d),
                         lambda b_, h, *_: (b_, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, ns, g, 1),
                         lambda b_, h, *_: (b_, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, ns, g, 1),
                         lambda b_, h, *_: (b_, h, 0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((nb, ss, d), kt.dtype),
            pltpu.VMEM((nb, ss, d), vt.dtype),
            pltpu.SemaphoreType.DMA((2, nb)),
        ],
    )
    o_part, m_part, l_part = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, ns, g, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, ns, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, ns, g, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(**params),
        interpret=interpret,
        name="flash_decode_pipelined",
    )(kv_len.astype(jnp.int32), qt, kt, vt)

    # combine shared verbatim with the classic kernel (bit-identity)
    m_glob = jnp.max(m_part, axis=2, keepdims=True)          # [B,Hkv,1,G,1]
    w = jnp.exp(m_part - m_glob)
    l_glob = jnp.sum(l_part * w, axis=2)                     # [B,Hkv,G,1]
    o = jnp.sum(o_part * w, axis=2) / jnp.maximum(l_glob, 1e-30)
    return o.reshape(b, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Quantized split-K variant: int8/fp8 K/V rows with one scale per row.
# The per-row scale factors out of both contractions (scores scaled per
# column, p scaled before the value matmul), so the math equals the
# dequantized-f32 oracle up to f32 rounding.  Partials and combine are
# shared with the float kernel.
# ---------------------------------------------------------------------------


def _decode_quant_kernel(kv_len_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                         o_ref, m_ref, l_ref, *, split_size: int, d: int):
    b = pl.program_id(0)
    s_idx = pl.program_id(2)
    kv_len = kv_len_ref[b]

    q = q_ref[0, 0].astype(jnp.float32)           # [G, D]
    k = k_ref[0, 0].astype(jnp.float32)           # [ss, D] quantized
    v = v_ref[0, 0].astype(jnp.float32)
    ks = ks_ref[0, 0].astype(jnp.float32)         # [ss, 1]
    vs = vs_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * ks.reshape(1, split_size)             # dequant on the scores
    s = s * (1.0 / np.sqrt(d))                    # [G, ss]
    pos = s_idx * split_size + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    s = jnp.where(pos < kv_len, s, NEG_INF)

    m = jnp.max(s, axis=1, keepdims=True)         # [G, 1]
    safe_m = jnp.maximum(m, -1e29)
    p = jnp.where(m > NEG_INF / 2, jnp.exp(s - safe_m), 0.0)
    l = jnp.sum(p, axis=1, keepdims=True)         # [G, 1]
    acc = jax.lax.dot_general(p * vs.reshape(1, split_size), v,
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[0, 0, 0] = acc
    m_ref[0, 0, 0] = m
    l_ref[0, 0, 0] = l


def decode_attention_fwd_quantized(
    q: jax.Array,        # [B, Hq, D]
    k_q: jax.Array,      # [B, S, Hkv, D] int8/fp8
    k_scale: jax.Array,  # [B, S, Hkv, 1]
    v_q: jax.Array,
    v_scale: jax.Array,
    kv_len: jax.Array,   # [B] int32
    *,
    num_splits: int,
    interpret: bool = False,
) -> jax.Array:
    b, hq, d = q.shape
    s, hkv = k_q.shape[1], k_q.shape[2]
    g = hq // hkv
    ns = autotune.fit_block(s, num_splits)
    ss = s // ns

    qt = q.reshape(b, hkv, g, d)
    kt = k_q.transpose(0, 2, 1, 3)   # [B, Hkv, S, D]
    vt = v_q.transpose(0, 2, 1, 3)
    kst = k_scale.transpose(0, 2, 1, 3)
    vst = v_scale.transpose(0, 2, 1, 3)

    kernel = functools.partial(_decode_quant_kernel, split_size=ss, d=d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, ns),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h, j, *_: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, ss, d), lambda b_, h, j, *_: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, ss, 1), lambda b_, h, j, *_: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, ss, d), lambda b_, h, j, *_: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, ss, 1), lambda b_, h, j, *_: (b_, h, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, g, d),
                         lambda b_, h, j, *_: (b_, h, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, g, 1),
                         lambda b_, h, j, *_: (b_, h, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, g, 1),
                         lambda b_, h, j, *_: (b_, h, j, 0, 0)),
        ],
    )
    o_part, m_part, l_part = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, ns, g, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, ns, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, ns, g, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
        name="flash_decode_quantized",
    )(kv_len.astype(jnp.int32), qt, kt, kst, vt, vst)

    # combine shared verbatim with the float kernel
    m_glob = jnp.max(m_part, axis=2, keepdims=True)          # [B,Hkv,1,G,1]
    w = jnp.exp(m_part - m_glob)
    l_glob = jnp.sum(l_part * w, axis=2)                     # [B,Hkv,G,1]
    o = jnp.sum(o_part * w, axis=2) / jnp.maximum(l_glob, 1e-30)
    return o.reshape(b, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged variant: the KV cache is a shared page pool addressed per row
# through a page table.  Split-K's fixed stride becomes the page: the grid's
# third axis walks LOGICAL pages and the k/v index maps dereference the
# prefetched page table, so each program DMAs exactly one physical page —
# the gather never materializes a contiguous cache.  Pool row 0 is the
# serve engine's reserved scratch page; it is simply never named by a live
# page table, so the kernel needs no special case for it.
# ---------------------------------------------------------------------------


def _paged_decode_kernel(pt_ref, kv_len_ref, q_ref, k_ref, v_ref,
                         o_ref, m_ref, l_ref, *, page_size: int, d: int):
    b = pl.program_id(0)
    j = pl.program_id(2)                          # logical page index
    kv_len = kv_len_ref[b]

    q = q_ref[0, 0].astype(jnp.float32)           # [G, D]
    k = k_ref[0, 0].astype(jnp.float32)           # [ps, D]
    v = v_ref[0, 0].astype(jnp.float32)           # [ps, D]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * (1.0 / np.sqrt(d))                    # [G, ps]
    pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < kv_len, s, NEG_INF)

    m = jnp.max(s, axis=1, keepdims=True)         # [G, 1]
    # wholly-masked page (past this row's length): exp(NEG_INF - NEG_INF)
    # would be 1 — guard with m > -inf, identical to the split-K kernel
    safe_m = jnp.maximum(m, -1e29)
    p = jnp.where(m > NEG_INF / 2, jnp.exp(s - safe_m), 0.0)
    l = jnp.sum(p, axis=1, keepdims=True)
    acc = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[0, 0, 0] = acc
    m_ref[0, 0, 0] = m
    l_ref[0, 0, 0] = l


def paged_decode_attention_fwd(
    q: jax.Array,           # [B, Hq, D]
    k_pool: jax.Array,      # [Np, ps, Hkv, D] shared page pool
    v_pool: jax.Array,
    page_table: jax.Array,  # [B, P] int32 pool indices per logical page
    kv_len: jax.Array,      # [B] int32
    *,
    interpret: bool = False,
) -> jax.Array:
    b, hq, d = q.shape
    ps, hkv = k_pool.shape[1], k_pool.shape[2]
    pages = page_table.shape[1]
    g = hq // hkv

    qt = q.reshape(b, hkv, g, d)
    kt = k_pool.transpose(0, 2, 1, 3)   # [Np, Hkv, ps, D]
    vt = v_pool.transpose(0, 2, 1, 3)

    kernel = functools.partial(_paged_decode_kernel, page_size=ps, d=d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, pages),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h, j, *_: (b_, h, 0, 0)),
            # the page-table dereference IS the gather: block (j) of row b_
            # lives at pool row pt[b_, j]
            pl.BlockSpec((1, 1, ps, d),
                         lambda b_, h, j, pt, kvl: (pt[b_, j], h, 0, 0)),
            pl.BlockSpec((1, 1, ps, d),
                         lambda b_, h, j, pt, kvl: (pt[b_, j], h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, g, d),
                         lambda b_, h, j, *_: (b_, h, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, g, 1),
                         lambda b_, h, j, *_: (b_, h, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, g, 1),
                         lambda b_, h, j, *_: (b_, h, j, 0, 0)),
        ],
    )
    o_part, m_part, l_part = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, pages, g, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, pages, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, pages, g, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_flash_decode",
    )(page_table.astype(jnp.int32), kv_len.astype(jnp.int32), qt, kt, vt)

    # identical partial-softmax combine: logical pages are the splits
    m_glob = jnp.max(m_part, axis=2, keepdims=True)
    w = jnp.exp(m_part - m_glob)
    l_glob = jnp.sum(l_part * w, axis=2)
    o = jnp.sum(o_part * w, axis=2) / jnp.maximum(l_glob, 1e-30)
    return o.reshape(b, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Multi-buffered paged variant: the page gather IS the DMA — each logical
# page's fetch from its physical pool row (scalar-prefetched page table)
# overlaps compute on the previous page through the same VMEM ring as the
# dense pipelined kernel.  Per-page partials + shared combine keep it
# bit-identical to ``paged_decode_attention_fwd``.
# ---------------------------------------------------------------------------


def _paged_decode_pipelined_kernel(pt_ref, kv_len_ref, q_ref, k_hbm, v_hbm,
                                   o_ref, m_ref, l_ref, k_buf, v_buf, sem, *,
                                   page_size: int, d: int, pages: int,
                                   num_buffers: int):
    b = pl.program_id(0)
    h = pl.program_id(1)
    kv_len = kv_len_ref[b]
    nb = num_buffers

    def kv_copy(blk, slot):
        phys = pt_ref[b, blk]                     # physical pool row
        return (
            pltpu.make_async_copy(
                k_hbm.at[phys, h], k_buf.at[slot], sem.at[0, slot]),
            pltpu.make_async_copy(
                v_hbm.at[phys, h], v_buf.at[slot], sem.at[1, slot]),
        )

    for slot in range(min(nb - 1, pages)):
        ck, cv = kv_copy(slot, slot)
        ck.start()
        cv.start()

    q = q_ref[0, 0].astype(jnp.float32)           # [G, D]

    def body(j, carry):
        nxt = j + nb - 1

        @pl.when(nxt < pages)
        def _prefetch():
            ck, cv = kv_copy(nxt, jax.lax.rem(nxt, nb))
            ck.start()
            cv.start()

        slot = jax.lax.rem(j, nb)
        ck, cv = kv_copy(j, slot)
        ck.wait()
        cv.wait()
        k = k_buf[slot].astype(jnp.float32)       # [ps, D]
        v = v_buf[slot].astype(jnp.float32)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (1.0 / np.sqrt(d))                # [G, ps]
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < kv_len, s, NEG_INF)

        m = jnp.max(s, axis=1, keepdims=True)     # [G, 1]
        safe_m = jnp.maximum(m, -1e29)
        p = jnp.where(m > NEG_INF / 2, jnp.exp(s - safe_m), 0.0)
        l = jnp.sum(p, axis=1, keepdims=True)
        acc = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        o_ref[0, 0, j] = acc
        m_ref[0, 0, j] = m
        l_ref[0, 0, j] = l
        return carry

    jax.lax.fori_loop(0, pages, body, 0)


def paged_decode_attention_fwd_pipelined(
    q: jax.Array,           # [B, Hq, D]
    k_pool: jax.Array,      # [Np, ps, Hkv, D] shared page pool
    v_pool: jax.Array,
    page_table: jax.Array,  # [B, P] int32 pool indices per logical page
    kv_len: jax.Array,      # [B] int32
    *,
    num_buffers: int = 2,
    vmem_limit: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Paged decode with an explicit page staging ring — bit-identical to
    :func:`paged_decode_attention_fwd`."""
    b, hq, d = q.shape
    ps, hkv = k_pool.shape[1], k_pool.shape[2]
    pages = page_table.shape[1]
    g = hq // hkv
    nb = min(max(1, num_buffers), pages)

    qt = q.reshape(b, hkv, g, d)
    kt = k_pool.transpose(0, 2, 1, 3)   # [Np, Hkv, ps, D]
    vt = v_pool.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _paged_decode_pipelined_kernel, page_size=ps, d=d, pages=pages,
        num_buffers=nb)
    params = dict(dimension_semantics=("parallel", "parallel"))
    if vmem_limit is not None:
        params["vmem_limit_bytes"] = int(vmem_limit)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h, *_: (b_, h, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, pages, g, d),
                         lambda b_, h, *_: (b_, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, pages, g, 1),
                         lambda b_, h, *_: (b_, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, pages, g, 1),
                         lambda b_, h, *_: (b_, h, 0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((nb, ps, d), kt.dtype),
            pltpu.VMEM((nb, ps, d), vt.dtype),
            pltpu.SemaphoreType.DMA((2, nb)),
        ],
    )
    o_part, m_part, l_part = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, pages, g, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, pages, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, pages, g, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(**params),
        interpret=interpret,
        name="paged_flash_decode_pipelined",
    )(page_table.astype(jnp.int32), kv_len.astype(jnp.int32), qt, kt, vt)

    # identical partial-softmax combine: logical pages are the splits
    m_glob = jnp.max(m_part, axis=2, keepdims=True)
    w = jnp.exp(m_part - m_glob)
    l_glob = jnp.sum(l_part * w, axis=2)
    o = jnp.sum(o_part * w, axis=2) / jnp.maximum(l_glob, 1e-30)
    return o.reshape(b, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Quantized paged variant: pool pages hold int8/fp8 rows plus a per-row
# scale page.  The scale pages ride the same page-table dereference as the
# values, so page placement stays irrelevant to the math — bit-identity
# across placements holds exactly as in the float kernel.
# ---------------------------------------------------------------------------


def _paged_decode_quant_kernel(pt_ref, kv_len_ref, q_ref, k_ref, ks_ref,
                               v_ref, vs_ref, o_ref, m_ref, l_ref, *,
                               page_size: int, d: int):
    b = pl.program_id(0)
    j = pl.program_id(2)                          # logical page index
    kv_len = kv_len_ref[b]

    q = q_ref[0, 0].astype(jnp.float32)           # [G, D]
    k = k_ref[0, 0].astype(jnp.float32)           # [ps, D] quantized
    v = v_ref[0, 0].astype(jnp.float32)
    ks = ks_ref[0, 0].astype(jnp.float32)         # [ps, 1]
    vs = vs_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * ks.reshape(1, page_size)              # dequant on the scores
    s = s * (1.0 / np.sqrt(d))                    # [G, ps]
    pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < kv_len, s, NEG_INF)

    m = jnp.max(s, axis=1, keepdims=True)         # [G, 1]
    safe_m = jnp.maximum(m, -1e29)
    p = jnp.where(m > NEG_INF / 2, jnp.exp(s - safe_m), 0.0)
    l = jnp.sum(p, axis=1, keepdims=True)
    acc = jax.lax.dot_general(p * vs.reshape(1, page_size), v,
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[0, 0, 0] = acc
    m_ref[0, 0, 0] = m
    l_ref[0, 0, 0] = l


def paged_decode_attention_fwd_quantized(
    q: jax.Array,           # [B, Hq, D]
    k_pool: jax.Array,      # [Np, ps, Hkv, D] int8/fp8 page pool
    k_scale: jax.Array,     # [Np, ps, Hkv, 1] per-row scale pages
    v_pool: jax.Array,
    v_scale: jax.Array,
    page_table: jax.Array,  # [B, P] int32 pool indices per logical page
    kv_len: jax.Array,      # [B] int32
    *,
    interpret: bool = False,
) -> jax.Array:
    b, hq, d = q.shape
    ps, hkv = k_pool.shape[1], k_pool.shape[2]
    pages = page_table.shape[1]
    g = hq // hkv

    qt = q.reshape(b, hkv, g, d)
    kt = k_pool.transpose(0, 2, 1, 3)   # [Np, Hkv, ps, D]
    vt = v_pool.transpose(0, 2, 1, 3)
    kst = k_scale.transpose(0, 2, 1, 3)  # [Np, Hkv, ps, 1]
    vst = v_scale.transpose(0, 2, 1, 3)

    kernel = functools.partial(_paged_decode_quant_kernel, page_size=ps, d=d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, pages),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h, j, *_: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, ps, d),
                         lambda b_, h, j, pt, kvl: (pt[b_, j], h, 0, 0)),
            pl.BlockSpec((1, 1, ps, 1),
                         lambda b_, h, j, pt, kvl: (pt[b_, j], h, 0, 0)),
            pl.BlockSpec((1, 1, ps, d),
                         lambda b_, h, j, pt, kvl: (pt[b_, j], h, 0, 0)),
            pl.BlockSpec((1, 1, ps, 1),
                         lambda b_, h, j, pt, kvl: (pt[b_, j], h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, g, d),
                         lambda b_, h, j, *_: (b_, h, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, g, 1),
                         lambda b_, h, j, *_: (b_, h, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, g, 1),
                         lambda b_, h, j, *_: (b_, h, j, 0, 0)),
        ],
    )
    o_part, m_part, l_part = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, pages, g, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, pages, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, pages, g, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_flash_decode_quantized",
    )(page_table.astype(jnp.int32), kv_len.astype(jnp.int32),
      qt, kt, kst, vt, vst)

    # identical partial-softmax combine: logical pages are the splits
    m_glob = jnp.max(m_part, axis=2, keepdims=True)
    w = jnp.exp(m_part - m_glob)
    l_glob = jnp.sum(l_part * w, axis=2)
    o = jnp.sum(o_part * w, axis=2) / jnp.maximum(l_glob, 1e-30)
    return o.reshape(b, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Multi-buffered quantized paged variant: four DMA streams per page (k, its
# scale, v, its scale) share one prefetch ring.  The scale pages are tiny
# ([ps, 1] f16) next to the value pages, so the extra streams cost DMA issue
# overhead, not bandwidth — exactly the regime the measured autotuner is
# there to arbitrate.  Partials + combine shared with the classic quant
# kernel → bit-identical output.
# ---------------------------------------------------------------------------


def _paged_decode_quant_pipelined_kernel(
        pt_ref, kv_len_ref, q_ref, k_hbm, ks_hbm, v_hbm, vs_hbm,
        o_ref, m_ref, l_ref, k_buf, ks_buf, v_buf, vs_buf, sem, *,
        page_size: int, d: int, pages: int, num_buffers: int):
    b = pl.program_id(0)
    h = pl.program_id(1)
    kv_len = kv_len_ref[b]
    nb = num_buffers

    def kv_copy(blk, slot):
        phys = pt_ref[b, blk]                     # physical pool row
        return (
            pltpu.make_async_copy(
                k_hbm.at[phys, h], k_buf.at[slot], sem.at[0, slot]),
            pltpu.make_async_copy(
                ks_hbm.at[phys, h], ks_buf.at[slot], sem.at[1, slot]),
            pltpu.make_async_copy(
                v_hbm.at[phys, h], v_buf.at[slot], sem.at[2, slot]),
            pltpu.make_async_copy(
                vs_hbm.at[phys, h], vs_buf.at[slot], sem.at[3, slot]),
        )

    for slot in range(min(nb - 1, pages)):
        for c in kv_copy(slot, slot):
            c.start()

    q = q_ref[0, 0].astype(jnp.float32)           # [G, D]

    def body(j, carry):
        nxt = j + nb - 1

        @pl.when(nxt < pages)
        def _prefetch():
            for c in kv_copy(nxt, jax.lax.rem(nxt, nb)):
                c.start()

        slot = jax.lax.rem(j, nb)
        for c in kv_copy(j, slot):
            c.wait()
        k = k_buf[slot].astype(jnp.float32)       # [ps, D]
        v = v_buf[slot].astype(jnp.float32)
        ks = ks_buf[slot].astype(jnp.float32)     # [ps, 1]
        vs = vs_buf[slot].astype(jnp.float32)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * ks.reshape(1, page_size)
        s = s * (1.0 / np.sqrt(d))                # [G, ps]
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < kv_len, s, NEG_INF)

        m = jnp.max(s, axis=1, keepdims=True)     # [G, 1]
        safe_m = jnp.maximum(m, -1e29)
        p = jnp.where(m > NEG_INF / 2, jnp.exp(s - safe_m), 0.0)
        l = jnp.sum(p, axis=1, keepdims=True)
        acc = jax.lax.dot_general(p * vs.reshape(1, page_size), v,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        o_ref[0, 0, j] = acc
        m_ref[0, 0, j] = m
        l_ref[0, 0, j] = l
        return carry

    jax.lax.fori_loop(0, pages, body, 0)


def paged_decode_attention_fwd_quantized_pipelined(
    q: jax.Array,           # [B, Hq, D]
    k_pool: jax.Array,      # [Np, ps, Hkv, D] int8/fp8 page pool
    k_scale: jax.Array,     # [Np, ps, Hkv, 1]
    v_pool: jax.Array,
    v_scale: jax.Array,
    page_table: jax.Array,  # [B, P] int32 pool indices per logical page
    kv_len: jax.Array,      # [B] int32
    *,
    num_buffers: int = 2,
    vmem_limit: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Quantized paged decode with an explicit page staging ring —
    bit-identical to :func:`paged_decode_attention_fwd_quantized`."""
    b, hq, d = q.shape
    ps, hkv = k_pool.shape[1], k_pool.shape[2]
    pages = page_table.shape[1]
    g = hq // hkv
    nb = min(max(1, num_buffers), pages)

    qt = q.reshape(b, hkv, g, d)
    kt = k_pool.transpose(0, 2, 1, 3)   # [Np, Hkv, ps, D]
    vt = v_pool.transpose(0, 2, 1, 3)
    kst = k_scale.transpose(0, 2, 1, 3)  # [Np, Hkv, ps, 1]
    vst = v_scale.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _paged_decode_quant_pipelined_kernel, page_size=ps, d=d,
        pages=pages, num_buffers=nb)
    params = dict(dimension_semantics=("parallel", "parallel"))
    if vmem_limit is not None:
        params["vmem_limit_bytes"] = int(vmem_limit)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h, *_: (b_, h, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, pages, g, d),
                         lambda b_, h, *_: (b_, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, pages, g, 1),
                         lambda b_, h, *_: (b_, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, pages, g, 1),
                         lambda b_, h, *_: (b_, h, 0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((nb, ps, d), kt.dtype),
            pltpu.VMEM((nb, ps, 1), kst.dtype),
            pltpu.VMEM((nb, ps, d), vt.dtype),
            pltpu.VMEM((nb, ps, 1), vst.dtype),
            pltpu.SemaphoreType.DMA((4, nb)),
        ],
    )
    o_part, m_part, l_part = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, pages, g, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, pages, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, pages, g, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(**params),
        interpret=interpret,
        name="paged_flash_decode_quantized_pipelined",
    )(page_table.astype(jnp.int32), kv_len.astype(jnp.int32),
      qt, kt, kst, vt, vst)

    # identical partial-softmax combine: logical pages are the splits
    m_glob = jnp.max(m_part, axis=2, keepdims=True)
    w = jnp.exp(m_part - m_glob)
    l_glob = jnp.sum(l_part * w, axis=2)
    o = jnp.sum(o_part * w, axis=2) / jnp.maximum(l_glob, 1e-30)
    return o.reshape(b, hq, d).astype(q.dtype)
