"""Attention: chunked (flash-style) softmax attention in pure JAX.

The chunked path is the framework default — it never materializes the full
[Sq, Sk] score matrix, so 32k-token prefill lowers with bounded live memory.
Chunk sizes are the paper's block-size knob, chosen by
:func:`repro.core.autotune.attention_block_sizes`; on real TPUs the Pallas
kernel (`repro.kernels.flash_attention`) takes over via ``use_kernel``.

Layout convention: q [B, Sq, Hq, D]; k, v [B, Skv, Hkv, D]; Hq = G * Hkv.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import autotune
from repro.kernels import quant
from repro.models import layers

NEG_INF = -1e30


def naive_attention(q, k, v, *, causal=True, kv_len=None, q_offset=None):
    """O(S²)-memory oracle (tests & tiny shapes only)."""
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qf = q.astype(jnp.float32).reshape(b, sq, hkv, g, d)
    kf = k.astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf) / np.sqrt(d)
    qpos = jnp.arange(sq) + (q_offset if q_offset is not None else (skv - sq))
    kpos = jnp.arange(skv)
    mask = jnp.ones((b, sq, skv), bool)
    if causal:
        mask &= (kpos[None, :] <= qpos[:, None])[None]
    if kv_len is not None:
        kl = jnp.broadcast_to(jnp.asarray(kv_len), (b,))
        mask &= kpos[None, None, :] < kl[:, None, None]
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, hq, v.shape[-1]).astype(q.dtype)


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_k: Optional[int] = None,
    kv_len: Optional[jax.Array] = None,
    q_offset: Optional[int] = None,
) -> jax.Array:
    """Flash-style attention: scan over KV blocks with running (m, l, o).

    kv_len: optional [B] (or scalar) valid-length mask over the KV axis (for
    decode against a fixed-size cache). q_offset: absolute position of q[0]
    (defaults to Skv - Sq, the standard suffix alignment).
    """
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]           # may differ from d (MLA latent decode)
    g = hq // hkv
    bk = block_k or autotune.attention_block_sizes(sq, skv, d).block_k
    bk = int(min(bk, skv))
    nk = -(-skv // bk)
    pad = nk * bk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    offset = q_offset if q_offset is not None else (skv - sq)
    qpos = (jnp.arange(sq) + offset).astype(jnp.int32)
    qf = (q.astype(jnp.float32) / np.sqrt(d)).reshape(b, sq, hkv, g, d)
    # [nk, B, bk, Hkv, D].  NB: forcing a sharding constraint on these
    # stacked blocks was tried and REFUTED (EXPERIMENTS.md §Perf, "kvblk"):
    # GSPMD's resharding around the forced layout cost more than the cache
    # gather it avoided; the real decode fix is a shard_map flash-decode
    # with partial-softmax combine (see kernels/decode_attention).
    ks = k.reshape(b, nk, bk, hkv, d).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(b, nk, bk, hkv, dv).transpose(1, 0, 2, 3, 4)

    def body(carry, inputs):
        m, l, o = carry
        kblk, vblk, blk_idx = inputs
        kpos = blk_idx * bk + jnp.arange(bk, dtype=jnp.int32)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qf, kblk.astype(jnp.float32))
        mask = jnp.ones((b, sq, bk), bool)
        if causal:
            mask &= kpos[None, None, :] <= qpos[None, :, None]
        mask &= kpos[None, None, :] < skv  # padding
        if kv_len is not None:
            kl = jnp.asarray(kv_len)
            kl = kl[:, None, None] if kl.ndim else kl
            mask &= kpos[None, None, :] < kl
        s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bqhgk,bkhd->bqhgd", p, vblk.astype(jnp.float32)
        )
        return (m_new, l_new, o_new), None

    m0 = jnp.full((b, sq, hkv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, sq, hkv, g), jnp.float32)
    o0 = jnp.zeros((b, sq, hkv, g, dv), jnp.float32)
    (m, l, o), _ = jax.lax.scan(
        body, (m0, l0, o0), (ks, vs, jnp.arange(nk, dtype=jnp.int32))
    )
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, sq, hq, dv).astype(q.dtype)


def attention(q, k, v, *, causal=True, block_k=None, kv_len=None,
              q_offset=None, use_kernel=False):
    """Dispatch: Pallas kernel on TPU, chunked jnp elsewhere."""
    if use_kernel:
        from repro.kernels.flash_attention import ops as fa_ops

        return fa_ops.flash_attention(
            q, k, v, causal=causal, kv_len=kv_len, q_offset=q_offset
        )
    return chunked_attention(
        q, k, v, causal=causal, block_k=block_k, kv_len=kv_len,
        q_offset=q_offset,
    )


def attend_cache_and_new(q, k, v, kv_len, k_new, v_new):
    """Attention of one query per row over the row's first ``kv_len``
    cache positions and the query's own new K/V.

    q [B, Hq, D]; k, v [B, Smax, Hkv, D] (positions >= kv_len masked);
    kv_len [B]; k_new, v_new [B, Hkv, D].  The scores of the two parts are
    joined before one float32 softmax; no K/V is concatenated, so the
    cache need not hold the new token yet (the in-place decode write).
    """
    b, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qf = q.astype(jnp.float32).reshape(b, hkv, g, d)
    s_old = jnp.einsum("bhgd,bkhd->bhgk", qf,
                       k.astype(jnp.float32)) / np.sqrt(d)
    s_new = jnp.einsum("bhgd,bhd->bhg", qf,
                       k_new.astype(jnp.float32)) / np.sqrt(d)
    live = jnp.arange(k.shape[1])[None, :] < kv_len[:, None]
    s_old = jnp.where(live[:, None, None, :], s_old, NEG_INF)
    p = jax.nn.softmax(jnp.concatenate([s_old, s_new[..., None]], axis=-1),
                       axis=-1)
    o = (jnp.einsum("bhgk,bkhd->bhgd", p[..., :-1], v.astype(jnp.float32))
         + p[..., -1:] * v_new.astype(jnp.float32)[:, :, None, :])
    return o.reshape(b, hq, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Standard GQA attention block (projections + rope + cache)
# ---------------------------------------------------------------------------

def distributed_decode_attention(q, k, v, kv_len, *, mesh, axis="model",
                                 batch_axes=("data",)):
    """Flash-decode split across the mesh's model axis — the split-K
    ParallelFor dual at cluster scale.

    The KV cache arrives SEQUENCE-SHARDED over `axis` (each chip owns
    S/m cache rows); every chip computes a partial (m, l, o) over its rows
    and three tiny collectives (pmax + 2 psum over [B, H(, D)]) combine the
    partial softmaxes — wire cost per step is O(B·H·D), vs gathering the
    whole cache.

    q [B, Hq, D]; k/v [B, S, Hkv, D]; kv_len scalar or [B].
    """
    from jax.sharding import PartitionSpec as P

    b_, hq, d = q.shape
    hkv = k.shape[2]
    dv = v.shape[-1]          # may differ from d (MLA latent decode)
    g = hq // hkv

    def body(q_l, k_l, v_l, kvl):
        idx = jax.lax.axis_index(axis)
        s_loc = k_l.shape[1]
        pos = idx * s_loc + jnp.arange(s_loc)
        qf = (q_l.astype(jnp.float32) / np.sqrt(d)).reshape(
            q_l.shape[0], hkv, g, d)
        s = jnp.einsum("bhgd,bkhd->bhgk", qf, k_l.astype(jnp.float32))
        mask = pos[None, :] < kvl[:, None]
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        m_l = jnp.max(s, axis=-1)                       # [B,Hkv,G]
        m_g = jax.lax.pmax(m_l, axis)
        p = jnp.exp(s - m_g[..., None])
        l_g = jax.lax.psum(jnp.sum(p, -1), axis)        # [B,Hkv,G]
        o_l = jnp.einsum("bhgk,bkhd->bhgd", p, v_l.astype(jnp.float32))
        o_g = jax.lax.psum(o_l, axis)
        out = o_g / jnp.maximum(l_g, 1e-30)[..., None]
        return out.reshape(q_l.shape[0], hq, dv).astype(q_l.dtype)

    ba = tuple(a for a in ("pod", *batch_axes) if a in mesh.shape)
    ba = ba if q.shape[0] % max(
        1, int(np.prod([mesh.shape[a] for a in ba]))) == 0 else ()
    bspec = ba if ba else None
    kvl = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (q.shape[0],))
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, None), P(bspec, axis, None, None),
                  P(bspec, axis, None, None), P(bspec)),
        out_specs=P(bspec, None, None),
        check_vma=False,
    )(q, k, v, kvl)


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    causal: bool = True
    use_rope: bool = True


def attn_init(key, cfg: AttnConfig, dtype=jnp.float32):
    kq, kk, kv, ko = jax.random.split(key, 4)
    hd = cfg.head_dim
    return {
        "wq": layers.dense_init(kq, cfg.d_model, cfg.n_heads * hd,
                                bias=cfg.qkv_bias, dtype=dtype),
        "wk": layers.dense_init(kk, cfg.d_model, cfg.n_kv_heads * hd,
                                bias=cfg.qkv_bias, dtype=dtype),
        "wv": layers.dense_init(kv, cfg.d_model, cfg.n_kv_heads * hd,
                                bias=cfg.qkv_bias, dtype=dtype),
        "wo": layers.dense_init(
            ko, cfg.n_heads * hd, cfg.d_model,
            stddev=1.0 / np.sqrt(cfg.n_heads * hd), dtype=dtype),
    }


def attn_apply(
    p,
    cfg: AttnConfig,
    x: jax.Array,
    *,
    kv: Optional[jax.Array] = None,      # cross-attention source
    cache: Optional[dict] = None,         # {"k","v": [B,Smax,Hkv,D], "len": int32}
    positions: Optional[jax.Array] = None,
    block_k: Optional[int] = None,
    use_kernel: bool = False,
    append_only: bool = False,
):
    """Returns (out [B,S,d], new_cache or None).

    ``append_only``: the cache is read and not written, and ``new_cache``
    holds only the new tokens' leaves as the cache stores them (``"k"``,
    ``"v"`` [B,S,Hkv,D], plus ``"ks"``/``"vs"`` when quantized) for the
    caller to write (``Model.decode_step``'s in-place write of a per-row
    contiguous cache)."""
    b, s, _ = x.shape
    hd, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    src = kv if kv is not None else x
    q = layers.dense(p["wq"], x).reshape(b, s, hq, hd)
    k = layers.dense(p["wk"], src).reshape(b, src.shape[1], hkv, hd)
    v = layers.dense(p["wv"], src).reshape(b, src.shape[1], hkv, hd)

    new_cache = None
    if cache is not None:
        length = cache["len"]
        # Per-row cache lengths ([B] vector instead of scalar) are the
        # continuous-batching serve path: every batch slot sits at its own
        # position after an in-flight refill.  s == 1 is the decode tick;
        # s > 1 is the speculative verify step (Model.verify_step): all s
        # tokens are written at each row's own offset and attention runs
        # per position so every logit is bit-identical to s == 1 decode.
        per_row = getattr(length, "ndim", 0) == 1
        if cfg.use_rope:
            if per_row:
                qpos = length[:, None] + jnp.arange(s)[None, :]
                kpos = length[:, None] + jnp.arange(src.shape[1])[None, :]
            else:
                qpos = length + jnp.arange(s)
                kpos = length + jnp.arange(src.shape[1])
            q = layers.apply_rope(q, jnp.broadcast_to(qpos, (b, s)),
                                  cfg.rope_theta)
            k = layers.apply_rope(k, jnp.broadcast_to(kpos, (b, src.shape[1])),
                                  cfg.rope_theta)
        # The new tokens as the cache stores them: cast to its dtype, or,
        # for a quantized cache ("ks"/"vs" scale leaves present), quantized
        # per (token, head) vector.  Every write below stores these same
        # leaves and reads dequantize before the attention math, so paged,
        # contiguous and in-place decode read back the same values.  (The
        # Pallas paged decode kernel applies the same scales in-kernel,
        # post-matmul — kernels/decode_attention.paged_decode_attention_
        # quantized.)
        if "ks" in cache:
            kq_t, ks_t = quant.quantize(k, dtype=cache["k"].dtype,
                                        scale_dtype=cache["ks"].dtype)
            vq_t, vs_t = quant.quantize(v, dtype=cache["v"].dtype,
                                        scale_dtype=cache["vs"].dtype)
            tok = {"k": kq_t, "ks": ks_t, "v": vq_t, "vs": vs_t}
        else:
            tok = {"k": k.astype(cache["k"].dtype),
                   "v": v.astype(cache["v"].dtype)}
        k_tok, v_tok = _read_kv(tok)

        if append_only:
            new_cache, view = tok, cache
        elif "pt" in cache:
            # Paged decode: k/v are a SHARED page pool [Np+1, ps, Hkv, D]
            # (pool index 0 = reserved scratch), "pt" [B, P] maps each
            # row's logical pages to pool pages.  Write one token into the
            # row's current page, then gather the row's pages back to a
            # contiguous [B, P*ps, Hkv, D] view — identical in shape and
            # live values to the per-row contiguous cache, so the same
            # attention call below is bit-identical to it (masked garbage
            # positions contribute exactly exp(NEG_INF - m) = 0).
            if not per_row:
                raise ValueError("paged KV cache requires per-row lengths "
                                 "(run set_cache_lengths / the serve path)")
            pt = cache["pt"]
            ps, pcount = cache["k"].shape[1], pt.shape[1]
            # [B, S] write coordinates: token j of row b lands at logical
            # position length[b] + j.  Rows whose tables don't cover a
            # position (idle slots, speculative overflow past the page
            # budget) resolve to pool page 0 — the reserved scratch page,
            # whose contents are never read unmasked.
            steps = length[:, None] + jnp.arange(s)[None, :]
            page = jnp.minimum(steps // ps, pcount - 1)
            phys = jnp.take_along_axis(pt, page, axis=1)
            off = steps % ps
            written = {n: cache[n].at[phys, off].set(t)
                       for n, t in tok.items()}
            new_cache = {**written, "pt": pt, "len": length + s}
            view = {n: c[pt].reshape((b, pcount * ps) + c.shape[2:])
                    for n, c in written.items()}
        elif per_row:
            # each row writes its token at its own position
            upd = lambda c, u, l: jax.lax.dynamic_update_slice(c, u, (l, 0, 0))
            view = {n: jax.vmap(upd)(cache[n], t, length)
                    for n, t in tok.items()}
            new_cache = {**view, "len": length + s}
        else:
            view = {n: jax.lax.dynamic_update_slice(cache[n], t,
                                                    (0, length, 0, 0))
                    for n, t in tok.items()}
            new_cache = {**view, "len": length + s}
        k, v = _read_kv(view)
        from repro.distributed.sharding import active_policy
        pol = active_policy()
        # the sequence-sharded flash-decode reads the cache after the write
        if (s == 1 and not append_only and pol is not None
                and pol.decode_seq_shard
                and "model" in pol.mesh.shape
                and k.shape[1] % pol.mesh.shape["model"] == 0):
            out = distributed_decode_attention(
                q[:, 0], k, v, length + s, mesh=pol.mesh)[:, None]
        elif per_row or s == 1:
            # Decode positions.  Query j of row b sits at position
            # length[b] + j and sees the row's first length[b] + j cache
            # positions plus its own K/V — what a one-token decode step at
            # that length sees — so speculative verify (s > 1), paged and
            # contiguous decode, with or without the write in place, give
            # the same bits.  A row already at the end of the cache
            # rewrites its last position (dynamic_update_slice clamps),
            # so it sees the first Smax - 1.  s is static: the loop
            # unrolls under jit.
            lens = jnp.broadcast_to(length, (b,))
            last = k.shape[1] - 1
            out = jnp.stack(
                [attend_cache_and_new(q[:, j], k, v,
                                      jnp.minimum(lens + j, last),
                                      k_tok[:, j], v_tok[:, j])
                 for j in range(s)], axis=1)
        else:
            # causal alignment: query i sits at absolute position length+i,
            # so q_offset is the (dynamic) pre-update cache length.
            out = attention(q, k, v, causal=cfg.causal, block_k=block_k,
                            kv_len=length + s, q_offset=length,
                            use_kernel=use_kernel)
    else:
        if cfg.use_rope:
            pos = positions if positions is not None else jnp.arange(s)[None, :]
            q = layers.apply_rope(q, jnp.broadcast_to(pos, (b, s)), cfg.rope_theta)
            k = layers.apply_rope(
                k, jnp.broadcast_to(pos, (b, src.shape[1])), cfg.rope_theta)
        out = attention(q, k, v, causal=cfg.causal, block_k=block_k,
                        use_kernel=use_kernel)
    out = layers.dense(p["wo"], out.reshape(b, s, hq * hd))
    return out, new_cache


def _read_kv(c):
    """(k, v) of a cache tree or of new-token leaves, dequantized when the
    tree carries "ks"/"vs" scales."""
    if "ks" in c:
        return (quant.dequantize(c["k"], c["ks"]),
                quant.dequantize(c["v"], c["vs"]))
    return c["k"], c["v"]


def init_kv_cache(cfg: AttnConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """KV cache tree.  A quantized ``dtype`` (int8 / fp8) adds per-token
    scale leaves "ks"/"vs" [B, Smax, Hkv, 1] — the token axis rides the
    same position as k/v, so the generic cache walkers (paging, splice,
    prefix gather) handle them with no special cases."""
    c = {
        "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype),
        "len": jnp.zeros((), jnp.int32),
    }
    if quant.is_quant_dtype(dtype):
        c["ks"] = jnp.zeros((batch, max_len, cfg.n_kv_heads, 1),
                            quant.SCALE_DTYPE)
        c["vs"] = jnp.zeros((batch, max_len, cfg.n_kv_heads, 1),
                            quant.SCALE_DTYPE)
    return c
