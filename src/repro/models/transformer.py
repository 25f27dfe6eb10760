"""Family assemblies: blocks + scan-over-layers for all 10 assigned archs.

Layers are stacked (leading L axis) and iterated with ``jax.lax.scan`` so the
lowered HLO stays one-block-sized regardless of depth — this is what keeps
512-device dry-run compiles tractable for 60-80-layer models.  Training scans
wrap the block in ``jax.checkpoint`` (remat) so activation memory is one
layer's worth of live values plus one carry per layer.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models import attention as attn_mod
from repro.models import layers, mla, moe, ssm


# ---------------------------------------------------------------------------
# config adapters
# ---------------------------------------------------------------------------

def attn_cfg(cfg: ModelConfig, *, causal=True, use_rope=True,
             n_heads=None, n_kv=None) -> attn_mod.AttnConfig:
    return attn_mod.AttnConfig(
        d_model=cfg.d_model,
        n_heads=n_heads or cfg.n_heads,
        n_kv_heads=n_kv or cfg.n_kv_heads,
        head_dim=cfg.resolved_head_dim,
        qkv_bias=cfg.qkv_bias,
        rope_theta=cfg.rope_theta,
        causal=causal,
        use_rope=use_rope,
    )


def mla_cfg(cfg: ModelConfig) -> mla.MLAConfig:
    return mla.MLAConfig(
        d_model=cfg.d_model, n_heads=cfg.n_heads,
        kv_lora_rank=cfg.kv_lora_rank, q_lora_rank=cfg.q_lora_rank,
        qk_nope_dim=cfg.qk_nope_dim, qk_rope_dim=cfg.qk_rope_dim,
        v_head_dim=cfg.v_head_dim, rope_theta=cfg.rope_theta,
    )


def ssm_cfg(cfg: ModelConfig) -> ssm.SSMConfig:
    return ssm.SSMConfig(
        d_model=cfg.d_model, d_state=cfg.ssm_state, d_conv=cfg.ssm_conv,
        expand=cfg.ssm_expand, headdim=cfg.ssm_headdim,
        n_groups=cfg.ssm_ngroups,
    )


def moe_cfg(cfg: ModelConfig) -> moe.MoEConfig:
    return moe.MoEConfig(
        d_model=cfg.d_model, n_experts=cfg.n_experts, top_k=cfg.top_k,
        d_ff=cfg.moe_d_ff, n_shared_experts=cfg.n_shared_experts,
        capacity_factor=cfg.capacity_factor,
        dispatch_groups=cfg.moe_dispatch_groups,
    )


# ---------------------------------------------------------------------------
# blocks — each returns (x, new_cache, aux)
# ---------------------------------------------------------------------------

def dense_block_init(key, cfg: ModelConfig, *, d_ff=None, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    ac = attn_cfg(cfg)
    return {
        "ln1": layers.rmsnorm_init(cfg.d_model, dtype),
        "attn": (mla.mla_init(k1, mla_cfg(cfg), dtype) if cfg.use_mla
                 else attn_mod.attn_init(k1, ac, dtype)),
        "ln2": layers.rmsnorm_init(cfg.d_model, dtype),
        "mlp": layers.mlp_init(k2, cfg.d_model, d_ff or cfg.d_ff,
                               act=cfg.act, dtype=dtype),
    }


def dense_block_apply(p, cfg: ModelConfig, x, *, cache=None, block_k=None,
                      append_only=False):
    """``append_only``: see ``attention.attn_apply`` (non-MLA only)."""
    block_k = block_k or (cfg.attn_block_k or None)
    h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.use_mla:
        a, new_cache = mla.mla_apply(p["attn"], mla_cfg(cfg), h, cache=cache,
                                     block_k=block_k)
    else:
        a, new_cache = attn_mod.attn_apply(p["attn"], attn_cfg(cfg), h,
                                           cache=cache, block_k=block_k,
                                           append_only=append_only)
    x = x + a
    h = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
    x = x + layers.mlp(p["mlp"], h, act=cfg.act)
    x = constrain(x, "act_btd")
    return x, new_cache, jnp.zeros((), jnp.float32)


def moe_block_init(key, cfg: ModelConfig, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": layers.rmsnorm_init(cfg.d_model, dtype),
        "attn": (mla.mla_init(k1, mla_cfg(cfg), dtype) if cfg.use_mla
                 else attn_mod.attn_init(k1, attn_cfg(cfg), dtype)),
        "ln2": layers.rmsnorm_init(cfg.d_model, dtype),
        "moe": moe.moe_init(k2, moe_cfg(cfg), dtype),
    }


def moe_block_apply(p, cfg: ModelConfig, x, *, cache=None, block_k=None,
                    append_only=False):
    block_k = block_k or (cfg.attn_block_k or None)
    h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.use_mla:
        a, new_cache = mla.mla_apply(p["attn"], mla_cfg(cfg), h, cache=cache,
                                     block_k=block_k)
    else:
        a, new_cache = attn_mod.attn_apply(p["attn"], attn_cfg(cfg), h,
                                           cache=cache, block_k=block_k,
                                           append_only=append_only)
    x = x + a
    h = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if cfg.moe_impl == "sharded":
        from repro.models.moe_sharded import moe_apply_sharded
        y, metrics = moe_apply_sharded(p["moe"], moe_cfg(cfg), h)
    else:
        y, metrics = moe.moe_apply(p["moe"], moe_cfg(cfg), h)
    x = x + y
    x = constrain(x, "act_btd")
    return x, new_cache, metrics["aux_loss"]


def ssm_block_init(key, cfg: ModelConfig, dtype=jnp.float32):
    return {
        "ln": layers.rmsnorm_init(cfg.d_model, dtype),
        "ssm": ssm.ssm_init(key, ssm_cfg(cfg), dtype),
    }


def ssm_block_apply(p, cfg: ModelConfig, x, *, cache=None, chunk=None):
    h = layers.rmsnorm(p["ln"], x, cfg.norm_eps)
    y, new_cache = ssm.ssm_apply(p["ssm"], ssm_cfg(cfg), h, cache=cache,
                                 chunk=chunk)
    x = x + y
    x = constrain(x, "act_btd")
    return x, new_cache, jnp.zeros((), jnp.float32)


def cross_block_init(key, cfg: ModelConfig, *, gated=False,
                     dtype=jnp.float32):
    """Cross-attention block (seamless decoder / llama-vision)."""
    k1, k2 = jax.random.split(key)
    ac = attn_cfg(cfg, causal=False, use_rope=False)
    p = {
        "ln1": layers.rmsnorm_init(cfg.d_model, dtype),
        "xattn": attn_mod.attn_init(k1, ac, dtype),
        "ln2": layers.rmsnorm_init(cfg.d_model, dtype),
        "mlp": layers.mlp_init(k2, cfg.d_model, cfg.d_ff, act=cfg.act,
                               dtype=dtype),
    }
    if gated:
        p["gate_attn"] = jnp.zeros((), dtype)
        p["gate_mlp"] = jnp.zeros((), dtype)
    return p


def cross_block_apply(p, cfg: ModelConfig, x, enc, *, cache=None):
    """enc: encoder/vision output [B, S_enc, d], or None during decode (the
    cross K/V are decode-invariant and come from the cache written at
    prefill)."""
    ac = attn_cfg(cfg, causal=False, use_rope=False)
    h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
    b, s, _ = h.shape
    hd, hq, hkv = ac.head_dim, ac.n_heads, ac.n_kv_heads
    if enc is None:
        ck, cv = cache["ck"], cache["cv"]
    else:
        ck = layers.dense(p["xattn"]["wk"], enc).reshape(
            b, enc.shape[1], hkv, hd)
        cv = layers.dense(p["xattn"]["wv"], enc).reshape(
            b, enc.shape[1], hkv, hd)
        if cache is not None:
            ck = ck.astype(cache["ck"].dtype)
            cv = cv.astype(cache["cv"].dtype)
    q = layers.dense(p["xattn"]["wq"], h).reshape(b, s, hq, hd)
    o = attn_mod.chunked_attention(q, ck, cv, causal=False)
    a = layers.dense(p["xattn"]["wo"], o.reshape(b, s, hq * hd))
    if "gate_attn" in p:
        a = jnp.tanh(p["gate_attn"].astype(a.dtype)) * a
    x = x + a
    h = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
    m = layers.mlp(p["mlp"], h, act=cfg.act)
    if "gate_mlp" in p:
        m = jnp.tanh(p["gate_mlp"].astype(m.dtype)) * m
    x = x + m
    new_cache = {"ck": ck, "cv": cv} if cache is not None else None
    return x, new_cache, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# stacking + scan machinery
# ---------------------------------------------------------------------------

def stacked_init(init_one: Callable, key, n: int):
    """vmap a per-layer init over n keys -> params with leading [n] axis."""
    keys = jax.random.split(key, n)
    return jax.vmap(init_one)(keys)


_REMAT_POLICIES = {
    "full": lambda: jax.checkpoint_policies.nothing_saveable,
    # keep matmul outputs: trades activation memory for ~25% less recompute
    "dots": lambda: jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
}


def scan_layers(
    block_apply: Callable,   # (params_i, x, cache_i) -> (x, cache_i, aux)
    stacked_params: Any,
    x: jax.Array,
    caches: Any = None,      # pytree with leading [n] axis, or None
    *,
    remat: bool = False,
    remat_policy: str = "full",
    unroll: int = 1,
):
    """Returns (x, new_caches, aux_sum)."""

    def body(carry, inp):
        xc, aux = carry
        p_i, c_i = inp
        y, new_c, a = block_apply(p_i, xc, c_i)
        return (y, aux + a), new_c

    fn = body
    if remat and remat_policy != "none":
        fn = jax.checkpoint(body, policy=_REMAT_POLICIES[remat_policy]())
    (x, aux), new_caches = jax.lax.scan(
        fn, (x, jnp.zeros((), jnp.float32)), (stacked_params, caches),
        unroll=unroll)
    return x, new_caches, aux
