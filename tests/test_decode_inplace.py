"""The decode step's in-place cache write.

On the per-row contiguous cache the layers only read the cache and emit
the new token's K/V, and one scatter per leaf writes it
(``Model.decode_step``, ``Model.writes_in_place``); ``Engine`` donates
that cache to the step.  These tests hold the step to the formulation it
replaced — write the token into the whole cache, then attend over it —
and check which engine steps donate.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import Model, layers
from repro.models import attention as attn_mod
from repro.serve.engine import Engine, ServeConfig
from repro.serve.paged_cache import make_cache_backend
from repro.serve.queue import Request

MAX_LEN, TICKS = 64, 8
# rows at position 0, mid-cache, one that reaches max_len - 1 on the last
# tick, and an idle one that runs past the end (its writes clamp)
START = np.array([0, 21, MAX_LEN - TICKS, MAX_LEN - 3], np.int32)


def _full_write_attn(p, cfg, x, *, cache, block_k=None, append_only=False):
    """The replaced decode attention: write the token into the whole cache
    at each row's position, then attend over the cache with
    ``naive_attention``."""
    assert not append_only
    b = x.shape[0]
    hd, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    length = cache["len"]
    pos = length[:, None]
    q = layers.apply_rope(layers.dense(p["wq"], x).reshape(b, 1, hq, hd),
                          pos, cfg.rope_theta)
    k = layers.apply_rope(layers.dense(p["wk"], x).reshape(b, 1, hkv, hd),
                          pos, cfg.rope_theta)
    v = layers.dense(p["wv"], x).reshape(b, 1, hkv, hd)
    upd = jax.vmap(lambda c, u, l: jax.lax.dynamic_update_slice(
        c, u, (l, 0, 0)))
    ck = upd(cache["k"], k.astype(cache["k"].dtype), length)
    cv = upd(cache["v"], v.astype(cache["v"].dtype), length)
    out = attn_mod.naive_attention(q, ck, cv, causal=False,
                                   kv_len=length + 1)
    out = layers.dense(p["wo"], out.reshape(b, 1, hq * hd))
    return out, {"k": ck, "v": cv, "len": length + 1}


def _layers_own_write(model, params, tokens, cache):
    """``decode_step`` with the layers writing the cache themselves."""
    x = layers.embed(params["embed"], tokens).astype(model.cfg.dtype)
    x, cache, _ = model._backbone(params, x, {"tokens": tokens}, cache)
    x = layers.rmsnorm(params["ln_f"], x, model.cfg.norm_eps)
    return model._logits(params, x)[:, 0].astype(jnp.float32), cache


def _filled_cache(model, kv_dtype, seed=0):
    """A per-row cache at START whose every position holds a value, so a
    stale position that leaked into attention would show."""
    cache = model.set_cache_lengths(
        model.init_cache(len(START), MAX_LEN, kv_dtype), START)
    rng = np.random.default_rng(seed)

    def fill(path, a):
        if path[-1].key == "len":
            return a
        return jnp.asarray(rng.standard_normal(a.shape), jnp.float32).astype(
            a.dtype)

    return jax.tree_util.tree_map_with_path(fill, cache)


def _copy(tree):
    return jax.tree.map(lambda a: jnp.array(a, copy=True), tree)


def _run(step, params, toks, cache):
    out = []
    for t in range(TICKS):
        logits, cache = step(params, jnp.asarray(toks[t]), cache)
        out.append(np.asarray(logits))
    return np.stack(out), cache


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_in_place_step_matches_whole_cache_write(dtype, monkeypatch):
    cfg = get_config("qwen2.5-3b").reduced().with_dtype(dtype)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache = _filled_cache(model, jnp.dtype(dtype))
    assert model.writes_in_place(cache)
    ref_cache = _copy(cache)
    toks = np.random.default_rng(1).integers(
        1, cfg.vocab_size, (TICKS, len(START), 1)).astype(np.int32)

    got, got_cache = _run(jax.jit(model.decode_step, donate_argnums=2),
                          params, toks, cache)
    monkeypatch.setattr(attn_mod, "attn_apply", _full_write_attn)
    want, want_cache = _run(
        jax.jit(lambda p, t, c: _layers_own_write(model, p, t, c)),
        params, toks, ref_cache)

    np.testing.assert_array_equal(np.asarray(got_cache["len"]),
                                  np.broadcast_to(START + TICKS,
                                                  (cfg.n_layers, len(START))))
    if dtype == "bfloat16":
        np.testing.assert_array_equal(got, want)
        for name in got_cache:
            np.testing.assert_array_equal(np.asarray(got_cache[name]),
                                          np.asarray(want_cache[name]),
                                          err_msg=name)
    else:
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


def _moe_model():
    cfg = dataclasses.replace(
        get_config("deepseek-v2-lite-16b").reduced().with_dtype("bfloat16"),
        use_mla=False, capacity_factor=8.0)
    return Model(cfg)


@pytest.mark.parametrize("case", ["moe", "int8-kv"])
def test_in_place_step_equals_the_layers_own_write(case):
    """MoE (two layer stacks) and a quantized cache (scale leaves) take the
    same scatter, and give the bits the layers' own write gives."""
    if case == "moe":
        model, kv_dtype = _moe_model(), jnp.bfloat16
    else:
        model = Model(get_config("qwen2.5-3b").reduced().with_dtype(
            "bfloat16"))
        kv_dtype = jnp.int8
    params = model.init(jax.random.PRNGKey(0))
    cache = model.set_cache_lengths(
        model.init_cache(len(START), MAX_LEN, kv_dtype), START)
    assert model.writes_in_place(cache)
    ref_cache = _copy(cache)
    toks = np.random.default_rng(2).integers(
        1, model.cfg.vocab_size, (TICKS, len(START), 1)).astype(np.int32)

    got, got_cache = _run(jax.jit(model.decode_step, donate_argnums=2),
                          params, toks, cache)
    want, want_cache = _run(
        jax.jit(lambda p, t, c: _layers_own_write(model, p, t, c)),
        params, toks, ref_cache)
    np.testing.assert_array_equal(got, want)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), got_cache, want_cache)


def test_contiguous_step_donates_its_cache_and_paged_keeps_it():
    model = Model(get_config("qwen2.5-3b").reduced())
    params = model.init(jax.random.PRNGKey(0))
    prompt = np.arange(1, 6, dtype=np.int32)
    for kind, donated in (("contiguous", True), ("paged", False)):
        eng = Engine(model, params, ServeConfig(
            max_len=32, slots=2, cache=kind, page_size=8))
        backend = make_cache_backend(eng)
        backend.admit(0, Request(rid=0, prompt=prompt), 4)
        before = backend.cache
        assert model.writes_in_place(before) is donated
        _, backend.cache = eng._decode(params, jnp.ones((2, 1), jnp.int32),
                                       before)
        gone = [a.is_deleted() for a in jax.tree.leaves(before)]
        assert gone == [donated] * len(gone), kind
