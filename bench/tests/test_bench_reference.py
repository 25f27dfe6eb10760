"""The dense family's float32 reference against the program's own
prefill-then-decode logits, on both cache backends, at a tiny size in
float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import modeldef
from bench.tests.tiny import config

DENSE = modeldef.family(config())
Reference = DENSE.Reference


def engine_logits(cfg, params, cache, prompts, steps):
    """Each prompt admitted into its own slot, then ``steps`` decode
    steps teacher-forced with fixed tokens: the logits the program gives
    at every position, per request."""
    from repro.models import Model
    from repro.serve.engine import Engine, ServeConfig
    from repro.serve.paged_cache import make_cache_backend
    from repro.serve.queue import Request

    model = Model(DENSE.model_config(cfg))
    eng = Engine(model, params, ServeConfig(
        max_len=128, slots=len(prompts), cache=cache, page_size=16,
        cache_dtype="float32"))
    backend = make_cache_backend(eng)
    rng = np.random.default_rng(0)
    forced = rng.integers(1, cfg["vocab_size"], (len(prompts), steps))
    out = [[] for _ in prompts]
    for s, p in enumerate(prompts):
        res = backend.admit(s, Request(rid=s, prompt=p), steps + 1)
        out[s].append(np.asarray(res.logits_row))
    for j in range(steps):
        tok = jnp.asarray(forced[:, j:j + 1], jnp.int32)
        logits, backend.cache = eng._decode(params, tok, backend.cache)
        for s in range(len(prompts)):
            out[s].append(np.asarray(logits[s]))
    return [np.stack(o) for o in out], forced


@pytest.mark.parametrize("cache", ["contiguous", "paged"])
def test_reference_matches_prefill_then_decode(cache):
    cfg = config(torch_dtype="float32")
    params = modeldef.make_params(cfg, 11)
    rng = np.random.default_rng(1)
    first = rng.integers(1, cfg["vocab_size"], 37).astype(np.int32)
    # the second prompt shares two pages with the first: on the paged
    # backend it is a prefix hit, served by the continuation prefill
    second = np.concatenate([first[:32], rng.integers(
        1, cfg["vocab_size"], 9)]).astype(np.int32)
    third = rng.integers(1, cfg["vocab_size"], 5).astype(np.int32)
    prompts = [first, second, third]
    got, forced = engine_logits(cfg, params, cache, prompts, steps=6)
    ref = Reference(cfg)
    for s, p in enumerate(prompts):
        seq = np.concatenate([p, forced[s]])
        pos = np.arange(len(p) - 1, len(seq))
        want = ref.logits(params, seq, pos)
        np.testing.assert_allclose(got[s], want, atol=2e-4, rtol=1e-4)


def test_granite_multipliers_are_read_when_present():
    cfg = config(torch_dtype="float32")
    params = modeldef.make_params(cfg, 3)
    toks = np.arange(1, 20, dtype=np.int32)
    pos = np.arange(len(toks))
    base = Reference(cfg).logits(params, toks, pos)
    assert np.allclose(
        Reference(config(torch_dtype="float32", logits_scaling=8.0)).logits(
            params, toks, pos), base / 8.0, rtol=1e-5, atol=1e-6)
    for key, val in [("embedding_multiplier", 12.0),
                     ("attention_multiplier", 0.015625),
                     ("residual_multiplier", 0.22)]:
        other = Reference(config(torch_dtype="float32", **{key: val}))
        assert not np.allclose(other.logits(params, toks, pos), base), key


def test_layout_is_the_programs():
    from repro.models import Model

    cfg = config()
    model = Model(DENSE.model_config(cfg))
    modeldef.check_layout(DENSE.init_fn(cfg), model)
    bad = config()
    bad["tie_word_embeddings"] = False
    with pytest.raises(ValueError):
        modeldef.check_layout(DENSE.init_fn(bad), model)


def test_weights_repeat_per_seed_and_differ_across_seeds():
    cfg = config()
    a = modeldef.make_params(cfg, 2**31 + 5)
    b = modeldef.make_params(cfg, 2**31 + 5)
    c = modeldef.make_params(cfg, 5)
    same = jax.tree.map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree.leaves(same))
    assert not bool((a["embed"]["table"] == c["embed"]["table"]).all())
    assert {str(x.dtype) for x in jax.tree.leaves(a)} == {"bfloat16"}
