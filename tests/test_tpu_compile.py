"""Compiles for a described TPU v5e chip: the served model at published
widths and the Pallas kernels at its attention shapes.

Nothing runs: each program is lowered and compiled by the TPU compiler
for a chip that is described, not attached, so a program the chip would
refuse (too much memory, a kernel Mosaic cannot lower) fails here at no
chip time.  The topology is described inside a module fixture, never at
import, so every test worker collects the same tests and only the worker
given this file loads the TPU library.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.ops import (decode_attention,
                                                paged_decode_attention)
from repro.kernels.flash_attention.ops import flash_attention
from repro.models import Model
from repro.serve.engine import Engine, ServeConfig

ARCH = "qwen2.5-3b"
SLOTS, PROMPT, MAX_LEN = 4, 512, 2048
HQ, HKV, HD, PAGE = 16, 2, 128, 16
HBM_BYTES = 16 * 2**30          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    # a compile for an unattached chip is written to the persistent cache
    # but cannot be read back, so the cache stays off around these tests
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def model():
    return Model(get_config(ARCH).with_dtype("bfloat16"))


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _params(model, one_chip):
    return _on(one_chip, jax.eval_shape(model.init, jax.random.PRNGKey(0)))


def _memory(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes, ma.output_size_in_bytes,
            ma.temp_size_in_bytes)


def test_init_compiles_in_bf16_without_f32_copies(model, one_chip):
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = jax.jit(model.init).lower(key).compile()
    _, out, temp = _memory(compiled)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(shapes)
    assert {a.dtype for a in leaves} == {jnp.dtype(jnp.bfloat16)}
    weights = sum(a.size * 2 for a in leaves)
    assert weights <= out < weights * 1.001   # plus tile padding
    # a float32 copy of the largest stacked weight would need twice its
    # bf16 bytes of scratch; the init must make each weight in place
    assert temp < max(a.size for a in leaves) * 4


def test_prefill_padded_compiles(model, one_chip):
    tokens = jax.ShapeDtypeStruct((SLOTS, PROMPT), jnp.int32,
                                  sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    fn = jax.jit(lambda p, t, n: model.prefill_padded(
        p, {"tokens": t, "lengths": n}, MAX_LEN, jnp.bfloat16))
    compiled = fn.lower(_params(model, one_chip), tokens, lengths).compile()
    args, out, temp = _memory(compiled)
    assert args + out + temp < HBM_BYTES


def test_decode_step_fits_one_chip(model, one_chip):
    cache = jax.eval_shape(lambda: model.set_cache_lengths(
        model.init_cache(SLOTS, MAX_LEN, jnp.bfloat16),
        jnp.zeros(SLOTS, jnp.int32)))
    tokens = jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32, sharding=one_chip)
    compiled = jax.jit(model.decode_step).lower(
        _params(model, one_chip), tokens, _on(one_chip, cache)).compile()
    args, out, temp = _memory(compiled)
    assert args + out + temp < HBM_BYTES


def test_served_decode_step_writes_its_cache_in_place(model, one_chip):
    """The engine's decode program at the benchmark cell's shapes (32
    slots, max_len 2048, bf16): the cache is donated and written in
    place — its buffer aliased to the output, no scratch the size of one
    layer's K, and no loop but the layer scan (no per-row write loops)."""
    slots = 32
    eng = Engine(model, None, ServeConfig(max_len=MAX_LEN, slots=slots,
                                          cache_dtype="bfloat16"))
    cache = jax.eval_shape(lambda: model.set_cache_lengths(
        model.init_cache(slots, MAX_LEN, jnp.bfloat16),
        jnp.zeros(slots, jnp.int32)))
    assert model.writes_in_place(cache)
    tokens = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    compiled = eng._decode_in_place.lower(
        _params(model, one_chip), tokens, _on(one_chip, cache)).compile()
    ma = compiled.memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    layer_k = slots * MAX_LEN * HKV * HD * 2
    assert ma.alias_size_in_bytes >= cache_bytes
    assert ma.temp_size_in_bytes < layer_k
    assert len(re.findall(r"\swhile\(", compiled.as_text())) == 1


def _assert_kernel(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("num_buffers", [1, 2])
def test_decode_attention_kernel_compiles(one_chip, num_buffers):
    bf16 = jnp.bfloat16
    q = jax.ShapeDtypeStruct((SLOTS, HQ, HD), bf16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((SLOTS, MAX_LEN, HKV, HD), bf16,
                              sharding=one_chip)
    kv_len = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    _assert_kernel(lambda q, k, v, n: decode_attention(
        q, k, v, n, num_buffers=num_buffers, interpret=False),
        q, kv, kv, kv_len)


def test_paged_decode_attention_kernel_compiles(one_chip):
    bf16 = jnp.bfloat16
    pages = MAX_LEN // PAGE
    q = jax.ShapeDtypeStruct((SLOTS, HQ, HD), bf16, sharding=one_chip)
    pool = jax.ShapeDtypeStruct((SLOTS * pages + 1, PAGE, HKV, HD), bf16,
                                sharding=one_chip)
    table = jax.ShapeDtypeStruct((SLOTS, pages), jnp.int32,
                                 sharding=one_chip)
    kv_len = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    _assert_kernel(lambda q, k, v, t, n: paged_decode_attention(
        q, k, v, t, n, interpret=False), q, pool, pool, table, kv_len)


def test_flash_attention_forward_kernel_compiles(one_chip):
    bf16 = jnp.bfloat16
    q = jax.ShapeDtypeStruct((1, PROMPT, HQ, HD), bf16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, PROMPT, HKV, HD), bf16, sharding=one_chip)
    _assert_kernel(lambda q, k, v: flash_attention(q, k, v, interpret=False),
                   q, kv, kv)
