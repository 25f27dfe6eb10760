"""A configuration file (``bench/configs/<name>.json``, Hugging Face key
names) turned into the program's ``ModelConfig``, and weights made by the
benchmark itself from the seed.

The weights are the benchmark's, not the program's: one jitted call makes
every leaf on the device in the served dtype, in the program's parameter
layout (checked against ``jax.eval_shape(Model.init)``), so the program
and the reference read the same numbers and neither made them.  Biases
and norm scales are drawn too, so those paths are compared.
"""

from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import Shapes

HERE = Path(__file__).resolve().parent


def load_config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a dense decoder configuration."""
    from repro.configs.base import ModelConfig

    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {cfg['hidden_act']!r}: the program's "
                         f"dense block is a SwiGLU")
    sh = Shapes.of(cfg)
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=sh.layers, d_model=sh.d,
        n_heads=sh.heads, n_kv_heads=sh.kv_heads, d_ff=sh.ff,
        vocab_size=sh.vocab, head_dim=sh.head_dim, qkv_bias=sh.qkv_bias,
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=sh.tied, param_dtype=cfg["torch_dtype"])


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, 32 bits or more."""
    key = jax.random.PRNGKey(seed % (1 << 31))
    return jax.random.fold_in(key, seed >> 31)


def init_fn(cfg: dict):
    """``key -> params`` for one jitted call.  Layers are drawn one at a
    time (``lax.map``), so no transient of the whole stack is live."""
    sh = Shapes.of(cfg)
    dt = jnp.dtype(cfg["torch_dtype"])
    q, kv = sh.heads * sh.head_dim, sh.kv_heads * sh.head_dim

    def normal(k, shape, std):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dt)

    def scale(k, n):
        return (1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)).astype(dt)

    def dense(k, d_in, d_out, bias=False):
        kw, kb = jax.random.split(k)
        p = {"w": normal(kw, (d_in, d_out), 1.0 / np.sqrt(d_in))}
        if bias:
            p["b"] = normal(kb, (d_out,), 0.1)
        return p

    def layer(k):
        ks = jax.random.split(k, 9)
        return {
            "ln1": {"scale": scale(ks[0], sh.d)},
            "attn": {"wq": dense(ks[1], sh.d, q, sh.qkv_bias),
                     "wk": dense(ks[2], sh.d, kv, sh.qkv_bias),
                     "wv": dense(ks[3], sh.d, kv, sh.qkv_bias),
                     "wo": dense(ks[4], q, sh.d)},
            "ln2": {"scale": scale(ks[5], sh.d)},
            "mlp": {"up": dense(ks[6], sh.d, sh.ff),
                    "down": dense(ks[7], sh.ff, sh.d),
                    "gate": dense(ks[8], sh.d, sh.ff)},
        }

    def init(key):
        ke, kf, kh, kb = jax.random.split(key, 4)
        p = {"embed": {"table": normal(ke, (sh.vocab, sh.d), 0.02)},
             "ln_f": {"scale": scale(kf, sh.d)},
             "blocks": jax.lax.map(layer, jax.random.split(kb, sh.layers))}
        if not sh.tied:
            p["head"] = {"w": normal(kh, (sh.d, sh.vocab), 0.02)}
        return p

    return init


def check_layout(cfg: dict, model) -> None:
    """The benchmark's tree must be the program's parameter layout."""
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(init_fn(cfg), jax.random.PRNGKey(0))
    sig = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)
    if jax.tree.structure(want) != jax.tree.structure(got) or \
            sig(want) != sig(got):
        raise ValueError(f"parameter layout differs from the program's:\n"
                         f"program {sig(want)}\nbenchmark {sig(got)}")


def make_params(cfg: dict, seed: int):
    params = jax.jit(init_fn(cfg))(seed_key(seed))
    return jax.block_until_ready(params)
