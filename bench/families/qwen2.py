"""The dense decoder (``model_type`` ``qwen2``): Qwen2's published block,
with Granite's scalar multipliers read where a configuration has them.
The contract it keeps is in ``bench/families/__init__.py``.

- ``model_config`` and ``init_fn``: the program's ``family="dense"``
  ``ModelConfig``, and weights in its parameter layout.
- ``Reference``: a dense decoder in float32, in ``jax.numpy``.
- ``Shapes``: operations and bytes of the dense decoder.

The reference follows the published Qwen2 / Granite decoder: RMSNorm,
grouped-query attention with rotary positions (half-split rotation) and
optional QKV bias, a SwiGLU MLP, and tied or untied read-out.  Granite's
four scalar multipliers are read from the configuration under their
Hugging Face names (``embedding_multiplier``, ``attention_multiplier``,
``residual_multiplier``, ``logits_scaling``); where the configuration
lacks one it is the identity.  It imports nothing of the program: it
reads the benchmark's configuration and the benchmark's weights.

Every product runs at ``Precision.HIGHEST``.  Weights stay in their
served dtype and one layer at a time is raised to float32 inside the
scan over layers, so a float32 copy of the model never exists.
Attention runs in blocks of query rows.

``quant="int8"`` (or ``"fp8"``) is the control: the same forward with
every weight product taken in that 8-bit type (weights scaled per output
channel, activations per row) and keys and values stored in it (per
token and head); round to nearest.  That is the precision below the
bfloat16 that the configurations state.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import DTYPE_BYTES
from bench.reference import HI, LOW

Q_BLOCK = 256      # query rows per attention block
PAD = 512          # sequences are padded to a multiple of this
LOGIT_ROWS = 128   # read-out rows per product


# ------------------------------------------------------------------ counts
@dataclasses.dataclass(frozen=True)
class Shapes:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    tied: bool
    qkv_bias: bool
    dtype_bytes: int

    @classmethod
    def of(cls, cfg: dict) -> "Shapes":
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        return cls(
            layers=cfg["num_hidden_layers"], d=d, heads=h,
            kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim") or d // h,
            ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
            tied=bool(cfg.get("tie_word_embeddings", False)),
            qkv_bias=bool(cfg.get("attention_bias", False)),
            dtype_bytes=DTYPE_BYTES[cfg["torch_dtype"]])

    # ---- parameters
    @property
    def layer_matmul_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return self.d * (q + 2 * kv) + q * self.d + 3 * self.d * self.ff

    @property
    def layer_params(self) -> int:
        bias = (self.heads + 2 * self.kv_heads) * self.head_dim
        return (self.layer_matmul_params + (bias if self.qkv_bias else 0)
                + 2 * self.d)

    @property
    def params(self) -> int:
        emb = self.vocab * self.d * (1 if self.tied else 2)
        return self.layers * self.layer_params + emb + self.d

    @property
    def kv_bytes_per_token(self) -> int:
        return (self.layers * 2 * self.kv_heads * self.head_dim
                * self.dtype_bytes)

    # ---- operations
    def attn_flops(self, ctx: int) -> int:
        """One query position attending to ``ctx`` positions, all layers:
        scores and the weighted sum of values."""
        return 4 * ctx * self.heads * self.head_dim * self.layers

    @property
    def logits_flops(self) -> int:
        return 2 * self.d * self.vocab

    def token_flops(self, ctx: int) -> int:
        """One token through every layer, attending to ``ctx`` positions
        (itself included); no logits."""
        return 2 * self.layers * self.layer_matmul_params + self.attn_flops(ctx)

    def prefill_flops(self, n: int, start: int = 0) -> int:
        """``n`` prompt tokens at positions ``start .. start + n - 1`` and
        the logits of the last one."""
        return (sum(self.token_flops(start + i + 1) for i in range(n))
                + self.logits_flops)

    def decode_flops(self, contexts: Iterable[int]) -> int:
        """One decode step: one token per live row, each attending to its
        context (the new token included), with its logits."""
        return sum(self.token_flops(c) + self.logits_flops for c in contexts)

    # ---- bytes
    @property
    def weight_bytes(self) -> int:
        """Every weight a decode step reads once.  With tied embeddings the
        table is read whole for the logits (and a few rows for the
        lookup, not counted); untied, the head is read whole and the
        table is not."""
        table = 0 if self.tied else self.vocab * self.d
        return (self.params - table) * self.dtype_bytes

    def decode_bytes(self, contexts: Iterable[int]) -> int:
        """Weights once, the KV of each live row's context, and the new
        KV written."""
        contexts = list(contexts)
        return (self.weight_bytes
                + (sum(contexts) + len(contexts)) * self.kv_bytes_per_token)


# ---------------------------------------------------------- program side
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


# --------------------------------------------------------------- reference
class Reference:
    def __init__(self, cfg: dict):
        self.sh = Shapes.of(cfg)
        self.eps = float(cfg["rms_norm_eps"])
        self.theta = float(cfg["rope_theta"])
        self.emb_mult = float(cfg.get("embedding_multiplier", 1.0))
        self.res_mult = float(cfg.get("residual_multiplier", 1.0))
        self.logit_div = float(cfg.get("logits_scaling", 1.0))
        self.attn_scale = float(cfg.get("attention_multiplier",
                                        1.0 / np.sqrt(self.sh.head_dim)))

    # ------------------------------------------------------------ pieces
    def _mm(self, x, w, quant):
        if quant:
            x, w = LOW[quant](x, -1), LOW[quant](w, 0)
        return jnp.dot(x, w, precision=HI)

    def _dense(self, p, x, quant):
        y = self._mm(x, p["w"].astype(jnp.float32), quant)
        if "b" in p:
            y = y + p["b"].astype(jnp.float32)
        return y

    def _rms(self, x, scale):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + self.eps) * scale.astype(jnp.float32)

    def _rope(self, x, pos):
        hd = x.shape[-1]
        inv = 1.0 / (self.theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32)
                                    / hd))
        ang = pos[:, None].astype(jnp.float32) * inv[None, :]
        cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
        sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
        x1, x2 = jnp.split(x, 2, axis=-1)
        return x * cos + jnp.concatenate([-x2, x1], -1) * sin

    def _attention(self, q, k, v, quant):
        """Causal GQA attention, query rows in blocks.  q [S, Hq, D],
        k/v [S, Hkv, D]."""
        s, hq, d = q.shape
        hkv = k.shape[1]
        g = hq // hkv
        if quant:
            k, v = LOW[quant](k, -1), LOW[quant](v, -1)
        nb = s // Q_BLOCK
        qb = q.reshape(nb, Q_BLOCK, hkv, g, d)
        kpos = jnp.arange(s)

        def block(args):
            qi, i = args
            qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
            sc = jnp.einsum("qhgd,khd->hgqk", qi, k,
                            precision=HI) * self.attn_scale
            sc = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :,
                                                             None],
                           sc, -jnp.inf)
            p = jax.nn.softmax(sc, axis=-1)
            return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI)

        out = jax.lax.map(block, (qb, jnp.arange(nb)))
        return out.reshape(s, hq, d)

    def _layer(self, x, p, quant):
        sh = self.sh
        s = x.shape[0]
        pos = jnp.arange(s)
        a = p["attn"]
        h = self._rms(x, p["ln1"]["scale"])
        q = self._dense(a["wq"], h, quant).reshape(s, sh.heads, sh.head_dim)
        k = self._dense(a["wk"], h, quant).reshape(s, sh.kv_heads,
                                                   sh.head_dim)
        v = self._dense(a["wv"], h, quant).reshape(s, sh.kv_heads,
                                                   sh.head_dim)
        o = self._attention(self._rope(q, pos), self._rope(k, pos), v, quant)
        x = x + self.res_mult * self._dense(
            a["wo"], o.reshape(s, sh.heads * sh.head_dim), quant)
        h = self._rms(x, p["ln2"]["scale"])
        m = p["mlp"]
        u = jax.nn.silu(self._dense(m["gate"], h, quant)) * \
            self._dense(m["up"], h, quant)
        return x + self.res_mult * self._dense(m["down"], u, quant)

    # ------------------------------------------------------------ forward
    @functools.partial(jax.jit, static_argnums=(0, 3))
    def _hidden(self, params, tokens, quant):
        """Final-norm hidden states [S, d] of one padded sequence."""
        x = params["embed"]["table"][tokens].astype(jnp.float32)
        x = x * self.emb_mult

        def body(x, p):
            return self._layer(x, p, quant), None

        x, _ = jax.lax.scan(body, x, params["blocks"])
        return self._rms(x, params["ln_f"]["scale"])

    @functools.partial(jax.jit, static_argnums=(0, 3))
    def _logits(self, params, h, quant):
        if self.sh.tied:
            w = params["embed"]["table"].astype(jnp.float32).T
        else:
            w = params["head"]["w"].astype(jnp.float32)
        return self._mm(h, w, quant) / self.logit_div

    def logits(self, params, tokens, positions, quant=None) -> np.ndarray:
        """float32 logits [len(positions), V] of the sequence ``tokens``
        at ``positions`` (each predicting the token after it)."""
        tokens = np.asarray(tokens, np.int32)
        n = len(tokens)
        padded = np.zeros(-(-n // PAD) * PAD, np.int32)
        padded[:n] = tokens
        h = self._hidden(params, jnp.asarray(padded), quant)
        rows = np.asarray(positions, np.int32)
        out = []
        for i in range(0, len(rows), LOGIT_ROWS):
            idx = np.zeros(LOGIT_ROWS, np.int32)
            part = rows[i:i + LOGIT_ROWS]
            idx[:len(part)] = part
            lg = self._logits(params, h[jnp.asarray(idx)], quant)
            out.append(np.asarray(lg)[:len(part)])
        return np.concatenate(out, 0)

    # ------------------------------------------------------- comparisons
    def served_gaps(self, params, prompt, served, controls=()) -> dict:
        """For one served request: at each position that produced a
        served token, how far that token's reference logit lies below
        the reference's best (``gap``).  For each precision in
        ``controls``, the same for the token that the forward in that
        precision puts first (``gap_<precision>``)."""
        prompt = np.asarray(prompt, np.int32)
        served = np.asarray(served, np.int32)
        seq = np.concatenate([prompt, served[:-1]])
        pos = np.arange(len(prompt) - 1, len(seq))
        ref = self.logits(params, seq, pos)
        best = ref.max(-1)
        out = {"gap": best - ref[np.arange(len(served)), served]}
        for q in controls:
            pick = self.logits(params, seq, pos, quant=q).argmax(-1)
            out[f"gap_{q}"] = best - ref[np.arange(len(served)), pick]
        return out
