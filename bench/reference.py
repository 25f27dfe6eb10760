"""The plain reference: a dense decoder in float32, in ``jax.numpy``.

It follows the published Qwen2 / Granite decoder: RMSNorm, grouped-query
attention with rotary positions (half-split rotation) and optional QKV
bias, a SwiGLU MLP, and tied or untied read-out.  Granite's four scalar
multipliers are read from the configuration under their Hugging Face
names (``embedding_multiplier``, ``attention_multiplier``,
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

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import Shapes

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256      # query rows per attention block
PAD = 512          # sequences are padded to a multiple of this
LOGIT_ROWS = 128   # read-out rows per product


def fake_int8(x, axis):
    """Round ``x`` to int8 levels with one symmetric scale per slice
    along ``axis`` (the reduction axis of the product it feeds)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def fake_fp8(x, axis):
    """Round ``x`` to float8_e4m3fn with one scale per slice along
    ``axis`` that puts the slice's largest magnitude at 448."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


LOW = {"int8": fake_int8, "fp8": fake_fp8}


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
