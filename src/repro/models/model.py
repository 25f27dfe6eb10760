"""Top-level Model: init / loss / prefill / decode_step for every family.

Public API (used by train/, serve/, launch/):

    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    loss, metrics = model.loss(params, batch)
    logits, cache = model.prefill(params, batch, max_len)
    logits, cache = model.decode_step(params, tokens, cache)

Loss never materializes [B, S, V] logits — the head is applied in sequence
chunks inside a scan (vocab up to 256206 would otherwise dominate memory).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.kernels import quant
from repro.models import attention as attn_mod
from repro.models import layers, mla, ssm, transformer as tfm

LOSS_CHUNK = 512


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------------ init

    def init(self, key) -> dict:
        cfg = self.cfg
        dtype = cfg.dtype
        keys = jax.random.split(key, 8)
        p: dict[str, Any] = {
            "embed": layers.embedding_init(keys[0], cfg.vocab_size,
                                           cfg.d_model, dtype),
            "ln_f": layers.rmsnorm_init(cfg.d_model, dtype),
        }
        if not cfg.tie_embeddings:
            p["head"] = layers.dense_init(keys[1], cfg.d_model,
                                          cfg.vocab_size, stddev=0.02,
                                          dtype=dtype)
        fam = cfg.family
        if fam in ("dense",):
            p["blocks"] = tfm.stacked_init(
                lambda k: tfm.dense_block_init(k, cfg, dtype=dtype),
                keys[2], cfg.n_layers)
        elif fam == "moe":
            nd = cfg.first_dense_layers
            if nd:
                p["dense0"] = tfm.stacked_init(
                    lambda k: tfm.dense_block_init(
                        k, cfg, d_ff=cfg.dense_d_ff, dtype=dtype),
                    keys[3], nd)
            p["blocks"] = tfm.stacked_init(
                lambda k: tfm.moe_block_init(k, cfg, dtype=dtype),
                keys[2], cfg.n_layers - nd)
        elif fam == "ssm":
            p["blocks"] = tfm.stacked_init(
                lambda k: tfm.ssm_block_init(k, cfg, dtype=dtype),
                keys[2], cfg.n_layers)
        elif fam == "hybrid":
            g = cfg.n_layers // cfg.attn_every
            p["groups"] = tfm.stacked_init(
                lambda k: tfm.stacked_init(
                    lambda k2: tfm.ssm_block_init(k2, cfg, dtype=dtype),
                    k, cfg.attn_every),
                keys[2], g)
            p["shared_proj"] = layers.dense_init(
                keys[4], 2 * cfg.d_model, cfg.d_model, dtype=dtype)
            p["shared"] = tfm.dense_block_init(keys[5], cfg, dtype=dtype)
        elif fam == "vlm":
            p["groups"] = {
                "self": tfm.stacked_init(
                    lambda k: tfm.stacked_init(
                        lambda k2: tfm.dense_block_init(k2, cfg, dtype=dtype),
                        k, cfg.self_per_group),
                    keys[2], cfg.cross_attn_groups),
                "cross": tfm.stacked_init(
                    lambda k: tfm.cross_block_init(k, cfg, gated=True,
                                                   dtype=dtype),
                    keys[3], cfg.cross_attn_groups),
            }
        elif fam == "encdec":
            enc_cfg = dataclasses.replace(cfg)
            p["enc_blocks"] = tfm.stacked_init(
                lambda k: self._enc_block_init(k, enc_cfg, dtype),
                keys[2], cfg.n_encoder_layers)
            p["dec_blocks"] = tfm.stacked_init(
                lambda k: self._encdec_block_init(k, cfg, dtype),
                keys[3], cfg.n_layers)
            p["enc_ln"] = layers.rmsnorm_init(cfg.d_model, dtype)
        else:
            raise ValueError(f"unknown family {fam}")
        return p

    # ---------------------------------------------------------- enc-dec bits

    @staticmethod
    def _enc_block_init(key, cfg: ModelConfig, dtype):
        k1, k2 = jax.random.split(key)
        ac = tfm.attn_cfg(cfg, causal=False)
        return {
            "ln1": layers.rmsnorm_init(cfg.d_model, dtype),
            "attn": attn_mod.attn_init(k1, ac, dtype),
            "ln2": layers.rmsnorm_init(cfg.d_model, dtype),
            "mlp": layers.mlp_init(k2, cfg.d_model, cfg.d_ff, act=cfg.act,
                                   dtype=dtype),
        }

    @staticmethod
    def _enc_block_apply(p, cfg: ModelConfig, x):
        ac = tfm.attn_cfg(cfg, causal=False)
        h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
        a, _ = attn_mod.attn_apply(p["attn"], ac, h)
        x = x + a
        h = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
        x = x + layers.mlp(p["mlp"], h, act=cfg.act)
        return constrain(x, "act_btd")

    @staticmethod
    def _encdec_block_init(key, cfg: ModelConfig, dtype):
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "ln1": layers.rmsnorm_init(cfg.d_model, dtype),
            "self": attn_mod.attn_init(k1, tfm.attn_cfg(cfg), dtype),
            "ln2": layers.rmsnorm_init(cfg.d_model, dtype),
            "xattn": attn_mod.attn_init(
                k2, tfm.attn_cfg(cfg, causal=False, use_rope=False), dtype),
            "ln3": layers.rmsnorm_init(cfg.d_model, dtype),
            "mlp": layers.mlp_init(k3, cfg.d_model, cfg.d_ff, act=cfg.act,
                                   dtype=dtype),
        }

    def _encdec_block_apply(self, p, x, enc, cache=None):
        """cache: {"self": kv-cache, "ck","cv": cross K/V} or None."""
        cfg = self.cfg
        h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
        self_cache = cache["self"] if cache is not None else None
        a, new_self = attn_mod.attn_apply(p["self"], tfm.attn_cfg(cfg), h,
                                          cache=self_cache)
        x = x + a
        # cross attention
        ac = tfm.attn_cfg(cfg, causal=False, use_rope=False)
        h = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
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
        x = x + layers.dense(p["xattn"]["wo"], o.reshape(b, s, hq * hd))
        h = layers.rmsnorm(p["ln3"], x, cfg.norm_eps)
        x = x + layers.mlp(p["mlp"], h, act=cfg.act)
        x = constrain(x, "act_btd")
        new_cache = ({"self": new_self, "ck": ck, "cv": cv}
                     if cache is not None else None)
        return x, new_cache, jnp.zeros((), jnp.float32)

    # ------------------------------------------------------------- backbone

    def _backbone(self, params, x, batch, caches=None, *, train=False,
                  append_only=False):
        """x: [B,S,d] embedded tokens. Returns (x, new_caches, aux).

        ``append_only`` (dense and MoE): the layers read ``caches`` and
        return only the new tokens' leaves, stacked like the cache, in
        place of new caches (see :meth:`decode_step`)."""
        cfg = self.cfg
        fam = cfg.family
        remat = train

        if fam == "dense":
            return tfm.scan_layers(
                lambda p, xc, c: tfm.dense_block_apply(
                    p, cfg, xc, cache=c, append_only=append_only),
                params["blocks"], x, caches, remat=remat, remat_policy=cfg.remat_policy)

        if fam == "moe":
            aux = jnp.zeros((), jnp.float32)
            new_caches = {}
            nd = cfg.first_dense_layers
            if nd:
                c0 = caches["dense0"] if caches is not None else None
                x, nc0, a0 = tfm.scan_layers(
                    lambda p, xc, c: tfm.dense_block_apply(
                        p, cfg, xc, cache=c, append_only=append_only),
                    params["dense0"], x, c0, remat=remat, remat_policy=cfg.remat_policy)
                new_caches["dense0"] = nc0
                aux += a0
            cm = caches["blocks"] if caches is not None else None
            x, ncm, am = tfm.scan_layers(
                lambda p, xc, c: tfm.moe_block_apply(
                    p, cfg, xc, cache=c, append_only=append_only),
                params["blocks"], x, cm, remat=remat, remat_policy=cfg.remat_policy)
            new_caches["blocks"] = ncm
            aux += am
            return x, (new_caches if caches is not None else None), aux

        if fam == "ssm":
            return tfm.scan_layers(
                lambda p, xc, c: tfm.ssm_block_apply(p, cfg, xc, cache=c),
                params["blocks"], x, caches, remat=remat, remat_policy=cfg.remat_policy)

        if fam == "hybrid":
            x0 = x  # original embeddings feed the shared block every group

            def group_apply(gp, xc, gc):
                ssm_c = gc["ssm"] if gc is not None else None
                xc, new_ssm, aux = tfm.scan_layers(
                    lambda p, xx, c: tfm.ssm_block_apply(p, cfg, xx, cache=c),
                    gp, xc, ssm_c, remat=False)
                h = layers.dense(params["shared_proj"],
                                 jnp.concatenate([xc, x0], axis=-1))
                attn_c = gc["attn"] if gc is not None else None
                h, new_attn, a2 = tfm.dense_block_apply(
                    params["shared"], cfg, h, cache=attn_c)
                xc = xc + h
                xc = constrain(xc, "act_btd")
                new_gc = ({"ssm": new_ssm, "attn": new_attn}
                          if gc is not None else None)
                return xc, new_gc, aux + a2

            return tfm.scan_layers(group_apply, params["groups"], x, caches,
                                   remat=remat)

        if fam == "vlm":
            patches = batch.get("patches")
            if patches is not None:
                patches = patches.astype(x.dtype)

            def group_apply(gp, xc, gc):
                self_c = gc["self"] if gc is not None else None
                xc, new_self, aux = tfm.scan_layers(
                    lambda p, xx, c: tfm.dense_block_apply(p, cfg, xx,
                                                           cache=c),
                    gp["self"], xc, self_c, remat=False)
                cross_c = gc["cross"] if gc is not None else None
                xc, new_cross, a2 = tfm.cross_block_apply(
                    gp["cross"], cfg, xc, patches, cache=cross_c)
                new_gc = ({"self": new_self, "cross": new_cross}
                          if gc is not None else None)
                return xc, new_gc, aux + a2

            return tfm.scan_layers(group_apply, params["groups"], x, caches,
                                   remat=remat)

        if fam == "encdec":
            frames = batch.get("frames")
            if frames is not None:
                enc = frames.astype(x.dtype)

                def enc_body(carry, p):
                    return self._enc_block_apply(p, cfg, carry), None

                enc, _ = jax.lax.scan(enc_body, enc, params["enc_blocks"])
                enc = layers.rmsnorm(params["enc_ln"], enc, cfg.norm_eps)
            else:
                enc = None  # decode: cross K/V come from the cache

            return tfm.scan_layers(
                lambda p, xc, c: self._encdec_block_apply(p, xc, enc,
                                                          cache=c),
                params["dec_blocks"], x, caches, remat=remat, remat_policy=cfg.remat_policy)

        raise ValueError(fam)

    # ----------------------------------------------------------------- loss

    def _logits(self, params, x):
        if self.cfg.tie_embeddings:
            return layers.unembed(params["embed"], x)
        return layers.dense(params["head"], x)

    def loss(self, params, batch):
        """Next-token CE over batch["tokens"]; returns (loss, metrics)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = layers.embed(params["embed"], tokens).astype(cfg.dtype)
        x = constrain(x, "act_btd")
        x, _, aux = self._backbone(params, x, batch, None, train=True)
        x = layers.rmsnorm(params["ln_f"], x, cfg.norm_eps)

        # chunked CE: predict tokens[:, i+1] from x[:, i]; last pos masked.
        b, s, _ = x.shape
        targets = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
        mask = jnp.concatenate(
            [jnp.ones((b, s - 1), jnp.float32), jnp.zeros((b, 1), jnp.float32)],
            axis=1)
        chunk = min(LOSS_CHUNK, s)
        pad = (-s) % chunk
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
            targets = jnp.pad(targets, ((0, 0), (0, pad)))
            mask = jnp.pad(mask, ((0, 0), (0, pad)))
        nc = (s + pad) // chunk
        xc = x.reshape(b, nc, chunk, -1).transpose(1, 0, 2, 3)
        tc = targets.reshape(b, nc, chunk).transpose(1, 0, 2)
        mc = mask.reshape(b, nc, chunk).transpose(1, 0, 2)

        def body(carry, inp):
            xs, ts, ms = inp
            logits = self._logits(params, xs)
            logits = constrain(logits, "logits")
            logits = logits.astype(jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, ts[..., None], axis=-1)[..., 0]
            nll = jnp.sum((logz - gold) * ms)
            return (carry[0] + nll, carry[1] + jnp.sum(ms)), None

        (total, denom), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (xc, tc, mc))
        ce = total / jnp.maximum(denom, 1.0)
        loss = ce + aux
        return loss, {"ce": ce, "aux": aux}

    # ------------------------------------------------------------ inference

    def init_cache(self, batch_size: int, max_len: int,
                   dtype=jnp.bfloat16, enc_len: Optional[int] = None) -> Any:
        cfg = self.cfg
        fam = cfg.family
        if quant.is_quant_dtype(dtype) and (
                cfg.use_mla or fam in ("vlm", "encdec")):
            raise ValueError(
                f"quantized KV cache ({jnp.dtype(dtype).name}) requires "
                f"every attention cache to be a standard attn_apply KV "
                f"cache; family {fam!r}{' (MLA)' if cfg.use_mla else ''} "
                f"keeps latent/cross caches with their own access paths")
        ac = tfm.attn_cfg(cfg)
        sc = tfm.ssm_cfg(cfg) if cfg.ssm_state else None

        def stack(make, n):
            one = make()
            return jax.tree.map(lambda a: jnp.broadcast_to(
                a[None], (n,) + a.shape), one)

        if fam in ("dense", "moe"):
            if cfg.use_mla:
                mk = lambda: mla.init_mla_cache(tfm.mla_cfg(cfg), batch_size,
                                                max_len, dtype)
            else:
                mk = lambda: attn_mod.init_kv_cache(ac, batch_size, max_len,
                                                    dtype)
            if fam == "dense":
                return stack(mk, cfg.n_layers)
            out = {"blocks": stack(mk, cfg.n_layers - cfg.first_dense_layers)}
            if cfg.first_dense_layers:
                out["dense0"] = stack(mk, cfg.first_dense_layers)
            return out
        if fam == "ssm":
            return stack(lambda: ssm.init_ssm_cache(sc, batch_size),
                         cfg.n_layers)
        if fam == "hybrid":
            g = cfg.n_layers // cfg.attn_every
            def mk_group():
                return {
                    "ssm": stack(lambda: ssm.init_ssm_cache(sc, batch_size),
                                 cfg.attn_every),
                    "attn": attn_mod.init_kv_cache(ac, batch_size, max_len,
                                                   dtype),
                }
            return stack(mk_group, g)
        if fam == "vlm":
            def mk_group():
                return {
                    "self": stack(lambda: attn_mod.init_kv_cache(
                        ac, batch_size, max_len, dtype), cfg.self_per_group),
                    "cross": {
                        "ck": jnp.zeros((batch_size, cfg.vision_seq,
                                         ac.n_kv_heads, ac.head_dim), dtype),
                        "cv": jnp.zeros((batch_size, cfg.vision_seq,
                                         ac.n_kv_heads, ac.head_dim), dtype),
                    },
                }
            return stack(mk_group, cfg.cross_attn_groups)
        if fam == "encdec":
            enc_len = enc_len or max_len // cfg.encoder_downsample
            def mk():
                return {
                    "self": attn_mod.init_kv_cache(ac, batch_size, max_len,
                                                   dtype),
                    "ck": jnp.zeros((batch_size, enc_len, ac.n_kv_heads,
                                     ac.head_dim), dtype),
                    "cv": jnp.zeros((batch_size, enc_len, ac.n_kv_heads,
                                     ac.head_dim), dtype),
                }
            return stack(mk, cfg.n_layers)
        raise ValueError(fam)

    def prefill(self, params, batch, max_len: int,
                cache_dtype=jnp.bfloat16):
        """Run the prompt; returns (last-token logits [B,V], cache)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        b = tokens.shape[0]
        enc_len = (batch["frames"].shape[1] if cfg.family == "encdec"
                   else None)
        cache = self.init_cache(b, max_len, cache_dtype, enc_len=enc_len)
        x = layers.embed(params["embed"], tokens).astype(cfg.dtype)
        x = constrain(x, "act_btd")
        x, cache, _ = self._backbone(params, x, batch, cache, train=False)
        x = layers.rmsnorm(params["ln_f"], x[:, -1:], cfg.norm_eps)
        logits = self._logits(params, x)[:, 0]
        return logits.astype(jnp.float32), cache

    def decode_step(self, params, tokens, cache):
        """tokens: [B,1] -> (logits [B,V], new cache).

        A cache that :meth:`writes_in_place` is only read by the layers,
        which emit each row's new K/V; one scatter per leaf then writes
        them at (layer, row, len[row]).  Under a jit that donates the
        cache (``Engine`` does), the step writes those few bytes into the
        cache's own buffer instead of rewriting the whole cache.  Any
        other cache takes the layers' own write."""
        cfg = self.cfg
        batch = {"tokens": tokens}
        x = layers.embed(params["embed"], tokens).astype(cfg.dtype)
        in_place = tokens.shape[1] == 1 and self.writes_in_place(cache)
        x, new, _ = self._backbone(params, x, batch, cache, train=False,
                                   append_only=in_place)
        cache = _write_tokens(cache, new) if in_place else new
        x = layers.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = self._logits(params, x)[:, 0]
        return logits.astype(jnp.float32), cache

    def writes_in_place(self, cache) -> bool:
        """Whether :meth:`decode_step` writes ``cache`` in place: the
        continuous-serve form of a dense or MoE stack of standard
        attention caches (contiguous k/v leaves, quantized or not, no
        page table, per-row ``len``).  Paged pools, scalar lengths, MLA
        latents, recurrent state and cross K/V keep the layers' own
        write."""
        cfg = self.cfg
        if cfg.family not in ("dense", "moe") or cfg.use_mla:
            return False
        stacks = [cache] if cfg.family == "dense" else list(cache.values())
        return all("pt" not in c and len(c["len"].shape) == 2
                   for c in stacks)

    def verify_step(self, params, tokens, cache):
        """tokens: [B,S] -> (logits [B,S,V], new cache).

        The multi-token sibling of :meth:`decode_step` for speculative
        verification: every position's logits are kept, and each position
        j is computed exactly as an s==1 decode at row length ``len + j``
        would compute it (see the per-position loop in ``attn_apply``), so
        greedy argmax over position j is bit-identical to the token a
        non-speculative decode tick would have produced after consuming
        ``tokens[:, :j]``.  The cache advances by S per row; the caller
        rolls back to the accepted length with
        :meth:`override_cache_lengths`.
        """
        if not self.supports_speculation:
            raise ValueError(
                f"{self.cfg.name}: family={self.cfg.family}"
                f"{' (MLA)' if self.cfg.use_mla else ''} cannot verify "
                "speculatively — rollback requires every cache leaf to be "
                "a length-masked KV cache (dense, non-MLA)")
        cfg = self.cfg
        batch = {"tokens": tokens}
        x = layers.embed(params["embed"], tokens).astype(cfg.dtype)
        x, cache, _ = self._backbone(params, x, batch, cache, train=False)
        x = layers.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = self._logits(params, x)
        return logits.astype(jnp.float32), cache

    @property
    def supports_speculation(self) -> bool:
        """Whether this model can act as speculative target or drafter.

        Rollback after partial acceptance is a pure length truncation, so
        every growing cache leaf must be a length-masked KV cache: dense,
        non-MLA.  SSM/hybrid recurrent state advances irreversibly (no
        way to rewind k tokens without replay), and MoE's batch-coupled
        expert capacity would let one slot's rejected drafts perturb
        other slots' routing during the multi-token verify — the same
        up-front rejects as the paged/quantized MoE/MLA paths."""
        return self.cfg.family == "dense" and not self.cfg.use_mla

    # ------------------------------------------- continuous-serving hooks

    @property
    def pad_safe_prefill(self) -> bool:
        """Whether right-padded prompts can batch without contaminating the
        real tokens.  True only where every cross-position op is causal
        attention (pads are causally invisible to earlier positions): the
        dense family.  MoE routes with batch-coupled expert capacity (pad
        tokens would compete with real ones for slots), and SSM/hybrid
        carry a recurrent state straight through the pads."""
        return self.cfg.family == "dense"

    def prefill_padded(self, params, batch, max_len: int,
                       cache_dtype=jnp.bfloat16):
        """Pad-masked prefill of right-padded mixed-length prompts.

        ``batch["tokens"]`` [B, W] right-padded, ``batch["lengths"]`` [B]
        true lengths (1 <= L <= W).  Returns (logits at each row's last
        *real* token [B, V], cache whose ``len`` entries are per-row [B]
        vectors set to the true lengths) — the cache shape a continuous
        decode loop needs: each slot resumes at its own position, and the
        pad positions' garbage K/V stay masked behind ``kv_len`` forever.
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        lengths = jnp.asarray(batch["lengths"], jnp.int32)
        b = tokens.shape[0]
        enc_len = (batch["frames"].shape[1] if cfg.family == "encdec"
                   else None)
        cache = self.init_cache(b, max_len, cache_dtype, enc_len=enc_len)
        x = layers.embed(params["embed"], tokens).astype(cfg.dtype)
        x = constrain(x, "act_btd")
        x, cache, _ = self._backbone(params, x, batch, cache, train=False)
        idx = jnp.clip(lengths - 1, 0, tokens.shape[1] - 1)[:, None, None]
        x_last = jnp.take_along_axis(
            x, jnp.broadcast_to(idx, (b, 1, x.shape[-1])), axis=1)
        x_last = layers.rmsnorm(params["ln_f"], x_last, cfg.norm_eps)
        logits = self._logits(params, x_last)[:, 0]
        return logits.astype(jnp.float32), self.set_cache_lengths(cache,
                                                                  lengths)

    @staticmethod
    def set_cache_lengths(cache, lengths) -> Any:
        """Rewrite every ``len`` entry of a cache tree to per-row lengths.

        Cache leaves are layer-stacked (``init_cache``'s ``stack``), so a
        ``len`` leaf's existing shape is pure stack dims; the row vector is
        broadcast behind them: ``[*stack] -> [*stack, B]``.
        """
        lengths = jnp.asarray(lengths, jnp.int32)

        def walk(node):
            if isinstance(node, dict):
                return {k: (jnp.broadcast_to(lengths, v.shape + lengths.shape)
                            if k == "len" else walk(v))
                        for k, v in node.items()}
            return node

        return walk(cache)

    @staticmethod
    def override_cache_lengths(cache, lengths) -> Any:
        """Rewrite the per-row ``len`` entries of a *serve-form* cache.

        The speculative rollback primitive: a verify step advanced every
        row by the full draft span, and the accepted prefix per row is
        shorter — truncating ``len`` masks the rejected positions, whose
        garbage K/V contribute exactly ``exp(NEG_INF - m) = 0`` until
        they are overwritten.  Unlike :meth:`set_cache_lengths` (which
        *adds* a row axis to scalar-form leaves), this expects ``len``
        leaves already in per-row form ``[*stack, B]`` and broadcasts the
        new ``[B]`` vector over the stack dims only.
        """
        lengths = jnp.asarray(lengths, jnp.int32)

        def walk(node):
            if isinstance(node, dict):
                return {k: (jnp.broadcast_to(lengths, v.shape)
                            if k == "len" else walk(v))
                        for k, v in node.items()}
            return node

        return walk(cache)

    def cache_batch_axes(self, *, per_row_len: bool = True,
                         dtype=jnp.bfloat16) -> Any:
        """Tree of ints: the batch-axis index of every cache leaf.

        Leaves are layer-stacked, so the batch axis is not a fixed
        position; probing two abstract batch sizes (eval_shape — nothing is
        allocated) identifies it per leaf.  ``per_row_len`` probes the
        continuous-serve cache form where ``len`` entries are [B] vectors
        (see :meth:`set_cache_lengths`); with ``per_row_len=False`` the
        scalar-``len`` leaves have no batch axis at all and map to ``-1``
        (:meth:`splice_cache` leaves such leaves untouched).  ``dtype``
        must match the cache being spliced — a quantized cache carries
        extra scale leaves the default probe would not see."""

        def make(bsz):
            cache = self.init_cache(bsz, 8, dtype)
            if per_row_len:
                cache = self.set_cache_lengths(cache,
                                               jnp.zeros(bsz, jnp.int32))
            return cache

        two = jax.eval_shape(lambda: make(2))
        three = jax.eval_shape(lambda: make(3))

        def axis(a, b):
            diffs = [i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                     if x != y]
            if not diffs:       # batch-independent leaf (scalar-form `len`)
                return -1
            if len(diffs) != 1:
                raise ValueError(
                    f"cannot identify batch axis: shapes {a.shape} vs "
                    f"{b.shape} differ at {diffs}")
            return diffs[0]

        return jax.tree.map(axis, two, three)

    def splice_cache(self, cache, prefill_cache, slot, *, axes, row: int = 0):
        """Copy row ``row`` of a prefill cache into batch slot ``slot`` of a
        (larger) serve cache — the in-flight refill of a freed decode slot.

        ``axes`` is the tree from :meth:`cache_batch_axes`; both caches
        must share every non-batch dim (allocate the prefill cache at the
        same ``max_len``).  ``slot`` may be traced, so one jit of this
        covers every slot.  Leaves whose axis is ``-1`` (batch-independent,
        e.g. scalar-form ``len``) keep the destination's value."""

        def sp(dst, src, ax):
            if ax < 0:
                return dst
            piece = jax.lax.index_in_dim(src, row, ax, keepdims=False)
            return jax.lax.dynamic_update_index_in_dim(dst, piece, slot, ax)

        return jax.tree.map(sp, cache, prefill_cache, axes)

    # ----------------------------------------------- paged-KV serving hooks

    @property
    def supports_paged_kv(self) -> bool:
        """Whether this family can decode against a paged KV pool.

        True where every growing cache leaf is a standard ``attn_apply``
        KV cache (dense; hybrid's shared attention blocks) or where nothing
        grows at all (ssm — the recurrent state is constant-size, so there
        are no pages and the paged engine degenerates to per-slot state).
        MoE/MLA keep a latent cache with its own access path
        (``mla_apply``) and a batch-coupled router; paging them is open
        work (see ROADMAP quantized/paged compounding)."""
        return (self.cfg.family in ("dense", "ssm", "hybrid")
                and not self.cfg.use_mla)

    @property
    def prefix_shareable(self) -> bool:
        """Whether a token-prefix's cache state is fully reconstructable
        from KV pages alone — the precondition for shared-prefix reuse.
        Only true when *every* cache leaf is paged (dense): a recurrent
        state (ssm/hybrid) lives outside the pages, and MoE's router makes
        split prefills batch-coupled."""
        return self.cfg.family == "dense" and not self.cfg.use_mla

    def cache_page_spec(self, *, max_len: int = 8,
                        dtype=jnp.bfloat16) -> Any:
        """Tree of ints over the contiguous cache: each leaf's *token-axis*
        index (the axis that scales with ``max_len``), or ``-1`` for leaves
        that do not grow with sequence length (recurrent state, ``len``
        entries).  Identified by probing two abstract ``max_len`` values —
        nothing is allocated.  ``dtype`` must match the cache being paged:
        a quantized cache's scale leaves ("ks"/"vs") carry the token axis
        too and become scale page pools alongside the value pools."""

        a = jax.eval_shape(lambda: self.init_cache(2, max_len, dtype))
        b = jax.eval_shape(lambda: self.init_cache(2, 2 * max_len, dtype))

        def axis(x, y):
            diffs = [i for i, (p, q) in enumerate(zip(x.shape, y.shape))
                     if p != q]
            if not diffs:
                return -1
            if len(diffs) != 1:
                raise ValueError(
                    f"cannot identify token axis: shapes {x.shape} vs "
                    f"{y.shape} differ at {diffs}")
            return diffs[0]

        return jax.tree.map(axis, a, b)

    def init_paged_cache(self, n_slots: int, max_len: int, num_pages: int,
                         page_size: int, dtype=jnp.bfloat16) -> Any:
        """Paged serve cache: every token-axis KV leaf becomes a *shared*
        page pool, everything else stays per-slot.

        A contiguous leaf ``[*stack, B, max_len, ...]`` becomes a pool
        ``[*stack, num_pages + 1, page_size, ...]`` — the batch axis is
        gone: slots address the pool through a page table instead of owning
        a private row.  Pool index 0 is the reserved scratch page (decode
        steps of idle slots write there; never allocated, never unmasked).
        Each dict that holds paged leaves gains a ``"pt"`` page-table entry
        ``[*stack, B, max_len // page_size]`` (identical across the stack —
        page identity is layer-independent) and its ``len`` entry takes the
        per-row ``[*stack, B]`` form.  Leaves with no token axis (recurrent
        state) keep their per-slot ``[*stack, B, ...]`` shape.

        ``attn_apply`` recognises the ``"pt"`` key and decodes through the
        pool (scatter one token into the slot's current page, gather the
        slot's pages back to a ``[B, max_len]`` view for attention) —
        bit-identical to the contiguous per-row path.
        """
        if max_len % page_size:
            raise ValueError(f"max_len {max_len} must be a multiple of "
                             f"page_size {page_size}")
        if not self.supports_paged_kv:
            raise ValueError(
                f"family {self.cfg.family!r}"
                f"{' (MLA)' if self.cfg.use_mla else ''} has no paged "
                f"decode path — see Model.supports_paged_kv")
        pages_per_seq = max_len // page_size
        template = jax.eval_shape(
            lambda: self.init_cache(n_slots, max_len, dtype))
        spec = self.cache_page_spec(dtype=dtype)

        def walk(tpl, sp):
            if isinstance(tpl, dict):
                out = {}
                paged_stack = None
                for key, sub in tpl.items():
                    if key == "len":
                        out["len"] = jnp.zeros(sub.shape + (n_slots,),
                                               jnp.int32)
                        continue
                    out[key] = walk(sub, sp[key])
                    if not isinstance(sub, dict) and sp[key] >= 0:
                        paged_stack = sub.shape[: sp[key] - 1]
                if paged_stack is not None:
                    out["pt"] = jnp.zeros(
                        paged_stack + (n_slots, pages_per_seq), jnp.int32)
                return out
            t = sp
            if t < 0:
                return jnp.zeros(tpl.shape, tpl.dtype)    # per-slot leaf
            return jnp.zeros(tpl.shape[: t - 1]
                             + (num_pages + 1, page_size)
                             + tpl.shape[t + 1:], tpl.dtype)

        return walk(template, spec)

    def write_page(self, paged_cache, prefill_cache, phys, src_page, *,
                   spec, page_size: int):
        """Copy one page worth of KV — tokens ``[src_page * page_size,
        (src_page + 1) * page_size)`` of row 0 of a contiguous prefill
        cache — into physical page ``phys`` of every pool leaf.  ``phys``
        and ``src_page`` may be traced (one jit covers every page); leaves
        without a token axis (and ``len``/``pt`` entries) are untouched.
        """
        ps = page_size

        def walk(pg, pre, sp):
            if isinstance(pg, dict):
                return {k: (walk(pg[k], pre[k], sp[k])
                            if k in pre and k not in ("len",) else pg[k])
                        for k in pg}
            t = sp
            if t < 0:
                return pg
            row = jax.lax.index_in_dim(pre, 0, t - 1, keepdims=False)
            piece = jax.lax.dynamic_slice_in_dim(row, src_page * ps, ps,
                                                 axis=t - 1)
            return jax.lax.dynamic_update_index_in_dim(pg, piece, phys,
                                                       axis=t - 1)

        return walk(paged_cache, prefill_cache, spec)

    def admit_paged_slot(self, paged_cache, prefill_cache, slot, length,
                         pt_row, *, spec, axes):
        """Point batch slot ``slot`` of a paged cache at its pages: set the
        slot's page-table row to ``pt_row``, its ``len`` to ``length``, and
        splice row 0 of the prefill cache into any per-slot (non-paged)
        leaves — the paged twin of :meth:`splice_cache`.  KV pool leaves
        are untouched (:meth:`write_page` fills them per page).
        """

        def walk(pg, pre, sp, ax):
            if isinstance(pg, dict):
                out = {}
                for k in pg:
                    if k == "pt":
                        row = jnp.broadcast_to(
                            pt_row, pg[k].shape[:-2] + pt_row.shape)
                        out[k] = jax.lax.dynamic_update_index_in_dim(
                            pg[k], row, slot, axis=pg[k].ndim - 2)
                    elif k == "len":
                        full = jnp.broadcast_to(
                            jnp.asarray(length, jnp.int32), pg[k].shape[:-1])
                        out[k] = jax.lax.dynamic_update_index_in_dim(
                            pg[k], full, slot, axis=pg[k].ndim - 1)
                    else:
                        out[k] = walk(pg[k], pre[k], sp[k], ax[k])
                return out
            if sp >= 0:
                return pg                                  # pool leaf
            piece = jax.lax.index_in_dim(pre, 0, ax, keepdims=False)
            return jax.lax.dynamic_update_index_in_dim(pg, piece, slot, ax)

        return walk(paged_cache, prefill_cache, spec, axes)

    def gather_prefix_cache(self, paged_cache, pt_row, length, *, spec,
                            page_size: int):
        """Materialize a batch-of-1, scalar-``len`` contiguous cache from
        the pages named by ``pt_row`` — the view :meth:`prefill_continue`
        extends when a prefix-cache hit skips recomputation.  Only valid
        for fully-paged families (:attr:`prefix_shareable`): a per-slot
        leaf cannot be reconstructed from pages."""

        def walk(pg, sp):
            if isinstance(pg, dict):
                out = {}
                for k, sub in pg.items():
                    if k == "pt":
                        continue
                    if k == "len":
                        out[k] = jnp.broadcast_to(
                            jnp.asarray(length, jnp.int32), sub.shape[:-1])
                        continue
                    out[k] = walk(sub, sp[k])
                return out
            t = sp
            if t < 0:
                raise ValueError(
                    "gather_prefix_cache needs a fully-paged cache "
                    "(Model.prefix_shareable families only)")
            got = jnp.take(pg, pt_row, axis=t - 1)   # [*stack, P, ps, ...]
            shp = got.shape
            got = got.reshape(shp[: t - 1] + (shp[t - 1] * shp[t],)
                              + shp[t + 1:])
            return jnp.expand_dims(got, t - 1)       # [*stack, 1, S, ...]

        return walk(paged_cache, spec)

    def prefill_continue(self, params, tokens, cache):
        """Extend an existing scalar-``len`` cache by ``tokens`` [B, S]
        (S >= 1): the continuation prefill a prefix-cache hit runs over
        just the uncached suffix.  Returns (logits at the last new token
        [B, V], updated cache) — the multi-token sibling of
        :meth:`decode_step`."""
        cfg = self.cfg
        batch = {"tokens": tokens}
        x = layers.embed(params["embed"], tokens).astype(cfg.dtype)
        x = constrain(x, "act_btd")
        x, cache, _ = self._backbone(params, x, batch, cache, train=False)
        x = layers.rmsnorm(params["ln_f"], x[:, -1:], cfg.norm_eps)
        logits = self._logits(params, x)[:, 0]
        return logits.astype(jnp.float32), cache


def _write_tokens(cache, tokens):
    """Write the layers' new-token leaves ``[L, B, 1, ...]`` into a
    layer-stacked per-row cache at (layer, row, len[row]), one scatter per
    leaf, and advance ``len`` by one.  A row already at the end of the
    cache rewrites its last position, as ``dynamic_update_slice`` clamps."""
    if "len" not in cache:                  # MoE: one stack per block kind
        return {n: _write_tokens(cache[n], tokens[n]) for n in cache}
    lens = cache["len"]                                    # [L, B]
    n_layers, b = lens.shape
    pos = jnp.minimum(lens, cache["k"].shape[2] - 1)
    at = (jnp.arange(n_layers)[:, None], jnp.arange(b)[None, :], pos)
    out = {n: cache[n].at[at].set(t[:, :, 0], unique_indices=True)
           for n, t in tokens.items()}
    out["len"] = lens + 1
    return out
