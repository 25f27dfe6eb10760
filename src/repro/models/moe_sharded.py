"""shard_map MoE dispatch — hierarchical FAA claiming + all_to_all exchange.

The einsum/scatter formulation (moe.py) is the faithful single-counter
baseline, but GSPMD partitions its token->buffer scatter as
"local-scatter-into-zeros + all-reduce over the data axis", moving the ENTIRE
expert buffer per layer (measured: 2.4 TB/device/layer on deepseek-v2-236b
train_4k — see EXPERIMENTS.md §Perf).  This module is the beyond-GSPMD fix,
and it is exactly the paper's core-group insight applied to dispatch:

* each (data, model) shard claims slots for ITS tokens with LOCAL counters
  (prefix-sum per shard = per-core-group FAA, no cross-group coherence);
* per-(source-shard, expert) capacity buckets are exchanged with ONE
  all_to_all over the model axis (the only inter-group traffic, analogous
  to the paper's cross-L3 line transfer — but batched and contention-free);
* expert FFN runs on the locally-owned experts; a second all_to_all returns
  outputs; combine is local.

Capacity semantics differ from the global counter only in being
per-source-shard (tokens never compete with another shard's tokens), the
same relaxation the paper applies between core groups.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import active_policy
from repro.models import layers
from repro.models.moe import MoEConfig, moe_apply, prefix_sum_slots


def moe_apply_sharded(
    p,
    cfg: MoEConfig,
    x: jax.Array,                 # [B, S, d]
    *,
    capacity: Optional[int] = None,
):
    """Drop-in for moe_apply; requires an active ShardingPolicy whose mesh
    has a 'model' axis dividing n_experts — else falls back to moe_apply."""
    pol = active_policy()
    if pol is None or "model" not in pol.mesh.shape \
            or cfg.n_experts % pol.mesh.shape["model"]:
        return moe_apply(p, cfg, x, capacity=capacity)

    mesh = pol.mesh
    m = mesh.shape["model"]
    token_axes = tuple(a for a in ("pod", "data", "model")
                       if a in mesh.shape)
    n_shards = int(np.prod([mesh.shape[a] for a in token_axes]))
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    e_loc = e // m
    if t % n_shards:
        return moe_apply(p, cfg, x, capacity=capacity)
    t_loc = t // n_shards
    cap = capacity or int(np.ceil(t_loc * k / e * cfg.capacity_factor))
    cap = max(4, -(-cap // 4) * 4)

    from jax.sharding import PartitionSpec as P

    tokens = x.reshape(t, d)

    def body(tok, router_w, gate, up, down):
        # gather FSDP'd expert weights for the locally-owned experts
        gate = jax.lax.all_gather(gate, "data", axis=1, tiled=True)
        up = jax.lax.all_gather(up, "data", axis=1, tiled=True)
        down = jax.lax.all_gather(down, "data", axis=2, tiled=True)
        tl = tok.shape[0]
        # ---- routing + aux losses, fully shard-local (global means via
        # pmean — no [T, E] tensor ever leaves the shard) ----
        logits = tok.astype(jnp.float32) @ router_w
        probs = jax.nn.softmax(logits, axis=-1)
        tp, ti = jax.lax.top_k(probs, k)
        tp = tp / jnp.maximum(jnp.sum(tp, -1, keepdims=True), 1e-9)
        assign_frac = jnp.mean(
            jax.nn.one_hot(ti[:, 0], e, dtype=jnp.float32), axis=0)
        prob_frac = jnp.mean(probs, axis=0)
        zloss_l = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        assign_frac = jax.lax.pmean(assign_frac, token_axes)
        prob_frac = jax.lax.pmean(prob_frac, token_axes)
        zloss = cfg.router_zloss * jax.lax.pmean(zloss_l, token_axes)
        aux = (e * jnp.sum(assign_frac * prob_frac) * cfg.aux_loss_weight
               + zloss)
        # ---- local (core-group) FAA claiming ----
        slot, keep = prefix_sum_slots(ti, e, cap)
        w = jnp.where(keep, tp, 0.0)
        ef = ti.reshape(-1)
        sf = jnp.where(keep, slot, cap - 1).reshape(-1)
        vals = jnp.repeat(tok[:, None, :], k, axis=1).reshape(tl * k, d)
        vals = vals * keep.reshape(-1, 1).astype(vals.dtype)
        buf = jnp.zeros((e, cap, d), tok.dtype).at[ef, sf].add(
            vals, mode="drop")
        # one all_to_all to the expert owners (dest = e // e_loc)
        send = buf.reshape(m, e_loc, cap, d)
        recv = jax.lax.all_to_all(send, "model", split_axis=0,
                                  concat_axis=0, tiled=False)
        flat = recv.transpose(1, 0, 2, 3).reshape(e_loc, m * cap, d)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", flat,
                                   gate.astype(flat.dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", flat, up.astype(flat.dtype))
        outb = jnp.einsum("ecf,efd->ecd", h, down.astype(flat.dtype))
        back = outb.reshape(e_loc, m, cap, d).transpose(1, 0, 2, 3)
        ret = jax.lax.all_to_all(back, "model", split_axis=0,
                                 concat_axis=0, tiled=False)
        retb = ret.reshape(e, cap, d)
        gathered = retb[ef, sf].reshape(tl, k, d)
        out = jnp.sum(gathered * w[..., None].astype(gathered.dtype), axis=1)
        kept = jax.lax.pmean(jnp.mean(keep.astype(jnp.float32)), token_axes)
        return out, aux, kept

    tok_spec = P(token_axes, None)
    out, aux, kept = jax.shard_map(
        body, mesh=mesh,
        in_specs=(tok_spec, P(None, None),
                  P("model", "data", None), P("model", "data", None),
                  P("model", None, "data")),
        out_specs=(tok_spec, P(), P()),
        check_vma=False,
    )(tokens, p["router"]["w"], p["gate"], p["up"], p["down"])

    if cfg.n_shared_experts:
        out = out + layers.mlp(p["shared"], tokens)

    metrics = {"aux_loss": aux, "dropped": 1.0 - kept}
    return out.reshape(b, s, d), metrics
