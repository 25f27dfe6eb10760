"""Distribution-layer integration: real multi-device (8 fake CPU devices)
runs in a subprocess so the device-count flag doesn't leak into this
process.  Covers: sharded train step under the policy (TP and pure-FSDP
layouts), shard_map MoE inside a full model, elastic checkpoint remesh."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.configs.inputs import make_dummy_batch
    from repro.distributed import params as psh
    from repro.distributed.sharding import ShardingPolicy, policy
    from repro.models import Model
    from repro.train import optimizer as opt_mod
    from repro.train.train_step import make_train_step

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))

    # ---- sharded train step: MoE arch with shard_map dispatch ----
    cfg = get_config("deepseek-v2-lite-16b").reduced()
    cfg = dataclasses.replace(cfg, moe_impl="sharded", n_experts=4)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    p_sh = psh.param_shardings(jax.eval_shape(lambda: params), mesh)
    params = jax.device_put(params, p_sh)
    opt_cfg = opt_mod.AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=4)
    opt = jax.device_put(opt_mod.init_state(params, opt_cfg),
                         psh.tree_shardings(
                             jax.eval_shape(lambda: opt_mod.init_state(
                                 params, opt_cfg)), mesh, psh.PARAM_RULES))
    batch = make_dummy_batch(cfg, batch=4, seq=32)
    step = jax.jit(make_train_step(model, opt_cfg))
    pol = ShardingPolicy(mesh)
    losses = []
    with policy(pol):
        for _ in range(3):
            params, opt, m = step(params, opt, batch)
            losses.append(float(m["loss"]))
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], losses
    print("MOE_SHARDED_TRAIN_OK", losses[0], losses[-1])

    # ---- pure-FSDP layout lowers and runs ----
    cfg2 = get_config("qwen2.5-3b").reduced()
    model2 = Model(cfg2)
    params2 = model2.init(jax.random.PRNGKey(1))
    p_sh2 = psh.param_shardings(jax.eval_shape(lambda: params2), mesh,
                                layout="fsdp")
    params2 = jax.device_put(params2, p_sh2)
    batch2 = make_dummy_batch(cfg2, batch=8, seq=32)
    pol2 = ShardingPolicy(mesh, fsdp_pure=True)
    with policy(pol2):
        loss, _ = jax.jit(model2.loss)(params2, batch2)
    assert np.isfinite(float(loss))
    print("FSDP_LAYOUT_OK", float(loss))

    # ---- elastic remesh: save under (2,4), restore under (4,2) ----
    from repro.checkpoint import checkpoint as ckpt
    import tempfile
    d = tempfile.mkdtemp()
    ckpt.save({"p": params2}, d, 1)
    mesh2 = make_mesh((4, 2), ("data", "model"))
    p_sh3 = psh.param_shardings(jax.eval_shape(lambda: params2), mesh2,
                                layout="tp")
    restored, _ = ckpt.restore(d, like={"p": params2},
                               shardings={"p": p_sh3})
    a = np.asarray(jax.tree.leaves(restored)[0])
    b = np.asarray(jax.tree.leaves({"p": params2})[0])
    np.testing.assert_array_equal(a, b)
    print("ELASTIC_REMESH_OK")

    # ---- distributed flash-decode (kvseq) matches the plain path ----
    from repro.models import attention as A
    from repro.kernels.decode_attention.ref import decode_attention_ref
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (4, 8, 16))
    k = jax.random.normal(ks[1], (4, 32, 2, 16))
    v = jax.random.normal(ks[2], (4, 32, 2, 16))
    kv_len = jnp.array([10, 32, 5, 20], jnp.int32)
    out = jax.jit(lambda q, k, v, kl: A.distributed_decode_attention(
        q, k, v, kl, mesh=mesh))(q, k, v, kv_len)
    ref = decode_attention_ref(q, k, v, kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    print("DIST_DECODE_OK")

    # ---- kvseq policy end-to-end: full decode_step (GQA + MLA) matches ----
    for arch in ("granite-3-2b", "deepseek-v2-lite-16b"):
        c = get_config(arch).reduced()
        if c.family == "moe":
            c = dataclasses.replace(c, capacity_factor=8.0)
        mm = Model(c)
        pp = mm.init(jax.random.PRNGKey(0))
        bb = make_dummy_batch(c, 4, 8)
        lg, cch = mm.prefill(pp, bb, max_len=16, cache_dtype=jnp.float32)
        tk = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)
        l_plain, _ = mm.decode_step(pp, tk, cch)
        with policy(ShardingPolicy(mesh, decode_seq_shard=True)):
            l_dist, _ = jax.jit(mm.decode_step)(pp, tk, cch)
        np.testing.assert_allclose(np.asarray(l_plain), np.asarray(l_dist),
                                   atol=2e-3, rtol=2e-3)
    print("KVSEQ_PATH_OK")
""")


def test_distributed_integration():
    # the parent's environment (JAX_PLATFORMS above all) reaches the child
    env = dict(os.environ)
    env["PYTHONPATH"] = (str(REPO / "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=str(REPO))
    out = r.stdout
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    assert "MOE_SHARDED_TRAIN_OK" in out
    assert "FSDP_LAYOUT_OK" in out
    assert "ELASTIC_REMESH_OK" in out
    assert "DIST_DECODE_OK" in out
    assert "KVSEQ_PATH_OK" in out
