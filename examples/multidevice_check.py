"""Distributed-correctness check on 8 virtual devices (CPU).

Verifies, with real shardings active:
  1. sharded (DP×TP, FSDP) train step == single-device step (loss/grads),
  2. MoE expert-parallel shard_map path == local ragged path,
  3. paged-decode shard_map island == unsharded decode,
  4. int8 error-feedback compressed gradients ≈ exact gradients, and the
     error buffer absorbs the residual.

    PYTHONPATH=src python examples/multidevice_check.py
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp

from repro.configs.registry import get_reduced
from repro.launch.mesh import auto_mesh
from repro.models import transformer as T
from repro.models.moe import moe_apply, moe_init
from repro.optim.adamw import AdamWConfig, init_opt_state
from repro.runtime.sharding import make_ctx, param_shardings
from repro.runtime.train_loop import (init_error_buffer,
                                      make_compressed_grad_fn,
                                      make_train_step)


def check(name, a, b, tol=3e-2):
    err = max(float(jnp.abs(x - y).max()) for x, y in
              zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    status = "OK " if err <= tol else "FAIL"
    print(f"  [{status}] {name}: max_err={err:.2e}")
    assert err <= tol, name
    return err


def main():
    mesh = auto_mesh((4, 2), ("data", "model"))
    ctx = make_ctx(mesh)
    key = jax.random.PRNGKey(0)

    print("== 1. sharded train step vs single device ==")
    cfg = get_reduced("qwen2-1.5b", num_layers=2, num_heads=4, num_kv_heads=2)
    params = T.init_params(cfg, key)
    batch = {"tokens": jax.random.randint(key, (8, 33), 0, cfg.vocab_size)}
    opt_cfg = AdamWConfig(lr=1e-3)
    opt = init_opt_state(params, opt_cfg)
    step1 = jax.jit(make_train_step(cfg, opt_cfg, None, {"scan_layers": True}))
    p1, o1, m1 = step1(params, opt, batch)
    ps = jax.device_put(params, param_shardings(ctx, params, cfg))
    step2 = jax.jit(make_train_step(cfg, opt_cfg, ctx, {"scan_layers": True}))
    p2, o2, m2 = step2(ps, init_opt_state(ps, opt_cfg), batch)
    check("loss", m1["loss"], m2["loss"], 1e-2)
    check("updated params", p1, p2)

    print("== 2. MoE EP shard_map vs local ==")
    mcfg = get_reduced("qwen2-moe-a2.7b", num_experts=8, moe_top_k=2)
    mp = moe_init(key, mcfg, ep=2)
    x = jax.random.normal(key, (8, 16, mcfg.d_model))
    y_local = moe_apply(mcfg, mp, x, None)
    y_ep = moe_apply(mcfg, mp, x, ctx)
    check("moe outputs", y_local, y_ep)

    print("== 3. paged-decode island vs unsharded ==")
    dcfg = get_reduced("qwen2-1.5b", num_layers=2, num_heads=4, num_kv_heads=2)
    dparams = T.init_params(dcfg, key)
    B, MB = 8, 4
    st = T.make_decode_state(dcfg, B, B * MB, MB, dtype=jnp.float32)
    # island semantics (DESIGN.md §4): block ids are LOCAL per dp shard;
    # the unsharded reference uses the equivalent GLOBAL numbering (local
    # id + shard * pool_shard_size) so both address the same physical
    # blocks of the same pool.
    st["seq_lens"] = jnp.full((B,), 9, jnp.int32)
    toks = jax.random.randint(key, (B,), 0, dcfg.vocab_size)
    bt_global = jnp.arange(B * MB, dtype=jnp.int32).reshape(B, MB)
    bt_local = jnp.tile(jnp.arange(2 * MB, dtype=jnp.int32).reshape(2, MB),
                        (4, 1))
    l1, s1 = T.decode_step(dcfg, dparams, {**st, "block_table": bt_global},
                           toks, None)
    l2, s2 = T.decode_step(dcfg, dparams, {**st, "block_table": bt_local},
                           toks, ctx)
    check("decode logits", l1, l2)
    check("decode pools", s1["k_pool"], s2["k_pool"])

    print("== 4. int8-EF compressed gradients ==")
    ctx_nofsdp = make_ctx(mesh).__class__(mesh=mesh, dp_axes=("data",),
                                          tp_axis="model", fsdp=False)
    gfn = jax.jit(make_compressed_grad_fn(cfg, ctx_nofsdp,
                                          {"scan_layers": True}))
    err0 = init_error_buffer(ctx_nofsdp, params)
    loss_c, g_c, err1 = gfn(params, batch, err0)
    loss_e, g_e = jax.value_and_grad(
        lambda p: T.loss_fn(cfg, p, batch, None, {"scan_layers": True}))(params)
    check("compressed loss", loss_c, loss_e, 1e-2)
    gnorm = float(jnp.sqrt(sum(jnp.sum(x**2) for x in jax.tree.leaves(g_e))))
    diff = float(jnp.sqrt(sum(jnp.sum((a - b)**2) for a, b in
                              zip(jax.tree.leaves(g_c), jax.tree.leaves(g_e)))))
    enorm = float(jnp.abs(err1).max())
    print(f"  [INFO] |g_c - g_e|/|g_e| = {diff/gnorm:.4f} "
          f"(int8 quantization noise), err-buffer max {enorm:.2e}")
    assert diff / gnorm < 0.25
    assert enorm > 0           # residual captured for next step
    print("\nall distributed-correctness checks passed")


if __name__ == "__main__":
    main()
