"""Beyond-paper optimization levers: chunked prefill, fp8 KV cache,
dp_only policy, int8-EF gradient compression math."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ShapeConfig
from repro.configs.registry import get_reduced
from repro.launch.mesh import auto_mesh
from repro.models import transformer as T
from repro.models.registry import decode_geometry

KEY = jax.random.PRNGKey(0)


def _setup(cfg, B=2, S=24):
    params = T.init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    g = decode_geometry(cfg, ShapeConfig("t", 64, B, "decode"))
    st = T.make_decode_state(cfg, B, g["num_blocks"],
                             g["max_blocks_per_seq"], dtype=jnp.float32)
    if "block_table" in st:
        st["block_table"] = jnp.arange(
            B * g["max_blocks_per_seq"], dtype=jnp.int32).reshape(B, -1)
    return params, toks, st


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "qwen2-moe-a2.7b"])
def test_chunked_prefill_matches_regular(arch):
    cfg = get_reduced(arch)
    params, toks, st = _setup(cfg)
    cl = jnp.array([24, 17], jnp.int32)
    b = {"tokens": toks, "ctx_lens": cl}
    l1, s1 = T.prefill(cfg, params, dict(st), b, rt={"scan_layers": True})
    l2, s2 = T.prefill(cfg, params, dict(st), b,
                       rt={"scan_layers": True, "prefill_chunk": 8})
    np.testing.assert_allclose(l1, l2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(s1["k_pool"]),
                               np.asarray(s2["k_pool"]), atol=2e-2)


def test_fp8_kv_cache_decode_close():
    cfg = get_reduced("qwen2-1.5b")
    cfg8 = cfg.replace(paging=cfg.paging.__class__(
        **{**cfg.paging.__dict__, "cache_dtype": "float8_e4m3fn"}))
    params = T.init_params(cfg, KEY)
    B, S = 2, 20
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    full = T.forward(cfg, params, {"tokens": toks})
    g = decode_geometry(cfg8, ShapeConfig("t", 40, B, "decode"))
    st = T.make_decode_state(cfg8, B, g["num_blocks"], g["max_blocks_per_seq"])
    assert st["k_pool"].dtype == jnp.float8_e4m3fn
    st["block_table"] = jnp.arange(B * g["max_blocks_per_seq"],
                                   dtype=jnp.int32).reshape(B, -1)
    cl = jnp.array([15, 15], jnp.int32)
    lg, st = T.prefill(cfg8, params, st, {"tokens": toks[:, :15],
                                          "ctx_lens": cl})
    st["seq_lens"] = cl + 1
    lg2, _ = T.decode_step(cfg8, params, st, toks[:, 15])
    scale = float(jnp.abs(full).max())
    assert float(jnp.abs(lg2 - full[:, 15]).max()) < 0.15 * max(scale, 1.0)


def test_dp_only_policy_matches_2d():
    """Same math under both parallelism policies (8 virtual... 1 device)."""
    from repro.runtime.sharding import make_ctx
    mesh = auto_mesh((1, 1), ("data", "model"))
    cfg = get_reduced("qwen2-1.5b", num_layers=2)
    params = T.init_params(cfg, KEY)
    b = {"tokens": jax.random.randint(KEY, (2, 17), 0, cfg.vocab_size)}
    l2d = T.loss_fn(cfg, params, b, make_ctx(mesh, "2d"))
    ldp = T.loss_fn(cfg, params, b, make_ctx(mesh, "dp_only"))
    np.testing.assert_allclose(float(l2d), float(ldp), rtol=1e-5)


def test_int8_ef_quantize_dequantize_cycle():
    """One-device check of the compression arithmetic: q/dq error is
    bounded by scale, and error feedback removes bias over steps."""
    rng = np.random.default_rng(0)
    g = rng.normal(size=512).astype(np.float32) * 0.01
    err = np.zeros_like(g)
    acc = np.zeros_like(g)
    acc_exact = np.zeros_like(g)
    for step in range(50):
        gs = g * (1 + 0.1 * rng.normal(size=g.shape).astype(np.float32))
        x = gs + err
        scale = np.abs(x).max() / 127.0 + 1e-20
        q = np.clip(np.round(x / scale), -127, 127)
        deq = q * scale
        err = x - deq
        acc += deq
        acc_exact += gs
    # with EF, accumulated compressed grads track accumulated exact grads
    rel = np.linalg.norm(acc - acc_exact) / np.linalg.norm(acc_exact)
    assert rel < 0.01
