"""Compile the serving path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers and compiles for a v5e chip that is
described, not attached, which is where Mosaic refuses a block shape the
TPU's (8, 128) tiling rule forbids or more VMEM than a kernel may use —
faults that interpret mode cannot see.  Shapes are qwen1.5-0.5b's
published widths (16 x 64 heads, KV 16 or the Opt-GQA grouping 2, 16-token
pages, a 256-token chunk, the 1024 x 2816 MLP); the kernels read a
two-layer stacked ``[L, NB, KV, BS, D]`` pool at a traced layer.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and xdist workers import every file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.core.alibi import alibi_slopes
from repro.kernels.flash_attention import flash_attention, flash_attention_chunk
from repro.kernels.gptq_matmul import gptq_matmul
from repro.kernels.paged_attention import paged_attention
from repro.kernels.paged_attention_quant import paged_attention_quant

H, D, BS, NB, MB, W, SLOTS, L = 16, 64, 16, 4096, 64, 256, 8, 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """A shape factory placed on one described v5e chip, with the
    persistent compile cache off (a described-chip compile is written to
    it but can never be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one = SingleDeviceSharding(topo.devices[0])

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one)
    yield shape
    jax.config.update("jax_enable_compilation_cache", was)


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


_COMPILED = {}


def _built(chip, build, args):
    """The compiled text of one kernel case, compiled once per module."""
    key = (build.__name__, args)
    if key not in _COMPILED:
        _COMPILED[key] = build(chip, *args)
    return _COMPILED[key]


def _decode(chip, kv, dtype, alibi):
    q = chip((SLOTS, H, D), jnp.bfloat16)
    pool = chip((L, NB, kv, BS, D), dtype)
    bt = chip((SLOTS, MB), jnp.int32)
    sl = chip((SLOTS,), jnp.int32)
    layer = chip((), jnp.int32)
    slopes = alibi_slopes(H) if alibi else None
    if dtype == jnp.int8:
        sc = chip((L, NB, kv), jnp.float32)
        return _hlo(lambda q, k, ks, v, vs, ly, bt, sl: paged_attention_quant(
            q, k, ks, v, vs, ly, bt, sl, slopes, interpret=False),
            q, pool, sc, pool, sc, layer, bt, sl)
    return _hlo(lambda q, k, v, ly, bt, sl: paged_attention(
        q, k, v, ly, bt, sl, slopes, interpret=False),
        q, pool, pool, layer, bt, sl)


def _chunk(chip, kv, dtype, alibi):
    q = chip((1, W, H, D), jnp.bfloat16)
    raw = chip((1, W, kv, D), jnp.bfloat16)
    pool = chip((L, NB, kv, BS, D), dtype)
    bt = chip((1, MB), jnp.int32)
    off = chip((), jnp.int32)
    slopes = alibi_slopes(H) if alibi else None
    sc = chip((L, NB, kv), jnp.float32) if dtype == jnp.int8 else None

    def f(q, k, v, ly, bt, off, tl, kr, vr, ks, vs):
        return flash_attention_chunk(q, k, v, ly, bt, off, tl, kr, vr,
                                     slopes, k_scales=ks, v_scales=vs,
                                     interpret=False)
    return _hlo(f, q, pool, pool, off, bt, off, off, raw, raw, sc, sc)


def _prefill(chip, kv, dtype, alibi):
    q = chip((1, W, H, D), dtype)
    k = chip((1, W, kv, D), dtype)
    slopes = alibi_slopes(H) if alibi else None
    return _hlo(lambda q, k, v: flash_attention(
        q, k, v, slopes, causal=True, interpret=False), q, k, k)


def _gptq(chip, group_size, m):
    K, N = 1024, 2816                          # the MLP's up projection
    x = chip((m, K), jnp.bfloat16)
    qw = chip((K // 8, N), jnp.int32)
    s = chip((K // group_size, N), jnp.float32)
    return _hlo(lambda x, qw, s, z: gptq_matmul(x, qw, s, z, interpret=False),
                x, qw, s, s)


@pytest.mark.parametrize("build,args", [
    (_decode, (16, jnp.bfloat16, False)),
    (_decode, (2, jnp.bfloat16, True)),
    (_decode, (16, jnp.float32, False)),
    (_decode, (2, jnp.int8, False)),
    (_decode, (16, jnp.int8, True)),
    (_chunk, (16, jnp.bfloat16, False)),
    (_chunk, (2, jnp.bfloat16, True)),
    (_chunk, (2, jnp.int8, False)),
    (_chunk, (16, jnp.int8, False)),
    (_prefill, (2, jnp.bfloat16, True)),
    (_prefill, (16, jnp.bfloat16, False)),
    (_gptq, (32, SLOTS)),
    (_gptq, (128, SLOTS)),
    (_gptq, (32, W)),
], ids=["decode-kv16", "decode-kv2-alibi", "decode-kv16-f32",
        "decode-int8-kv2", "decode-int8-kv16-alibi", "chunk-kv16",
        "chunk-kv2-alibi", "chunk-int8-kv2", "chunk-int8-kv16",
        "prefill-kv2-alibi", "prefill-kv16", "gptq-g32", "gptq-g128",
        "gptq-g32-chunk"])
def test_kernel_compiles_for_v5e(chip, build, args):
    assert "tpu_custom_call" in _built(chip, build, args)


@pytest.mark.parametrize("build,args,name", [
    (_decode, (16, jnp.bfloat16, False), "paged_attention"),
    (_decode, (2, jnp.int8, False), "paged_attention_quant"),
    (_chunk, (2, jnp.int8, False), "flash_attention_chunk"),
    (_prefill, (16, jnp.bfloat16, False), "flash_attention"),
    (_gptq, (128, SLOTS), "gptq_matmul"),
], ids=["paged_attention", "paged_attention_quant", "flash_attention_chunk",
        "flash_attention", "gptq_matmul"])
def test_kernel_hlo_names_are_stable(chip, build, args, name):
    """Each kernel's instruction carries the name its ``pallas_call``
    gives, whatever wrapper calls it: the names the benchmark's trace
    reduction finds kernels by."""
    calls = re.findall(r"^\s*(?:ROOT )?%([\w\-]+?)(?:\.\d+)* = .*"
                       r'custom_call_target="tpu_custom_call"',
                       _built(chip, build, args), re.M)
    assert calls == [name]


def test_decode_walk_at_cell_shapes(chip):
    """The offline cell's decode kernel — 64 slots, 2 KV heads, 128-token
    int8 pages of head dim 128, 48 table entries, a 28-layer pool of 1024
    pages — takes the live-page walk with several pages per block, its
    double-buffered page buffers fit the VMEM budget, and it compiles for
    the chip (Mosaic refuses a kernel whose scratch overflows VMEM)."""
    from repro.kernels.paged_attention import BUFFER_VMEM, pages_per_block
    nl, nb, kv, bs, d, h, slots, mb = 28, 1024, 2, 128, 128, 12, 64, 48
    P = pages_per_block(kv, bs, d, 1, mb)
    assert 1 < P <= mb
    assert 4 * P * kv * bs * d <= BUFFER_VMEM      # int8 tiles: no padding
    pool = chip((nl, nb, kv, bs, d), jnp.int8)
    sc = chip((nl, nb, kv), jnp.float32)
    hlo = _hlo(lambda q, k, ks, v, vs, ly, bt, sl: paged_attention_quant(
        q, k, ks, v, vs, ly, bt, sl, interpret=False),
        chip((slots, h, d), jnp.bfloat16), pool, sc, pool, sc,
        chip((), jnp.int32), chip((slots, mb), jnp.int32),
        chip((slots,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kv,kv_dtype,quant", [
    (16, "bf16", None), (2, "int8", "rtn-int4")],
    ids=["kv16-bf16", "kv2-int8-w4a16"])
def test_unified_step_compiles_with_kernels(chip, kv, kv_dtype, quant):
    """The serving engine's one-dispatch step, two layers deep at the
    published widths, compiles for the chip with every kernel in it."""
    from repro.models import transformer as T
    from repro.models.quantize import quantize_params_rtn
    cfg = get_config("qwen1.5-0.5b").replace(num_layers=2, num_kv_heads=kv)
    rt = {"use_pallas": True, "interpret": False}

    def params_fn():
        p = T.init_params(cfg, jax.random.PRNGKey(0))
        return quantize_params_rtn(p, cfg, group_size=32) if quant else p
    shapes = jax.tree.map(lambda a: chip(a.shape, a.dtype),
                          (jax.eval_shape(params_fn),
                           jax.eval_shape(lambda: T.make_decode_state(
                               cfg, SLOTS, 512, MB, dtype=jnp.float32,
                               kv_cache_dtype=kv_dtype))))
    n = SLOTS + 1
    sampling = {"keys": chip((n, 2), jnp.uint32),
                "counts": chip((n,), jnp.int32),
                "temps": chip((n,), jnp.float32),
                "top_ks": chip((n,), jnp.int32),
                "top_ps": chip((n,), jnp.float32)}
    i32 = chip((), jnp.int32)
    hlo = _hlo(lambda p, s, t, sp, a, c, cbt, off, tl: T.unified_step(
        cfg, p, s, t, sp, a, c, cbt, off, tl, None, rt),
        *shapes, chip((SLOTS,), jnp.int32), sampling, chip((SLOTS,), jnp.bool_),
        chip((1, W), jnp.int32), chip((1, MB), jnp.int32), i32, i32)
    assert "tpu_custom_call" in hlo


# ops that move no pool bytes of their own: views, loop plumbing, inputs
_NO_BYTES = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
             "conditional", "call", "opt-barrier"}


def _big_int8_ops(hlo: str, min_elems: int):
    """(name, opcode, shape, fused root opcode) of every compiled
    instruction that outputs an int8 array of at least ``min_elems``
    elements and is not a view or loop plumbing."""
    roots, comp = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            comp = head.group(1)
        root = re.match(r"^\s*ROOT %[\w.\-]+ = .*? ([\w\-]+)\(", line)
        if root and comp:
            roots[comp] = root.group(1)
    found = []
    for line in hlo.splitlines():
        m = re.match(r"^\s*(?:ROOT )?%([\w.\-]+) = (\([^=]*?\)|\S+) "
                     r"([\w\-]+)\(", line)
        if not m:
            continue
        name, shape, op = m.groups()
        if op in _NO_BYTES:
            continue
        for dims in re.findall(r"s8\[([\d,]*)\]", shape):
            n = 1
            for d in filter(None, dims.split(",")):
                n *= int(d)
            if n >= min_elems:
                called = re.search(r"calls=%([\w.\-]+)", line)
                found.append((name, op, shape,
                              roots.get(called.group(1)) if called else None))
    return found


@pytest.mark.parametrize("path", ["decode", "chunk"])
def test_int8_pool_read_and_written_in_place(chip, path):
    """Two layers of a serving path at the offline cell's widths — the
    decode write + ``paged_attention_quant``, or a 512-token prompt
    chunk's write + ``flash_attention_chunk`` — compiled for the chip:
    the only compiled op that outputs an int8 array as large as one
    layer's pool slice is the in-place scatter into the stacked pool, so
    no layer slice is copied, relaid out, sliced out or written back."""
    from repro.core.kv_quant import KVCache, kv_write_decode, kv_write_prefill
    nl, nb, bs, kv, d, h, slots, mb, w = 2, 1024, 128, 2, 128, 12, 64, 48, 512
    pool = chip((nl, nb, kv, bs, d), jnp.int8)
    sc = chip((nl, nb, kv), jnp.float32)
    rows = slots if path == "decode" else 1

    def step(q, kn, vn, kp, ks, vp, vs, bt, sl):
        def layer(li, carry):
            cache, o = carry
            if path == "decode":
                cache = kv_write_decode(cache, li, kn, vn, bt, sl - 1)
                o = paged_attention_quant(
                    q + o, cache.k, cache.k_scale, cache.v, cache.v_scale,
                    li, bt, sl, interpret=False)
            else:
                cache = kv_write_prefill(cache, li, kn, vn, bt, sl + w,
                                         pos_offset=sl[0])
                o = flash_attention_chunk(
                    q + o, cache.k, cache.v, li, bt, sl[0], sl[0] + w, kn,
                    vn, k_scales=cache.k_scale, v_scales=cache.v_scale,
                    interpret=False)
            return cache, o
        cache, o = jax.lax.fori_loop(
            0, nl, layer, (KVCache(kp, vp, ks, vs), jnp.zeros_like(q)))
        return o, cache

    lead = (slots,) if path == "decode" else (1, w)
    hlo = jax.jit(step, donate_argnums=(3, 4, 5, 6)).lower(
        chip(lead + (h, d), jnp.bfloat16), chip(lead + (kv, d), jnp.bfloat16),
        chip(lead + (kv, d), jnp.bfloat16), pool, sc, pool, sc,
        chip((rows, mb), jnp.int32), chip((rows,), jnp.int32)
    ).compile().as_text()
    assert "tpu_custom_call" in hlo
    big = _big_int8_ops(hlo, nb * kv * bs * d)
    stacked = f"s8[{nl},{nb},{kv},{bs},{d}]"
    bad = [b for b in big
           if not (b[2].startswith(stacked)
                   and "scatter" in (b[1], b[3]))]
    assert not bad, bad
    assert big                   # the pools' scatters themselves are seen
