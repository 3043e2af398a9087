"""Compile the main-path kernels for a TPU v5e at smollm-135m's published
widths, without a chip.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: blocks whose last two dims break Mosaic's (8, 128) tiling, or more
fast memory than a kernel may use.  These tests lower the engine's Pallas
dispatches for a *described* v5e chip and compile them with the installed
TPU compiler; nothing runs.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.precision import get_precision, signed
from repro.kernels import engine, tuning
from repro.kernels.act_quant import act_quant, act_quant_signed_grouped

CFG = get_config("smollm-135m")
H, KV, DH, D = CFG.n_heads, CFG.n_kv_heads, CFG.dh, CFG.d_model
G = H // KV
SLOTS, BS, N_BLOCKS = 8, 16, 10          # 8 slots, 160-position pages
N_POOL = SLOTS * N_BLOCKS + 1            # + the reserved null block
MATMUL_SHAPES = sorted(engine.model_matmul_shapes(CFG))   # (N, K) pairs


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_for(one_chip, no_compile_cache, tmp_path, monkeypatch):
    """``compile_for(fn, *shapes)`` lowers ``fn`` for one v5e chip, with an
    empty tuning cache (the default, cache-miss tiles), and compiles it."""
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "tuning.json"))
    tuning.reset()

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile()
    yield run
    tuning.reset()


def _packed_shapes(precision, n, k):
    """(wt_packed, scale) shapes/dtypes of a serving-packed (K, N) weight."""
    w = jax.random.normal(jax.random.PRNGKey(0), (k, n), jnp.float32)
    pw = engine.pack_weight(w, get_precision(precision))
    return pw, ((pw.wt_packed.shape, pw.wt_packed.dtype),
                (pw.scale.shape, pw.scale.dtype))


@pytest.mark.parametrize("m", [8, 256])
@pytest.mark.parametrize("precision", ["2xT", "1x1", "4x4", "8xT"])
def test_qmatmul_compiles_at_default_tiles(compile_for, precision, m):
    pcfg = signed(get_precision(precision))
    for n, k in MATMUL_SHAPES:
        pw, (wt_shape, scale_shape) = _packed_shapes(precision, n, k)
        assert engine.storage_kind(pw) != engine.K_CODES, (precision, n, k)

        def matmul(x, wt, scale, pw=pw):
            return engine.qmatmul(x, pw._replace(wt_packed=wt, scale=scale),
                                  pcfg, backend=engine.BACKEND_PALLAS,
                                  interpret=False)
        with engine.dispatch_trace() as events:
            compiled = compile_for(matmul, ((m, k), jnp.bfloat16),
                                   wt_shape, scale_shape)
        assert [e.impl_backend for e in events] == [engine.BACKEND_PALLAS]
        assert "tpu_custom_call" in compiled.as_text(), (precision, n, k)


@pytest.mark.parametrize("precision", ["2xT", "4x4"])
def test_int_kernels_compile_under_highest_matmul_precision(compile_for,
                                                           precision):
    """A global ``jax_default_matmul_precision="highest"`` must not reach
    the kernels' int8 dots (Mosaic refuses an fp32 contraction of int8)."""
    pcfg = signed(get_precision(precision))
    n, k = D, CFG.d_ff
    pw, (wt_shape, scale_shape) = _packed_shapes(precision, n, k)

    def matmul(x, wt, scale):
        return engine.qmatmul(x, pw._replace(wt_packed=wt, scale=scale), pcfg,
                              backend=engine.BACKEND_PALLAS, interpret=False)
    with jax.default_matmul_precision("highest"):
        compiled = compile_for(matmul, ((8, k), jnp.bfloat16), wt_shape,
                               scale_shape)
    assert "tpu_custom_call" in compiled.as_text()


def _pool_shapes(kv_bits):
    if kv_bits == 16:
        leaf = ((N_POOL, BS, KV, DH), jnp.bfloat16)
        return [leaf, leaf]
    codes = ((N_POOL, BS, KV, DH), jnp.int8)
    scale = ((N_POOL, BS, KV, 1), jnp.float32)
    return [codes, scale, codes, scale]


def _split_pool(kv_bits, leaves):
    if kv_bits == 16:
        k, v = leaves
        return k, None, v, None
    return leaves


def test_paged_attention_compiles_kv8(compile_for):
    def attend(q, pt, pos, *pool):
        return engine.paged_attention(q, *_split_pool(8, pool), pt, pos,
                                      kv_bits=8, backend=engine.BACKEND_PALLAS,
                                      interpret=False)
    compiled = compile_for(attend, ((SLOTS, KV, G, DH), jnp.bfloat16),
                           ((SLOTS, N_BLOCKS), jnp.int32),
                           ((SLOTS,), jnp.int32), *_pool_shapes(8))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv_bits", [16, 8])
def test_fused_decode_compiles(compile_for, kv_bits):
    pcfg = signed(get_precision("fp32"))

    def fused(q, pt, pos, slot_map, wo, *pool):
        return engine.fused_paged_decode(
            q, *_split_pool(kv_bits, pool), pt, pos, slot_map, {"qw": wo},
            pcfg, kv_bits=kv_bits, dtype=jnp.bfloat16,
            backend=engine.BACKEND_PALLAS, interpret=False)
    with engine.dispatch_trace() as events:
        compiled = compile_for(fused, ((SLOTS, KV, G, DH), jnp.bfloat16),
                               ((SLOTS, N_BLOCKS), jnp.int32),
                               ((SLOTS,), jnp.int32), ((SLOTS,), jnp.int32),
                               ((H * DH, D), jnp.bfloat16),
                               *_pool_shapes(kv_bits))
    assert [e.impl_backend for e in events] == [engine.BACKEND_PALLAS]
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m", [8, 256])
def test_act_quant_compiles(compile_for, m):
    for f in (D, CFG.d_ff):
        compiled = compile_for(lambda x: act_quant(x, bits=2),
                               ((m, f), jnp.float32))
        assert "tpu_custom_call" in compiled.as_text()
        compiled = compile_for(
            lambda x, s: act_quant_signed_grouped(x, s, bits=8),
            ((m, f), jnp.bfloat16), ((m, 1), jnp.float32))
        assert "tpu_custom_call" in compiled.as_text()
