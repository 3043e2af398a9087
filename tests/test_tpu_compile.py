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
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.precision import get_precision, signed
from repro.kernels import engine, kv_pool, tuning
from repro.kernels.act_quant import act_quant, act_quant_signed_grouped
from repro.models import build_model, to_serving, transformer as tfm

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
    """The stacked pool of two layers, as ``kv_pool`` lays it out."""
    return [(s.shape, s.dtype) for s in kv_pool.leaf_specs(
        N_POOL, BS, KV, DH, kv_bits, jnp.bfloat16, stacked=2).values()]


def _split_pool(kv_bits, leaves):
    """``(codes, scales)``: the engine's pool operands (scales None at 16)."""
    return (leaves[0], None) if kv_bits == 16 else tuple(leaves)


def _compile_paged_attention(compile_for, kv_bits, kv=KV, dh=DH):
    def attend(q, pt, pos, layer, *pool):
        return engine.paged_attention(q, *_split_pool(kv_bits, pool), pt, pos,
                                      layer=layer, kv_bits=kv_bits,
                                      backend=engine.BACKEND_PALLAS,
                                      interpret=False)
    pool = [(s.shape, s.dtype) for s in kv_pool.leaf_specs(
        N_POOL, BS, kv, dh, kv_bits, jnp.bfloat16, stacked=2).values()]
    return compile_for(attend, ((SLOTS, kv, G, dh), jnp.bfloat16),
                       ((SLOTS, N_BLOCKS), jnp.int32),
                       ((SLOTS,), jnp.int32), ((), jnp.int32), *pool), pool


def test_paged_attention_compiles_kv8(compile_for):
    compiled, _ = _compile_paged_attention(compile_for, 8)
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_attention_compiles_packed_rows(compile_for):
    """Rows of 3 KV heads of 32 (192 lanes) are stored two positions to a
    384-lane row; Mosaic takes the kernel's lane slices of them."""
    compiled, pool = _compile_paged_attention(compile_for, 8, kv=3, dh=32)
    assert pool[0][0][-2:] == (BS // 2, 2 * 2 * 3 * 32)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv_bits", [16, 8])
def test_fused_decode_compiles(compile_for, kv_bits):
    pcfg = signed(get_precision("fp32"))

    def fused(q, pt, pos, slot_map, wo, layer, *pool):
        return engine.fused_paged_decode(
            q, *_split_pool(kv_bits, pool), pt, pos, slot_map, {"qw": wo},
            pcfg, layer=layer, kv_bits=kv_bits, dtype=jnp.bfloat16,
            backend=engine.BACKEND_PALLAS, interpret=False)
    with engine.dispatch_trace() as events:
        compiled = compile_for(fused, ((SLOTS, KV, G, DH), jnp.bfloat16),
                               ((SLOTS, N_BLOCKS), jnp.int32),
                               ((SLOTS,), jnp.int32), ((SLOTS,), jnp.int32),
                               ((H * DH, D), jnp.bfloat16), ((), jnp.int32),
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


# ---------------------------------------------------------------------------
# the serving programs keep the paged pool in place
# ---------------------------------------------------------------------------
LIVE, CHUNK = 32, 32          # decode: 32 live slots of 64; 32-token chunks
_INSTR = re.compile(r"\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\](\{[^}]*\})? "
                    r"([\w-]+)\(")
_ALIAS = re.compile(r"\{[\d,]*\}: \((\d+), \{\}")


_HLO_TYPE = {"int8": "s8", "float32": "f32", "bfloat16": "bf16"}


def _pool_sized_moves(hlo: str, counts: set) -> list:
    """Instructions, fused ones included, that copy, transpose or slice an
    operand of a pool leaf's element type and count (the whole stack or one
    layer): ``counts`` holds ``(HLO type, elements)`` pairs."""
    moves = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m or m.group(4) not in ("copy", "transpose", "dynamic-slice"):
            continue
        n = 1
        for d in filter(None, m.group(2).split(",")):
            n *= int(d)
        if (m.group(1), n) in counts:
            moves.append(line.strip()[:160])
    return moves


def _relaid_async_copies(hlo: str, counts: set) -> list:
    """Pool-sized ``copy-start`` pairs whose two shapes differ in anything
    but the memory space.  XLA may stage a pool small enough for fast
    memory there for the layer loop (``S(1)``); that moves the same tiles
    and is no relayout."""
    bad = []
    for line in hlo.splitlines():
        if " copy-start(" not in line:
            continue
        shapes = re.findall(r"(\w+\[[\d,]*\])(\{[^}]*\})", line)[:2]
        dims = re.findall(r"\[([\d,]*)\]", shapes[0][0])[0] if shapes else ""
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        strip = lambda lay: re.sub(r"S\(\d+\)", "", lay)
        kind = shapes[0][0].split("[")[0] if shapes else ""
        if (kind, n) in counts and \
                strip(shapes[0][1]) != strip(shapes[1][1]):
            bad.append(line.strip()[:160])
    return bad


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_paged_programs_update_the_pool_in_place(one_chip, no_compile_cache,
                                                  tmp_path, monkeypatch,
                                                  program):
    """The batcher's decode program (fused kernel, 32 live slots of 64) and
    its 32-token chunk program, at smollm-135m's widths with int8 KV on the
    engine's Pallas path: the pool argument, pinned to the layout the
    kernels read, is aliased to the pool result, and no instruction copies,
    transposes or slices a pool-sized operand.  Before the pool was kept in
    the kernels' layout and carried through the layer loop, each layer
    relaid every leaf (four copies per code leaf) and the scan sliced and
    wrote back a layer of each."""
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "tuning.json"))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _check_in_place(one_chip, program, 8)


def test_packed_pool_chunk_program_updates_in_place(one_chip,
                                                    no_compile_cache,
                                                    tmp_path, monkeypatch):
    """The same at kv4, whose 192-lane rows are stored two positions to a
    row, so each chunk write merges into rows it shares with other
    positions: still in place, with no pool-sized move.  (The kv4 decode
    program is not compiled here: the kernel's nibble unpack does not lower
    under Mosaic.)"""
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "tuning.json"))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _check_in_place(one_chip, "chunk", 4)


@pytest.mark.parametrize("num_blocks", [N_POOL, 8321])
@pytest.mark.parametrize("kv_bits", [16, 8, 4])
def test_pool_leaves_default_to_the_kernels_layout(one_chip, no_compile_cache,
                                                   kv_bits, num_blocks):
    """The chip's own default layout for every pool leaf is the row-major one
    the kernels read and the programs pin, at the test's pool and at the
    benchmark's (8,321 blocks of 16).  A program loaded from the persistent
    compile cache hands its pool back in the default layout, and the next
    program refuses an argument in any layout but the pinned one: a scale
    leaf of 96 lanes, whose default puts the block dim minor, failed so on
    a TPU v5e."""
    cfg = get_config("smollm-135m", precision="8xT")
    pool = jax.eval_shape(lambda: tfm.make_pool(cfg, num_blocks, BS,
                                                kv_bits))
    for leaf in jax.tree_util.tree_leaves(pool):
        arg = jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=one_chip)
        compiled = jax.jit(lambda x: x + 1).lower(arg).compile()
        layout = compiled.input_formats[0][0].layout
        assert layout.major_to_minor == tuple(range(leaf.ndim)), \
            (leaf.shape, leaf.dtype, layout)


def _check_in_place(one_chip, program, kv_bits):
    tuning.reset()
    cfg = get_config("smollm-135m", precision="8xT")
    model = build_model(cfg)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = on_chip(jax.eval_shape(
        lambda: to_serving(model.init(jax.random.PRNGKey(0)), cfg)))
    pool = on_chip(jax.eval_shape(
        lambda: tfm.make_pool(cfg, N_POOL, BS, kv_bits)))
    fmt = kv_pool.formats(pool, one_chip)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    if program == "decode":
        def step(p, t, pool, pt, pos, sm):
            lg, new = model.decode_step_paged(p, t[sm], pool, pt[sm],
                                              pos[sm], kv_bits, fused=True)
            return lg, jnp.argmax(lg[:, 0], axis=-1), new
        args = (params, i32(2 * LIVE, 1), pool, i32(2 * LIVE, N_BLOCKS),
                i32(2 * LIVE), i32(LIVE))
        outs = (None, None, fmt)
    else:
        def step(p, t, pool, pt, pos):
            return model.prefill_chunk_paged(p, t, pool, pt, pos, kv_bits)
        args = (params, i32(1, CHUNK), pool, i32(1, N_BLOCKS), i32())
        outs = (None, fmt)
    ins = (None, None, fmt) + (None,) * (len(args) - 3)
    try:
        compiled = jax.jit(step, donate_argnums=(2,), in_shardings=ins,
                           out_shardings=outs).lower(*args).compile()
    finally:
        tuning.reset()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo

    leaves = jax.tree_util.tree_leaves(pool)
    counts = {(_HLO_TYPE[leaf.dtype.name], n) for leaf in leaves
              for n in (leaf.size, leaf.size // cfg.n_periods)}
    assert _pool_sized_moves(hlo, counts) == []
    assert _relaid_async_copies(hlo, counts) == []

    first = len(jax.tree_util.tree_leaves(args[:2]))
    aliased = {int(i) for i in _ALIAS.findall(
        hlo.split("input_output_alias=", 1)[1].split("entry_computation", 1)[0])}
    assert set(range(first, first + len(leaves))) <= aliased, aliased
    laid_out = sum(kv_pool.laid_out_bytes(leaf.shape, leaf.dtype)
                   for leaf in leaves)
    assert compiled.memory_analysis().alias_size_in_bytes == laid_out
