"""Precision-dispatch engine: registry dispatch, autotuner cache round-trip,
and tuned-kernel bit-exactness vs the ref.py oracles for every weight family.
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import packing
from repro.core.precision import W_BINARY, W_INT, W_TERNARY, get_precision
from repro.kernels import engine, ref, tuning

RNG = np.random.default_rng(7)


def _codes(shape, bits):
    qmax = (1 << (bits - 1)) - 1
    return jnp.asarray(RNG.integers(-qmax, qmax + 1, size=shape).astype(np.int8))


@pytest.fixture()
def tmp_cache(tmp_path, monkeypatch):
    path = tmp_path / "tuning.json"
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(path))
    tuning.reset()
    yield path
    tuning.reset()


# ---------------------------------------------------------------------------
# registry dispatch
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name,kind,impl_pallas", [
    ("2xT", W_TERNARY, "_ternary_pallas"),
    ("8xT", W_TERNARY, "_ternary_pallas"),
    ("4x4", W_INT, "_int_packed_pallas"),
    ("2x2", W_INT, "_int_packed_pallas"),
    ("1x1", W_BINARY, "_binary_xnor_pallas"),
])
def test_registry_picks_kernel_per_config(name, kind, impl_pallas):
    cfg = get_precision(name)
    w = jnp.asarray(RNG.normal(size=(256, 128)).astype(np.float32))
    pw = engine.pack_weight(w, cfg)
    assert engine.storage_kind(pw) == kind
    a_bits = cfg.a_bits
    fn = engine.resolve(kind, a_bits, pw.bits, engine.BACKEND_PALLAS)
    assert fn.__name__ == impl_pallas
    # the xla backend always resolves too (CPU fallback)
    assert engine.resolve(kind, a_bits, pw.bits, engine.BACKEND_XLA)


def test_registry_unpacked_and_fallbacks():
    # 3x3 stores unpacked int8 codes -> "codes" kind, xla impl even when
    # the pallas backend is requested
    cfg = get_precision("3x3")
    pw = engine.pack_weight(
        jnp.asarray(RNG.normal(size=(256, 128)).astype(np.float32)), cfg)
    assert engine.storage_kind(pw) == engine.K_CODES
    assert engine.resolve(engine.K_CODES, 3, 3,
                          engine.BACKEND_PALLAS).__name__ == "_codes_xla"
    # binary weights with 8-bit acts have no XNOR PE -> dequant fallback
    assert engine.resolve(W_BINARY, 8, 1,
                          engine.BACKEND_PALLAS).__name__ == "_binary_dequant_xla"
    with pytest.raises(KeyError):
        engine.resolve("nope", 0, 0, engine.BACKEND_PALLAS)


def test_qmatmul_rejects_float_config():
    cfg = get_precision("2xT")
    pw = engine.pack_weight(
        jnp.asarray(RNG.normal(size=(128, 128)).astype(np.float32)), cfg)
    with pytest.raises(ValueError):
        engine.qmatmul(_codes((4, 128), 8), pw, get_precision("fp32"))


# ---------------------------------------------------------------------------
# bit-exactness vs the ref oracles (binary / ternary / 2 / 4 / 8-bit)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bits", [2, 4, 8])
def test_int_packed_exact_vs_oracle(bits, tmp_cache):
    m, n, k = 24, 128, 256
    x = _codes((m, k), 8)
    wt_codes = _codes((n, k), bits)
    wt_packed = packing.pack(wt_codes, bits)
    scale = jnp.asarray(RNG.uniform(0.01, 1.0, n).astype(np.float32))
    pw = engine.PackedWeight(wt_packed, scale, bits, W_INT, k)
    want = ref.packed_matmul_ref(x, wt_packed, scale, bits)
    pcfg = get_precision("8x8")  # 8-bit acts; weights taken from pw
    # "tune" (synthetic timings favoring a non-default tile), then dispatch —
    # qmatmul must pick the tuned tiles up from the cache and stay bit-exact
    entry = tuning.autotune(
        m, n, k, kind=W_INT, a_bits=8, w_bits=bits, backend="pallas",
        measure=lambda b: 0.5 if b == (8, 128, 128) else 1.0,
        candidates=[(8, 128, 128)])
    assert tuple(entry["block"]) == (8, 128, 128)
    got = engine.qmatmul(x, pw, pcfg, backend="pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ternary_exact_vs_oracle():
    m, n, k = 16, 128, 256
    cfg = get_precision("2xT")
    w = jnp.asarray(RNG.normal(size=(k, n)).astype(np.float32))
    pw = engine.pack_weight(w, cfg)
    x = _codes((m, k), 8)
    want = ref.ternary_matmul_ref(x, pw.wt_packed, pw.scale)
    got = engine.qmatmul(x, pw, cfg, backend="pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_binary_exact_vs_oracle():
    m, n, k = 8, 128, 256
    cfg = get_precision("1x1")
    w = jnp.asarray(RNG.normal(size=(k, n)).astype(np.float32))
    pw = engine.pack_weight(w, cfg)
    a = RNG.choice([-1, 1], size=(m, k)).astype(np.int8)
    a_packed = packing.pack_binary_pm1(jnp.asarray(a))
    want = ref.binary_matmul_ref(a_packed, pw.wt_packed, k, alpha=pw.scale)
    got = engine.qmatmul(jnp.asarray(a), pw, cfg, backend="pallas",
                         interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_binary_unaligned_k_codes_fallback():
    """K % 32 != 0 binary weights store int8 +/-1 codes; qmatmul must NOT try
    to bit-pack the activations for the XNOR kernel (regression)."""
    m, n, k = 4, 128, 40
    cfg = get_precision("1x1")
    pw = engine.pack_weight(
        jnp.asarray(RNG.normal(size=(k, n)).astype(np.float32)), cfg)
    assert engine.storage_kind(pw) == engine.K_CODES
    x = jnp.asarray(RNG.normal(size=(m, k)).astype(np.float32))
    out = engine.qmatmul(x, pw, cfg)
    assert out.shape == (m, n) and np.all(np.isfinite(np.asarray(out)))
    # cnn serving at 1x1 hits the same path (first conv K = 9)
    import jax

    from repro.models import cnn
    params = cnn.cnn_to_serving(cnn.tinynet_init(jax.random.PRNGKey(0)), "1x1")
    img = jnp.asarray(RNG.uniform(0, 1, (2, 28, 28, 1)).astype(np.float32))
    logits = cnn.tinynet_apply(params, img, precision="1x1")
    assert np.all(np.isfinite(np.asarray(logits)))


def test_stale_cache_entry_evicted_not_double_counted(tmp_cache):
    tuning.autotune(8, 128, 256, kind=W_TERNARY, a_bits=2, w_bits=2,
                    backend="pallas",
                    measure=lambda b: 0.1 if b == (8, 128, 999) else 1.0,
                    candidates=[(8, 128, 999)])   # invalid bk "wins" the sweep
    tuning.reset()
    blk = tuning.get_block_sizes(8, 128, 256, kind=W_TERNARY, a_bits=2,
                                 w_bits=2, backend="pallas")
    # invalid winner -> counted as ONE miss (not hit+miss), safe default out
    assert blk == tuning.fallback_block(8, 128, 256, W_TERNARY, 2)
    assert tuning.stats() == {"hits": 0, "misses": 1, "sweeps": 0}


def test_float_activation_dynamic_quant_path():
    """Float x + quantized-act config -> dynamic symmetric quant, int dot."""
    m, n, k = 8, 128, 128
    cfg = get_precision("8xT")
    w = jnp.asarray(RNG.normal(size=(k, n)).astype(np.float32))
    pw = engine.pack_weight(w, cfg)
    x = jnp.asarray(RNG.normal(size=(m, k)).astype(np.float32))
    got = engine.qmatmul(x, pw, cfg, backend="xla")
    # hand-rolled reference of the same dynamic PER-ROW quantization
    qmax = 127.0
    a_scale = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True),
                          1e-8) / qmax                       # (M, 1)
    xq = jnp.clip(jnp.round(x / a_scale), -qmax, qmax).astype(jnp.int8)
    want = ref.ternary_matmul_ref(xq, pw.wt_packed, pw.scale,
                                  row_scale=a_scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_leading_dims_flattened():
    cfg = get_precision("2xT")
    pw = engine.pack_weight(
        jnp.asarray(RNG.normal(size=(128, 128)).astype(np.float32)), cfg)
    x = _codes((2, 3, 128), 8)
    out = engine.qmatmul(x, pw, cfg, backend="xla")
    assert out.shape == (2, 3, 128)
    flat = engine.qmatmul(x.reshape(-1, 128), pw, cfg, backend="xla")
    np.testing.assert_array_equal(np.asarray(out).reshape(-1, 128),
                                  np.asarray(flat))


class _FakeMesh:
    """Axis-shape stand-in: serving_tune_plan only reads mesh.shape /
    mesh.axis_names, so per-shard key planning is testable without 8 real
    devices."""

    def __init__(self, **shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


def test_serving_tune_keys_per_shard_quantized_act(tmp_cache):
    """tune_serving_shapes(…, mesh=…) must key the cache on the per-shard
    (LOCAL) M that the shard_map step functions dispatch for quantized-act
    configs — a plan keyed only on global M would make every sharded decode
    step a silent tuning-cache miss (regression: the pjit-era plan comment
    called local keys an open item)."""
    import dataclasses

    from repro.configs import get_config
    from repro.core.precision import signed
    from repro.models import reduce_for_smoke

    cfg = dataclasses.replace(reduce_for_smoke(get_config("smollm-135m")),
                              precision="2xT")
    pcfg = signed(get_precision("2xT"))
    mesh = _FakeMesh(data=8, model=1)
    plan = engine.serving_tune_plan(cfg, pcfg, n_slots=8, chunk_size=4,
                                    mesh=mesh)
    # dp=8 shards the 8-slot decode batch down to 1 local row per device
    assert any(m == 1 for (m, _, _) in plan), plan

    # candidates=[]: each sweep times only the default tile, which is legal
    # for every shape in the plan (a fixed extra tile cannot be: N=64 and
    # N=128 admit no common Mosaic-legal bn)
    engine.tune_serving_shapes(cfg, pcfg, n_slots=8, chunk_size=4, mesh=mesh,
                               candidates=[], iters=1)
    for (m, n, k) in plan:
        assert tuning.lookup(m, n, k, kind=W_TERNARY, a_bits=2, w_bits=2,
                             backend="pallas") is not None, (m, n, k)
    # dispatch-time lookup at the local decode bucket is a HIT, not a miss
    tuning.reset()
    n, k = 128, 128      # wq shard shape of the reduced config at tp=1
    tuning.get_block_sizes(1, n, k, kind=W_TERNARY, a_bits=2,
                           w_bits=2, backend="pallas")
    assert tuning.stats() == {"hits": 1, "misses": 0, "sweeps": 0}


# ---------------------------------------------------------------------------
# tuner cache round-trip
# ---------------------------------------------------------------------------
def test_tuning_cache_roundtrip(tmp_cache):
    calls = []

    def fake_measure(block):
        calls.append(block)
        return 1.0 if block != (16, 128, 256) else 0.5

    entry = tuning.autotune(8, 128, 256, kind=W_TERNARY, a_bits=2, w_bits=2,
                            backend="pallas", measure=fake_measure,
                            candidates=[(8, 128, 256), (16, 128, 256)])
    assert tuple(entry["block"]) == (16, 128, 256)
    assert tmp_cache.exists()
    n_swept = len(calls)
    assert n_swept >= 2

    # reload from disk: lookup must hit, and a repeat autotune must NOT sweep
    tuning.reset()
    blk = tuning.get_block_sizes(8, 128, 256, kind=W_TERNARY, a_bits=2,
                                 w_bits=2, backend="pallas")
    assert blk == (16, 128, 256)
    assert tuning.stats()["hits"] == 1 and tuning.stats()["sweeps"] == 0
    tuning.autotune(8, 128, 256, kind=W_TERNARY, a_bits=2, w_bits=2,
                    backend="pallas", measure=fake_measure,
                    candidates=[(8, 128, 256), (16, 128, 256)])
    assert len(calls) == n_swept, "second autotune re-swept despite cache"
    assert tuning.stats()["sweeps"] == 0

    # the JSON is plain data (inspectable / CI-artifact friendly)
    data = json.loads(tmp_cache.read_text())
    assert data["version"] == 1 and len(data["entries"]) == 1


def test_shape_class_buckets_m_only():
    assert tuning.shape_class(1, 256, 512) == (8, 256, 512)
    assert tuning.shape_class(8, 256, 512) == (8, 256, 512)
    assert tuning.shape_class(100, 256, 512) == (128, 256, 512)
    # same bucket -> same key; different (N, K) -> different key
    k1 = tuning.cache_key("ternary", 2, 2, "pallas", 100, 256, 512)
    k2 = tuning.cache_key("ternary", 2, 2, "pallas", 128, 256, 512)
    k3 = tuning.cache_key("ternary", 2, 2, "pallas", 128, 128, 512)
    assert k1 == k2 and k1 != k3


def test_candidate_blocks_valid_and_include_default():
    for kind, bits, k in [(W_INT, 4, 512), (W_TERNARY, 2, 256),
                          (W_BINARY, 1, 1024)]:
        cands = tuning.candidate_blocks(64, 256, k, kind, bits)
        assert tuning.fallback_block(64, 256, k, kind, bits) in cands
        align = tuning._bk_align(kind, bits)
        for (_bm, bn, bk) in cands:
            assert 256 % bn == 0 and k % bk == 0 and bk % align == 0


def test_cache_miss_returns_valid_default(tmp_cache):
    blk = tuning.get_block_sizes(5, 384, 768, kind=W_INT, a_bits=8, w_bits=4,
                                 backend="pallas")
    bm, bn, bk = blk
    assert 384 % bn == 0 and 768 % bk == 0 and bk % 8 == 0
    assert tuning.stats()["misses"] == 1 and tuning.stats()["sweeps"] == 0


@pytest.mark.parametrize("payload", [
    b"",                                            # empty file
    b'{"version": 1, "entries": {',                 # truncated mid-write
    b"\x00\xffgarbage",                             # binary garbage
    b'[1, 2, 3]',                                   # valid JSON, wrong shape
    b'{"version": 1, "entries": [1, 2]}',           # entries not a dict
    b'{"version": 1, "entries": {"k": "nope"}}',    # entry not a dict
    b'{"version": 1, "entries": {"k": {"block": "x"}}}',   # malformed block
    b'{"version": 1, "entries": {"k": {"block": [8]}}}',   # wrong arity
])
def test_corrupt_cache_falls_back_to_defaults(tmp_cache, payload):
    """A corrupt/truncated tuning.json (e.g. a writer killed mid-write) must
    degrade to cache misses + safe defaults — never raise on the hot path."""
    tmp_cache.write_bytes(payload)
    tuning.reset()
    blk = tuning.get_block_sizes(8, 128, 256, kind=W_TERNARY, a_bits=2,
                                 w_bits=2, backend="pallas")
    assert blk == tuning.fallback_block(8, 128, 256, W_TERNARY, 2)
    assert tuning.stats()["misses"] == 1 and tuning.stats()["hits"] == 0
    # ... and a subsequent autotune repairs the file in place
    entry = tuning.autotune(8, 128, 256, kind=W_TERNARY, a_bits=2, w_bits=2,
                            backend="pallas", measure=lambda b: 1.0,
                            candidates=[(8, 128, 128)])
    assert tuning._sane_entry(entry)
    tuning.reset()
    assert tuning.get_block_sizes(8, 128, 256, kind=W_TERNARY, a_bits=2,
                                  w_bits=2, backend="pallas") in \
        {(8, 128, 128), tuning.fallback_block(8, 128, 256, W_TERNARY, 2)}


def test_corrupt_entry_does_not_break_good_entries(tmp_cache):
    """One malformed entry is dropped; valid siblings keep serving hits."""
    good_key = tuning.cache_key(W_TERNARY, 2, 2, "pallas", 8, 128, 256)
    tmp_cache.write_text(json.dumps({
        "version": 1,
        "entries": {good_key: {"block": [16, 128, 256], "us": 1.0},
                    "broken": {"block": None}},
    }))
    tuning.reset()
    blk = tuning.get_block_sizes(8, 128, 256, kind=W_TERNARY, a_bits=2,
                                 w_bits=2, backend="pallas")
    assert blk == (16, 128, 256)
    assert tuning.stats()["hits"] == 1


def test_cache_save_is_atomic(tmp_cache, monkeypatch):
    """The cache is written tmp-then-rename: an interrupted save must leave
    the previous file byte-identical (no torn JSON for the next reader)."""
    tuning.autotune(8, 128, 256, kind=W_TERNARY, a_bits=2, w_bits=2,
                    backend="pallas", measure=lambda b: 1.0,
                    candidates=[(8, 128, 128)])
    before = tmp_cache.read_bytes()

    def boom(src, dst):
        raise OSError("simulated crash during rename")
    with monkeypatch.context() as m:
        m.setattr(tuning.os, "replace", boom)
        with pytest.warns(RuntimeWarning):
            tuning.autotune(8, 256, 256, kind=W_TERNARY, a_bits=2, w_bits=2,
                            backend="pallas",
                            measure=lambda b: 0.5 if b == (8, 256, 256)
                            else 1.0,
                            candidates=[(8, 256, 256)])
    assert tmp_cache.read_bytes() == before     # old cache intact
    # in-memory state still serves the new entry this process
    assert tuning.get_block_sizes(8, 256, 256, kind=W_TERNARY, a_bits=2,
                                  w_bits=2, backend="pallas") == (8, 256, 256)


def test_autotune_matmul_end_to_end(tmp_cache):
    """Real sweep (tiny candidates) -> tuned dispatch stays bit-exact."""
    cfg = get_precision("2xT")
    m, n, k = 8, 128, 256
    entry = engine.autotune_matmul(cfg, m, n, k, backend="pallas",
                                   candidates=[(8, 128, 128), (8, 128, 256)],
                                   iters=1)
    assert tuple(entry["block"]) in {(8, 128, 128), (8, 128, 256),
                                     tuning.fallback_block(m, n, k, W_TERNARY, 2)}
    assert entry["us"] <= entry["default_us"] + 1e-9
    x = _codes((m, k), 8)
    pw = engine.pack_weight(
        jnp.asarray(RNG.normal(size=(k, n)).astype(np.float32)), cfg)
    want = ref.ternary_matmul_ref(x, pw.wt_packed, pw.scale)
    got = engine.qmatmul(x, pw, cfg, backend="pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# model-layer integration (serving path routes through the engine)
# ---------------------------------------------------------------------------
def test_qlinear_serving_through_engine():
    import dataclasses

    from repro.configs import get_config
    from repro.models import layers
    from repro.models.config import reduce_for_smoke
    from repro.models.convert import to_serving

    cfg = dataclasses.replace(reduce_for_smoke(get_config("smollm-135m")),
                              precision="2xT", dtype="float32")
    key = __import__("jax").random.PRNGKey(0)
    p = layers.qlinear_init(key, 128, 128, cfg)
    sp = to_serving({"layer": p}, cfg, tp=1)["layer"]
    assert "wt_packed" in sp
    x = jnp.asarray(RNG.normal(size=(4, 128)).astype(np.float32))
    out = layers.qlinear_apply(sp, x, cfg)
    assert out.shape == (4, 128)
    assert np.all(np.isfinite(np.asarray(out)))
    # engine path == direct qmatmul on the same packed weight
    from repro.core.precision import signed
    pcfg = signed(get_precision(cfg.precision))
    pw = engine.as_packed_weight(sp, pcfg)
    want = engine.qmatmul(x, pw, pcfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_cnn_serving_through_engine():
    import jax

    from repro.models import cnn

    params = cnn.tinynet_init(jax.random.PRNGKey(0))
    x = jnp.asarray(RNG.uniform(0, 1, (2, 28, 28, 1)).astype(np.float32))
    qat = cnn.tinynet_apply(params, x, precision="2xT")
    sparams = cnn.cnn_to_serving(params, "2xT")
    assert "wt_packed" in sparams["conv"][1]
    assert sparams["head"]["qw"] is params["head"]["qw"]  # classifier stays float
    served = cnn.tinynet_apply(sparams, x, precision="2xT")
    assert served.shape == qat.shape
    assert np.all(np.isfinite(np.asarray(served)))


def test_model_matmul_shapes():
    from repro.configs import get_config
    shapes = engine.model_matmul_shapes(get_config("smollm-135m"))
    cfg = get_config("smollm-135m")
    assert (cfg.d_ff, cfg.d_model) in shapes
    assert (cfg.d_model, cfg.n_heads * cfg.dh) in shapes


def test_save_merges_concurrent_writers(tmp_cache):
    """Two processes tuning different shape classes must not drop each
    other's entries: _save re-reads the file under the atomic replace and
    unions it with the in-memory entries (ours win on conflicts)."""
    # process A: loads (empty) cache, tunes key A
    tuning.autotune(8, 128, 256, kind=W_TERNARY, a_bits=2, w_bits=2,
                    backend="pallas", measure=lambda b: 1.0,
                    candidates=[(8, 128, 128)])
    key_a = tuning.cache_key(W_TERNARY, 2, 2, "pallas", 8, 128, 256)

    # process B persisted a different key while A was sweeping: simulate by
    # rewriting the file behind A's in-memory cache
    key_b = tuning.cache_key(W_TERNARY, 2, 2, "pallas", 8, 512, 256)
    entry_b = {"block": [8, 512, 128], "us": 1.0, "default_us": 2.0}
    tmp_cache.write_text(json.dumps(
        {"version": 1, "entries": {key_b: entry_b}}))

    # A tunes (and saves) another key: B's entry must survive on disk
    tuning.autotune(8, 256, 256, kind=W_TERNARY, a_bits=2, w_bits=2,
                    backend="pallas", measure=lambda b: 1.0,
                    candidates=[(8, 256, 128)])
    key_c = tuning.cache_key(W_TERNARY, 2, 2, "pallas", 8, 256, 256)
    on_disk = json.loads(tmp_cache.read_text())["entries"]
    assert set(on_disk) == {key_a, key_b, key_c}
    assert on_disk[key_b]["block"] == [8, 512, 128]

    # conflict case: the writer's own (fresh) measurement wins over disk
    mine = list(tuning._load()[key_a]["block"])
    data = json.loads(tmp_cache.read_text())
    data["entries"][key_a] = dict(entry_b, block=[16, 128, 512])  # foreign
    tmp_cache.write_text(json.dumps(data))
    tuning.autotune(8, 1024, 256, kind=W_TERNARY, a_bits=2, w_bits=2,
                    backend="pallas", measure=lambda b: 1.0,
                    candidates=[(8, 1024, 128)])
    on_disk = json.loads(tmp_cache.read_text())["entries"]
    assert on_disk[key_a]["block"] == mine           # measured entry won

    # NOT-measured keys must not resurrect: a fresh process that only
    # LOADED key_a must not clobber a concurrent re-tune of key_a on disk
    tuning.reset()
    tuning._load()                                   # key_a now memory-held
    fresher = dict(entry_b, block=[32, 128, 256])
    data = json.loads(tmp_cache.read_text())
    data["entries"][key_a] = fresher                 # another proc re-tuned
    tmp_cache.write_text(json.dumps(data))
    tuning.autotune(8, 2048, 256, kind=W_TERNARY, a_bits=2, w_bits=2,
                    backend="pallas", measure=lambda b: 1.0,
                    candidates=[(8, 2048, 128)])     # unrelated key -> save
    on_disk = json.loads(tmp_cache.read_text())["entries"]
    assert on_disk[key_a]["block"] == [32, 128, 256]  # re-tune survived


def test_model_matmul_shapes_tp_local():
    """tp > 1 yields per-device shard shapes per the sharding policy:
    N-sharded projections shrink N, K-sharded ones shrink K, and
    non-dividing head counts keep the matrix global (replicated)."""
    from repro.models.config import ModelConfig
    cfg = ModelConfig(name="t", n_layers=2, d_model=2048, n_heads=16,
                      n_kv_heads=8, head_dim=128, d_ff=8192, vocab=4096)
    d, f, h, kv, dh = 2048, 8192, 16, 8, 128
    assert engine.model_matmul_shapes(cfg, tp=1) == {
        (h * dh, d), (kv * dh, d), (d, h * dh), (f, d), (d, f)}
    assert engine.model_matmul_shapes(cfg, tp=8) == {
        (h * dh // 8, d), (kv * dh // 8, d), (d, h * dh // 8),
        (f // 8, d), (d, f // 8)}
    # 16 heads don't divide tp=32 -> attention replicated, FFN still sharded
    got = engine.model_matmul_shapes(cfg, tp=32)
    assert (h * dh, d) in got and (f // 32, d) in got


def test_serving_tune_plan_per_device_shapes():
    """With a mesh, the serving pre-tune plan shrinks to per-device shapes:
    decode rows M = n_slots/dp, TP-local N and K; the batch-1 admission
    chunk keeps M = chunk_size."""
    import jax
    from jax.sharding import Mesh
    from repro.models.config import ModelConfig

    devs = np.array(jax.devices() * 8)[:8]
    cfg = ModelConfig(name="t", n_layers=2, d_model=2048, n_heads=16,
                      n_kv_heads=8, head_dim=128, d_ff=8192, vocab=4096)
    pcfg = get_precision("2xT")

    plan = engine.serving_tune_plan(cfg, pcfg, n_slots=16, chunk_size=32)
    assert (16, cfg.d_ff, cfg.d_model) in plan
    assert (32, cfg.d_ff, cfg.d_model) in plan

    mesh_dp = Mesh(devs.reshape(8, 1), ("data", "model"))
    plan = engine.serving_tune_plan(cfg, pcfg, n_slots=16, chunk_size=32,
                                    mesh=mesh_dp)
    assert (2, cfg.d_ff, cfg.d_model) in plan          # local M = 16/8
    assert (32, cfg.d_ff, cfg.d_model) in plan         # chunk M unchanged

    mesh_tp = Mesh(devs.reshape(1, 8), ("data", "model"))
    plan = engine.serving_tune_plan(cfg, pcfg, n_slots=16, chunk_size=32,
                                    mesh=mesh_tp)
    assert (16, cfg.d_ff // 8, cfg.d_model) in plan    # local N = d_ff/tp
    assert (16, cfg.d_model, cfg.d_ff // 8) in plan    # local K (w_down)

    # pure-DP model (small d_model): params replicate -> global N/K, but the
    # batch still shards over every axis (local decode M = n_slots/8)
    small = ModelConfig(name="s", n_layers=2, d_model=512, n_heads=8,
                        n_kv_heads=8, head_dim=64, d_ff=2048, vocab=4096)
    plan = engine.serving_tune_plan(small, pcfg, n_slots=16, chunk_size=32,
                                    mesh=mesh_tp)
    assert (2, small.d_ff, small.d_model) in plan


@pytest.mark.parametrize("k", [576, 1536])
@pytest.mark.parametrize("kind,bits", [("ternary", 2), ("binary", 1),
                                       ("int", 2), ("int", 4), ("int", 8)])
def test_default_tiles_are_mosaic_legal(kind, bits, k):
    """At smollm's contraction lengths every default and candidate tile
    keeps each block dim a multiple of (8, 128) or the whole array dim."""
    cpw = tuning._bk_align(kind, bits)
    for n in (192, 576, 1536):
        for m in (1, 8, 200, 256):
            blocks = [tuning.fallback_block(m, n, k, kind, bits)]
            blocks += tuning.candidate_blocks(m, n, k, kind, bits)
            for bm, bn, bk in blocks:
                assert tuning._valid_block(m, n, k, kind, bits, (bm, bn, bk))
                assert bm % 8 == 0
                assert bn == n or (bn % 128 == 0 and n % bn == 0)
                assert k % bk == 0 and bk % cpw == 0
                assert bk == k or bk % 128 == 0            # x block lanes
                bkw = bk // cpw
                assert bkw == k // cpw or bkw % 128 == 0   # weight lanes


def test_illegal_tiles_are_rejected():
    # the tile the old picker chose at K=576 (bk=64) and a 128-wide bk whose
    # packed ternary block is 8 words wide
    assert not tuning._valid_block(8, 576, 576, "ternary", 2, (8, 128, 64))
    assert not tuning._valid_block(8, 576, 576, "ternary", 2, (8, 128, 128))
    assert not tuning._valid_block(8, 576, 576, "ternary", 2, (4, 576, 576))
    assert not tuning._valid_block(8, 576, 576, "ternary", 2, (8, 64, 576))
    assert tuning._valid_block(8, 576, 576, "ternary", 2, (8, 576, 576))
    assert tuning._valid_block(8, 4096, 4096, "ternary", 2, (8, 128, 2048))
    assert tuning.fallback_block(8, 576, 576, "ternary", 2) == (8, 576, 576)
