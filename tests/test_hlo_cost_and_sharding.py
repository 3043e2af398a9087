"""Unit tests for the trip-count-aware HLO cost parser (synthetic HLO text)
and hypothesis property tests for the sharding rules — plus the contract
audit (repro.analysis) that the pure-DP serving steps are collective-free
and the quantized-act steps fire the tuned Pallas kernels."""
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import Mesh, PartitionSpec as P

from repro.analysis.hlo import analyze_hlo_text, parse_hlo
from repro.kernels import kv_pool
from repro.models.config import ModelConfig
from repro.parallel import sharding as sh

# ---------------------------------------------------------------------------
# HLO parser on synthetic modules
# ---------------------------------------------------------------------------
SIMPLE = """
HloModule test

ENTRY %main (a: f32[128,256], b: f32[256,64]) -> f32[128,64] {
  %a = f32[128,256]{1,0} parameter(0)
  %b = f32[256,64]{1,0} parameter(1)
  ROOT %dot.1 = f32[128,64]{1,0} dot(%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""


def test_parser_dot_flops_and_bytes():
    r = analyze_hlo_text(SIMPLE)
    assert r["flops_corrected"] == 2 * 128 * 64 * 256
    # traffic: a + b + out
    assert r["bytes_corrected"] == (128 * 256 + 256 * 64 + 128 * 64) * 4


LOOPED = """
HloModule test

%body (p: (s32[], f32[16,512])) -> (s32[], f32[16,512]) {
  %p = (s32[], f32[16,512]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[16,512]{1,0} get-tuple-element(%p), index=1
  %w = f32[512,512]{1,0} constant({...})
  %dot.2 = f32[16,512]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %ip = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[16,512]) tuple(%ip, %dot.2)
}

%cond (p: (s32[], f32[16,512])) -> pred[] {
  %p = (s32[], f32[16,512]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(30)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (x: f32[16,512]) -> f32[16,512] {
  %x = f32[16,512]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[16,512]) tuple(%zero, %x)
  %while.1 = (s32[], f32[16,512]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"30"}}
  ROOT %out = f32[16,512]{1,0} get-tuple-element(%while.1), index=1
}
"""


def test_parser_multiplies_while_body_by_trip_count():
    r = analyze_hlo_text(LOOPED)
    assert r["flops_corrected"] == 30 * 2 * 16 * 512 * 512


COLLECTIVE = """
HloModule test

ENTRY %main (x: f32[1024]) -> f32[1024] {
  %x = f32[1024]{0} parameter(0)
  %ar = f32[1024]{0} all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%sum
  ROOT %out = f32[1024]{0} copy(%ar)
}
"""


def test_parser_collective_bytes():
    r = analyze_hlo_text(COLLECTIVE)
    assert r["collectives_by_kind"]["all-reduce"] == 1024 * 4
    assert r["collective_op_counts"]["all-reduce"] == 1


SLICED_FUSION = """
HloModule test

%fused_slice (param_0: f32[61,4096,448], param_1: s32[]) -> f32[4096,448] {
  %param_0 = f32[61,4096,448]{2,1,0} parameter(0)
  %param_1 = s32[] parameter(1)
  %zero = s32[] constant(0)
  %ds = f32[1,4096,448]{2,1,0} dynamic-slice(%param_0, %param_1, %zero, %zero), dynamic_slice_sizes={1,4096,448}
  ROOT %bc = f32[4096,448]{1,0} bitcast(%ds)
}

ENTRY %main (stack: f32[61,4096,448], i: s32[]) -> f32[4096,448] {
  %stack = f32[61,4096,448]{2,1,0} parameter(0)
  %i = s32[] parameter(1)
  ROOT %fusion.1 = f32[4096,448]{1,0} fusion(%stack, %i), kind=kLoop, calls=%fused_slice
}
"""


def test_parser_discounts_fused_slice_reads():
    """A fusion slicing ONE layer from a 61-layer stack must charge ~one
    slice, not the whole stack (the kimi-train analyzer fix)."""
    r = analyze_hlo_text(SLICED_FUSION)
    stack_bytes = 61 * 4096 * 448 * 4
    slice_bytes = 4096 * 448 * 4
    assert r["bytes_corrected"] < 4 * slice_bytes
    assert r["bytes_corrected"] < stack_bytes / 10


# ---------------------------------------------------------------------------
# sharding rules — property tests
# ---------------------------------------------------------------------------
def _mesh(shape=(4, 4)):
    devs = np.array(jax.devices() * (shape[0] * shape[1]))[: shape[0] * shape[1]]
    return Mesh(devs.reshape(shape), ("data", "model"))


def _cfg(d_model, n_heads, n_kv, d_ff, vocab, experts=0):
    return ModelConfig(name="t", n_layers=2, d_model=d_model, n_heads=n_heads,
                       n_kv_heads=n_kv, d_ff=d_ff, vocab=vocab,
                       n_experts=experts, top_k=2 if experts else 0,
                       moe_d_ff=64 if experts else 0,
                       ffn_pattern=("moe",) if experts else ("dense",))


class FakeLeaf:
    def __init__(self, shape):
        self.shape = tuple(shape)


@given(n_heads=st.sampled_from([4, 6, 8, 9, 12, 16]),
       n_kv=st.sampled_from([1, 2, 3, 4, 8]),
       d_ff=st.sampled_from([64, 96, 128, 1536]))
@settings(max_examples=25, deadline=None)
def test_param_specs_divisibility_invariant(n_heads, n_kv, d_ff):
    """Property: every sharded axis size divides the mesh axis size."""
    mesh = _mesh((4, 4))
    dh = 32
    cfg = _cfg(2048, n_heads, min(n_kv, n_heads), d_ff, 4096)
    params = {
        "embed": {"w": FakeLeaf((cfg.padded_vocab, cfg.d_model))},
        "blocks": {"layer_0": {
            "attn": {"wq": {"qw": FakeLeaf((2, cfg.d_model, n_heads * dh))},
                     "wk": {"qw": FakeLeaf((2, cfg.d_model, cfg.n_kv_heads * dh))},
                     "wo": {"qw": FakeLeaf((2, n_heads * dh, cfg.d_model))}},
            "ffn": {"w_up": {"qw": FakeLeaf((2, cfg.d_model, d_ff))},
                    "w_down": {"qw": FakeLeaf((2, d_ff, cfg.d_model))}},
        }},
        "lm_head": {"qw": FakeLeaf((cfg.d_model, cfg.padded_vocab))},
    }
    specs = sh.param_specs(params, cfg, mesh)

    def check(spec_leaf, arr_leaf):
        for dim, ax in zip(arr_leaf.shape, tuple(spec_leaf)):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            assert dim % n == 0, (arr_leaf.shape, tuple(spec_leaf))

    jax.tree_util.tree_map(check, specs, params,
                           is_leaf=lambda x: isinstance(x, (P, FakeLeaf)))


@given(batch=st.sampled_from([1, 2, 4, 8, 16, 32, 128, 256]))
@settings(max_examples=10, deadline=None)
def test_batch_axes_always_divide(batch):
    mesh = _mesh((4, 4))
    cfg = _cfg(2048, 8, 4, 128, 4096)
    axes = sh._batch_axes(cfg, mesh, batch)
    if axes is not None:
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        assert batch % n == 0


def test_pure_dp_replicates_everything():
    mesh = _mesh((4, 4))
    cfg = _cfg(576, 9, 3, 1536, 49152)   # smollm-like
    params = {"x": {"qw": FakeLeaf((2, 576, 288))}}
    specs = sh.param_specs(params, cfg, mesh)
    assert tuple(specs["x"]["qw"]) == (None, None, None)


def test_cache_specs_allow_sp_disables_sequence_sharding():
    """The serving admission cache (batch=1 on a dp mesh) must NOT fall back
    to sequence-parallel sharding: chunk appends dynamic_update_slice over
    the sequence dim, which has to stay local to one shard."""
    mesh = _mesh((4, 4))
    cfg = _cfg(2048, 8, 4, 128, 4096)
    cache = {"layer_0": {"k": FakeLeaf((2, 1, 64, 4, 32)),
                         "v": FakeLeaf((2, 1, 64, 4, 32))}}
    # default (B=1, seq 64 divisible by data=4): SP fallback shards the seq
    sp = sh.cache_specs(cache, cfg, mesh, batch=1)
    assert tuple(sp["layer_0"]["k"])[2] == "data"   # P canonicalizes ("data",)
    # allow_sp=False: sequence replicated, KV heads still sharded (4 % 4 == 0)
    no_sp = sh.cache_specs(cache, cfg, mesh, batch=1, allow_sp=False)
    assert tuple(no_sp["layer_0"]["k"])[2] is None
    assert tuple(no_sp["layer_0"]["k"])[3] == "model"
    # batch-divisible slot cache is unaffected by the flag
    slot = {"layer_0": {"k": FakeLeaf((2, 8, 64, 4, 32))}}
    a = sh.cache_specs(slot, cfg, mesh, batch=8)
    b = sh.cache_specs(slot, cfg, mesh, batch=8, allow_sp=False)
    assert tuple(a["layer_0"]["k"]) == tuple(b["layer_0"]["k"])


def test_serving_shard_factors():
    mesh = _mesh((4, 4))
    big = _cfg(2048, 8, 4, 128, 4096)        # TP applies
    assert sh.serving_shard_factors(big, mesh, n_slots=8) == (4, 4)
    assert sh.serving_shard_factors(big, mesh, n_slots=3) == (1, 4)
    small = _cfg(576, 9, 3, 1536, 4096)      # pure DP: batch over all axes
    assert sh.serving_shard_factors(small, mesh, n_slots=16) == (16, 1)
    assert sh.serving_shard_factors(small, mesh, n_slots=4) == (4, 1)


def test_named_shardings_tree():
    mesh = _mesh((4, 4))
    specs = {"a": P("data", None), "b": {"c": P()}}
    out = sh.named_shardings(mesh, specs)
    assert out["a"].spec == P("data", None) and out["a"].mesh.shape == mesh.shape
    assert out["b"]["c"].spec == P()


def test_pool_specs_never_shard_block_or_position_dims():
    """Paged KV pool: appends scatter at dynamic (block, offset) coordinates,
    so only the lane dim, which holds whole KV heads one after another, may
    shard (over 'model', when TP applies)."""
    mesh = _mesh((4, 4))
    big = _cfg(2048, 8, 4, 128, 4096)          # TP applies, kv=4 divides 4
    pool = {"layer_0": {kv_pool.CODES: FakeLeaf((2, 10, 16, 2 * 4 * 32)),
                        kv_pool.SCALES: FakeLeaf((2, 16, 2 * 4 * 16))}}
    specs = sh.pool_specs(pool, big, mesh)
    assert tuple(specs["layer_0"][kv_pool.CODES]) == (None, None, None,
                                                      "model")
    assert tuple(specs["layer_0"][kv_pool.SCALES]) == (None, None, "model")
    # misaligned KV heads replicate; pure-DP models always replicate
    odd = _cfg(2048, 9, 3, 128, 4096)
    small = _cfg(576, 9, 3, 1536, 4096)
    for cfg in (odd, small):
        for name, leaf in sh.pool_specs(pool, cfg, mesh)["layer_0"].items():
            assert tuple(leaf) == (None,) * len(pool["layer_0"][name].shape)


@pytest.mark.parametrize("kv_bits", [16, 8, 4])
@pytest.mark.parametrize("tp", [2, 4])
def test_pool_lane_shards_hold_whole_heads(kv_bits, tp):
    """An even split of the pool's lane dim (what ``pool_specs`` asks of the
    'model' axis) gives shard ``i`` exactly the pool of KV heads
    ``[i*KV/tp, (i+1)*KV/tp)``: read as the kernels read a pool of that many
    heads, its keys, values and scales, nothing of another head's, at every
    position of every block and layer (rows that pack several positions
    included: at these widths kv16 and kv8 pack 2 a row, kv4 4)."""
    kv, dh, bs, nb = 4, 8, 16, 3
    dh_s = kv_pool.store_dh(dh, kv_bits)
    rng = np.random.default_rng(kv_bits + tp)
    heads = lambda last: jnp.asarray(rng.standard_normal(
        (2, nb, bs, kv, last)).astype(np.float32))
    k, v = heads(dh_s), heads(dh_s)
    ks, vs = (None, None) if kv_bits == 16 else (heads(1), heads(1))
    whole = kv_pool.from_heads(k, ks, v, vs)
    table = jnp.arange(nb, dtype=jnp.int32)[None]
    per = kv // tp
    for i in range(tp):
        h = slice(i * per, (i + 1) * per)
        shard = {}
        for name, leaf in whole.items():
            width = leaf.shape[kv_pool.LANE_DIM] // tp
            shard[name] = leaf[..., i * width:(i + 1) * width]
        for layer in range(2):
            got = kv_pool.gather(shard[kv_pool.CODES],
                                 shard.get(kv_pool.SCALES), table, layer,
                                 per, dh_s)
            want = [None if x is None else
                    x[layer, :, :, h].reshape(1, nb * bs, per, -1)
                    for x in (k, ks, v, vs)]
            for name, g, w in zip(("k", "ks", "v", "vs"), got, want):
                assert (g is None) == (w is None), name
                if w is not None:
                    np.testing.assert_array_equal(
                        np.asarray(g), np.asarray(w),
                        err_msg=f"{name} shard {i} layer {layer}")


# ---------------------------------------------------------------------------
# compiled serving steps on dp meshes: the contract audit replaces the old
# HLO-substring greps — audit_cell enforces no_collectives / cache_donated
# (and, for quantized cells, pallas_call_present / no_f32_upcast /
# scale_shape_is_per_row / tuning_cache_hit) from the structured walkers
# ---------------------------------------------------------------------------
_AUDIT_CELL_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
from repro.analysis.steps import audit_cell, cell_by_name

name, meshes = sys.argv[1], sys.argv[2:]
cache = {}
for m in meshes:
    mesh = None if m == "none" else tuple(int(x) for x in m.split(","))
    findings, checked = audit_cell(cell_by_name(name), mesh, _cache=cache)
    assert checked, (name, mesh, "no steps audited")
    assert not findings, (name, mesh, [str(f) for f in findings])
    print(f"AUDIT_{m}_OK")
print("AUDIT_CELL_OK")
"""


def _run_audit_cell(name, *meshes):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    # hermetic tuning cache: audit_cell primes its own keys (persist=False)
    env["REPRO_TUNING_CACHE"] = os.path.join(
        tempfile.mkdtemp(prefix="audit-tuning-"), "cache.json")
    out = subprocess.run(
        [sys.executable, "-c", _AUDIT_CELL_SCRIPT, name, *meshes],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    assert "AUDIT_CELL_OK" in out.stdout, out.stdout[-2000:]
    return out.stdout


def test_decode_step_collective_free_on_dp_mesh_8dev():
    """Pure-DP serving steps compile to ZERO collectives and donate the
    cache: the per-token KV row write (formerly a cross-device
    scatter/gather under pjit) runs shard-local under shard_map.  Enforced
    by the repro.analysis contract checker (no_collectives walks the parsed
    HLO, cache_donated checks input_output_alias)."""
    _run_audit_cell("smollm-dp", "8,1", "2,4")


def test_quantized_act_sharded_steps_fire_pallas_8dev():
    """Sharded decode AND chunk-prefill for a quantized-act PAPER_CONFIG
    (2xT) dispatch the tuned Pallas qmatmul on per-shard shapes with per-row
    activation scales, warm tuning keys, no float upcast of quantized
    operands, and zero collectives — the full quantized contract set,
    enforced from engine dispatch events + jaxpr + HLO rather than string
    greps."""
    _run_audit_cell("smollm-2xT", "8,1", "2,4")
