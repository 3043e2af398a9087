"""Unit + seeded-violation tests for the repro.analysis invariant auditor.

Three layers:

  * walker units — the shared HLO parser's contract-rule views
    (parse_collectives / donated_aliases / collective_ops) including the
    regression pinning ``parse_collectives`` byte totals to
    ``analyze_hlo_text`` (both now sit on the same walker, so the totals
    must be byte-identical), and the jaxpr dataflow walk;
  * AST linter units — seeded source strings firing each architecture rule
    exactly once, the exemption map, and the clean-repo scan;
  * seeded contract violations (subprocess, 8 virtual devices) — for each
    compile-time rule, a deliberately broken step (xla-forced backend,
    injected psum, per-tensor act scale, un-donated cache, cold tuning
    cache) must fire EXACTLY its own rule with a structured finding.
"""
import json
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp

from repro.analysis import astlint
from repro.analysis.hlo import (analyze_hlo_text, collective_ops,
                                donated_aliases, parse_collectives, parse_hlo)
from repro.analysis.jaxpr_walker import (count_primitives, find_float_upcasts,
                                         has_primitive)
from repro.analysis.report import Finding, Report, StepSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# HLO walker: contract-rule views
# ---------------------------------------------------------------------------
MIXED_COLLECTIVES = """
HloModule test

ENTRY %main (x: f32[1024], y: bf16[256,8]) -> f32[1024] {
  %x = f32[1024]{0} parameter(0)
  %y = bf16[256,8]{1,0} parameter(1)
  %ar = f32[1024]{0} all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%sum
  %ag = bf16[256,64]{1,0} all-gather(%y), dimensions={1}
  %ar2 = f32[1024]{0} all-reduce(%ar), replica_groups={{0,1,2,3}}, to_apply=%sum
  ROOT %out = f32[1024]{0} copy(%ar2)
}
"""


def test_parse_collectives_structure():
    r = parse_collectives(MIXED_COLLECTIVES)
    assert r["counts"]["all-reduce"] == 2
    assert r["counts"]["all-gather"] == 1
    assert r["bytes"]["all-reduce"] == 2 * 1024 * 4
    assert r["bytes"]["all-gather"] == 256 * 64 * 2
    assert r["total_bytes"] == sum(r["bytes"].values())


def test_parse_collectives_byte_totals_pin_to_analyze_hlo_text():
    """Regression for the dryrun/hlo_cost unification: both call sites now
    consume the ONE walker, so per-kind byte totals and op counts must be
    identical on the same module text."""
    cost = analyze_hlo_text(MIXED_COLLECTIVES)
    coll = parse_collectives(MIXED_COLLECTIVES)
    assert coll["bytes"] == {k: v for k, v in
                             cost["collectives_by_kind"].items()}
    assert coll["counts"] == {k: v for k, v in
                              cost["collective_op_counts"].items()}
    assert coll["total_bytes"] == sum(cost["collectives_by_kind"].values())


def test_collective_ops_walks_non_entry_computations():
    txt = """
HloModule test

%inner (p: f32[64]) -> f32[64] {
  %p = f32[64]{0} parameter(0)
  ROOT %cp = f32[64]{0} collective-permute(%p), source_target_pairs={{0,1}}
}

ENTRY %main (x: f32[64]) -> f32[64] {
  %x = f32[64]{0} parameter(0)
  ROOT %c = f32[64]{0} call(%x), to_apply=%inner
}
"""
    ops = list(collective_ops(parse_hlo(txt)))
    assert [o.opcode for o in ops] == ["collective-permute"]
    assert ops[0].out_bytes == 64 * 4


def test_donated_aliases_nested_braces():
    donated = ("HloModule m, input_output_alias={ {0}: (2, {}, may-alias), "
               "{1}: (3, {}, may-alias) }, entry_computation_layout={()->()}\n")
    assert len(donated_aliases(donated)) == 2
    assert donated_aliases("HloModule m, is_scheduled=true\n") == []


# ---------------------------------------------------------------------------
# jaxpr walker
# ---------------------------------------------------------------------------
def test_has_primitive_descends_into_calls():
    @jax.jit
    def f(x):
        return jnp.sin(x) * 2

    jpr = jax.make_jaxpr(f)(jnp.ones((4,)))
    assert has_primitive(jpr, "sin")
    assert not has_primitive(jpr, "cos")
    assert count_primitives(jpr)["sin"] == 1


def test_find_float_upcasts_flags_dequantized_dot():
    w8 = jnp.ones((8, 4), jnp.int8)

    def bad(x):
        return x @ (w8.astype(jnp.float32) * 0.02)

    jpr = jax.make_jaxpr(bad)(jnp.ones((2, 8)))
    hits = find_float_upcasts(jpr)
    assert hits and hits[0][0] == "dot_general"


def test_find_float_upcasts_clean_on_integer_dot():
    w8 = jnp.ones((8, 4), jnp.int8)

    def good(x):
        acc = jax.lax.dot_general(
            x, w8, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * 0.02

    jpr = jax.make_jaxpr(good)(jnp.ones((2, 8), jnp.int8))
    assert find_float_upcasts(jpr) == []


# ---------------------------------------------------------------------------
# AST architecture linter: seeded sources
# ---------------------------------------------------------------------------
def _fire(src, path, rule):
    findings = astlint.lint_source(src, path, rules=(rule,))
    assert [f.rule for f in findings] == [rule], [str(f) for f in findings]
    return findings[0]


def test_lint_kernel_import_boundary():
    src = "from repro.kernels import binary_matmul\n"
    f = _fire(src, "src/repro/models/foo.py", "kernel-import-boundary")
    assert "binary_matmul" in f.locus


def test_lint_kernel_import_exemption_is_path_based():
    src = "import repro.kernels.ternary_matmul\n"
    # lint_paths applies the exemption map; the kernels package is exempt
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "src", "repro", "kernels", "x.py")
        os.makedirs(os.path.dirname(p))
        with open(p, "w") as fh:
            fh.write(src)
        assert astlint.lint_paths([p], repo_root=d) == []
        p2 = os.path.join(d, "src", "repro", "models", "y.py")
        os.makedirs(os.path.dirname(p2))
        with open(p2, "w") as fh:
            fh.write(src)
        findings = astlint.lint_paths([p2], repo_root=d)
        assert [f.rule for f in findings] == ["kernel-import-boundary"]


def test_lint_legacy_kwargs():
    src = "b = ContinuousBatcher(model, params, n_slots=8, s_max=24)\n"
    f = _fire(src, "benchmarks/bench.py", "legacy-kwargs")
    assert "n_slots" in f.message
    ok = "b = ContinuousBatcher(model, params, ServingConfig(n_slots=8))\n"
    assert astlint.lint_source(ok, "benchmarks/bench.py",
                               rules=("legacy-kwargs",)) == []


def test_lint_batcher_config_bypass():
    src = "b = PagedBatcher(model, params)\n"
    f = _fire(src, "examples/demo.py", "batcher-config-bypass")
    assert "PagedBatcher" in f.message
    ok = "b = PagedBatcher(model, params, config=cfg)\n"
    assert astlint.lint_source(ok, "examples/demo.py",
                               rules=("batcher-config-bypass",)) == []


def test_lint_device_get_in_hot_loop():
    src = ("def step(self):\n"
           "    x = jax.device_get(self.tokens)\n"
           "    return x\n")
    f = _fire(src, "src/repro/runtime/foo.py", "device-get-in-hot-loop")
    assert "step" in f.message
    cold = ("def build(self):\n"
            "    return jax.device_get(self.tokens)\n")
    assert astlint.lint_source(cold, "src/repro/runtime/foo.py",
                               rules=("device-get-in-hot-loop",)) == []


def test_lint_tracing_in_jit_call():
    src = ("import jax\n"
           "def _decode_fn(p, t):\n"
           "    tr.instant('decode', 'scheduler')\n"
           "    return t\n"
           "decode = jax.jit(_decode_fn)\n")
    f = _fire(src, "src/repro/runtime/foo.py", "tracing-in-jit")
    assert "_decode_fn" in f.message
    # the same call OUTSIDE the jitted function is the supported pattern
    ok = ("import jax\n"
          "def _decode_fn(p, t):\n"
          "    return t\n"
          "decode = jax.jit(_decode_fn)\n"
          "def step(self):\n"
          "    tr.begin('step', 'scheduler')\n"
          "    return decode(None, None)\n")
    assert astlint.lint_source(ok, "src/repro/runtime/foo.py",
                               rules=("tracing-in-jit",)) == []


def test_lint_tracing_in_jit_span():
    """A span opened inside a jitted function fires like any tracer call;
    the same span around the call is the supported pattern."""
    src = ("import jax\n"
           "def _decode_fn(p, t):\n"
           "    with self.tracer.span('decode', 'scheduler'):\n"
           "        return t\n"
           "decode = jax.jit(_decode_fn)\n")
    f = _fire(src, "src/repro/runtime/foo.py", "tracing-in-jit")
    assert "_decode_fn" in f.message
    ok = ("import jax\n"
          "def _decode_fn(p, t):\n"
          "    return t\n"
          "decode = jax.jit(_decode_fn)\n"
          "def step(self):\n"
          "    with self.tracer.span('decode', 'scheduler'):\n"
          "        return decode(None, None)\n")
    assert astlint.lint_source(ok, "src/repro/runtime/foo.py",
                               rules=("tracing-in-jit",)) == []


def test_lint_tracing_in_jit_lambda():
    src = "f = jax.jit(lambda p, b: tracer.instant('x', 'y') or b)\n"
    f = _fire(src, "src/repro/launch/foo.py", "tracing-in-jit")
    assert "lambda" in f.message


def test_lint_tracing_import_forbidden_in_jit_land():
    src = "from repro.runtime.tracing import Tracer\n"
    for path in ("src/repro/models/foo.py", "src/repro/kernels/foo.py",
                 "src/repro/parallel/foo.py"):
        f = _fire(src, path, "tracing-in-jit")
        assert "flight recorder" in f.message
    # ...but host-side serving code imports it freely
    assert astlint.lint_source(src, "src/repro/runtime/serving.py",
                               rules=("tracing-in-jit",)) == []
    # the submodule-from spelling fires too
    alt = "from repro.runtime import tracing\n"
    _fire(alt, "src/repro/models/foo.py", "tracing-in-jit")


def test_lint_syntax_error_is_a_finding():
    findings = astlint.lint_source("def broken(:\n", "src/x.py")
    assert [f.rule for f in findings] == ["syntax-error"]


def test_repo_sources_are_lint_clean():
    findings = astlint.lint_paths(astlint.default_lint_roots(REPO),
                                  repo_root=REPO)
    assert findings == [], "\n".join(str(f) for f in findings)


# ---------------------------------------------------------------------------
# report / spec plumbing
# ---------------------------------------------------------------------------
def test_step_spec_default_rules_gating():
    base = dict(name="s", fn=None, args=())
    assert "no_collectives" in StepSpec(**base, pure_dp=True).default_rules()
    assert "no_collectives" not in \
        StepSpec(**base, pure_dp=False).default_rules()
    quant = StepSpec(**base, quantized_weights=True, quantized_acts=True,
                     backend="pallas", donate_argnums=(2,))
    rules = quant.default_rules()
    for r in ("pallas_call_present", "no_f32_upcast_of_quantized_operands",
              "tuning_cache_hit", "scale_shape_is_per_row", "cache_donated"):
        assert r in rules, rules
    # xla backend drops the pallas-path rules but keeps the scale contract
    ref = StepSpec(**base, quantized_weights=True, quantized_acts=True,
                   backend="xla").default_rules()
    assert "pallas_call_present" not in ref
    assert "scale_shape_is_per_row" in ref
    # the fused-decode promise binds its single-dispatch contract; steps
    # without it (dense decode, composition fallback) never see the rule
    fused = StepSpec(**base, fused_layers=2).default_rules()
    assert "fused_decode_single_dispatch" in fused
    assert "fused_decode_single_dispatch" not in \
        StepSpec(**base).default_rules()


def test_report_json_roundtrip():
    rep = Report()
    rep.extend([Finding(rule="r", step="s", message="m", locus="l")],
               cell="c")
    rep.checked.append({"cell": "c", "step": "s", "rules": ["r"]})
    data = json.loads(rep.to_json())
    assert data["findings"][0]["cell"] == "c"
    assert data["findings"][0]["rule"] == "r"
    assert not rep.ok
    assert "1 finding" in rep.summary()


def test_audit_step_rejects_unknown_rules():
    from repro.analysis.rules import audit_step
    spec = StepSpec(name="s", fn=jax.jit(lambda x: x), args=(jnp.zeros(2),))
    try:
        audit_step(spec, rules=("bogus",))
    except KeyError as e:
        assert "bogus" in str(e)
    else:
        raise AssertionError("unknown rule id must raise")


# ---------------------------------------------------------------------------
# seeded contract violations: each broken step fires EXACTLY its own rule
# (subprocess: 8 virtual devices + hermetic tuning cache)
# ---------------------------------------------------------------------------
_VIOLATIONS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from functools import partial

from repro.analysis.report import StepSpec
from repro.analysis.rules import audit_step
from repro.core.precision import get_precision, signed
from repro.kernels import engine, tuning
from jax import shard_map
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P

def only(findings, rule):
    fired = sorted({f.rule for f in findings})
    assert fired == [rule], (rule, [str(f) for f in findings])
    f = findings[0]
    assert f.rule == rule and f.step and f.message   # structured fields
    return f

pcfg = signed(get_precision("2xT"))
w = np.random.default_rng(0).standard_normal((64, 32)).astype(np.float32)
pw = engine.pack_weight(jnp.asarray(w), pcfg)
# the tuning lookup (and the interesting dispatch paths) only run under the
# Pallas backend; prime the m=8 key so only the SEEDED violation fires
engine.set_default_backend("pallas")
tuning.prime(8, 32, 64, kind="ternary", a_bits=pcfg.a_bits, w_bits=pcfg.w_bits,
             persist=False)

# 1. forced-xla dispatch: pallas_call_present flags the silent fallback
prev = engine._BACKEND_OVERRIDE
engine.set_default_backend("xla")
try:
    spec = StepSpec(name="xla-step", fn=jax.jit(
        lambda x: engine.qmatmul(x, pw, pcfg)), args=(jnp.ones((8, 64)),))
    f = only(audit_step(spec, rules=("pallas_call_present",)),
             "pallas_call_present")
    assert "'xla'" in f.message, f.message
finally:
    engine.set_default_backend(prev)
print("SEEDED_XLA_OK")

# 2. injected psum on a pure-DP step: no_collectives names the all-reduce
mesh = make_mesh(8, 1)
psum_fn = jax.jit(shard_map(
    lambda x: jax.lax.psum(x, "data"), mesh=mesh,
    in_specs=P("data"), out_specs=P()))
spec = StepSpec(name="psum-step", fn=psum_fn, args=(jnp.ones((8, 4)),))
f = only(audit_step(spec, rules=("no_collectives",)), "no_collectives")
assert "all-reduce" in f.message, f.message
print("SEEDED_PSUM_OK")

# 3. per-tensor activation scale: scale_shape_is_per_row catches the
#    batch-coupled quantization
orig = engine._prep_activations
def per_tensor_prep(x2, pw_, a_bits):
    xq, a_scale = orig(x2, pw_, a_bits)
    if a_scale is not None:
        a_scale = jnp.max(a_scale).reshape(1, 1)   # batch-coupled!
    return xq, a_scale
engine._prep_activations = per_tensor_prep
try:
    spec = StepSpec(name="scale-step", fn=jax.jit(
        lambda x: engine.qmatmul(x, pw, pcfg)), args=(jnp.ones((8, 64)),))
    f = only(audit_step(spec, rules=("scale_shape_is_per_row",)),
             "scale_shape_is_per_row")
    assert "(1, 1)" in f.message and "(8, 1)" in f.message, f.message
finally:
    engine._prep_activations = orig
print("SEEDED_SCALE_OK")

# 4. un-donated cache: cache_donated demands input_output_alias
def update(tok, cache):
    return cache.at[:, 0].set(tok)
toks, cache = jnp.ones((4,)), jnp.zeros((4, 16))
undonated = StepSpec(name="undonated", fn=jax.jit(update),
                     args=(toks, cache), donate_argnums=(1,))
f = only(audit_step(undonated, rules=("cache_donated",)), "cache_donated")
assert "input_output_alias" in f.message, f.message
donated = StepSpec(name="donated", fn=jax.jit(update, donate_argnums=(1,)),
                   args=(toks, cache), donate_argnums=(1,))
assert audit_step(donated, rules=("cache_donated",)) == []
print("SEEDED_DONATE_OK")

# 5. cold tuning cache: an unprimed shape class fires tuning_cache_hit;
#    priming it makes a FRESH trace pass
spec = StepSpec(name="cold-tuning", fn=jax.jit(
    lambda x: engine.qmatmul(x, pw, pcfg)), args=(jnp.ones((16, 64)),))
f = only(audit_step(spec, rules=("tuning_cache_hit",)), "tuning_cache_hit")
assert "miss" in f.message, f.message
tuning.prime(16, 32, 64, kind="ternary", a_bits=pcfg.a_bits,
             w_bits=pcfg.w_bits, persist=False)
warm = StepSpec(name="warm-tuning", fn=jax.jit(
    lambda x: engine.qmatmul(x, pw, pcfg)), args=(jnp.ones((16, 64)),))
assert audit_step(warm, rules=("tuning_cache_hit",)) == []
print("SEEDED_TUNING_OK")

# 6. fused-decode single dispatch: the real fused kernel passes; the
#    two-dispatch legacy layer fires (no fused call + a non-fused pallas
#    attention dispatch); a host callback inside the step is flagged
from repro.kernels.decode_fused import fused_decode
rng6 = np.random.default_rng(6)
B, KV, G, DH, BS, NB, D = 2, 1, 2, 4, 4, 2, 8
q6 = jnp.asarray(rng6.standard_normal((B, KV, G, DH)).astype(np.float32))
from repro.kernels import kv_pool
kp6 = jnp.asarray(rng6.integers(
    -127, 128, (B * NB + 1, BS, KV, DH)).astype(np.int8))
ks6 = jnp.ones((B * NB + 1, BS, KV, 1), jnp.float32)
pool6 = kv_pool.from_heads(kp6[None], ks6[None], kp6[None], ks6[None])
kv6, kvs6 = pool6[kv_pool.CODES], pool6[kv_pool.SCALES]
pt6 = jnp.arange(B * NB, dtype=jnp.int32).reshape(B, NB) + 1
pos6 = jnp.array([3, 5], jnp.int32)
sm6 = jnp.arange(B, dtype=jnp.int32)
wo6 = jnp.asarray(rng6.standard_normal((KV * G * DH, D)).astype(np.float32))

fused_fn = jax.jit(lambda q: fused_decode(
    q, kv6, kvs6, pt6, pos6, sm6, wo6, 0, kv_bits=8, interpret=True))
good = StepSpec(name="fused-step", fn=fused_fn, args=(q6,), fused_layers=1)
assert audit_step(good, rules=("fused_decode_single_dispatch",)) == []

unfused_fn = jax.jit(lambda q: engine.paged_attention(
    q, kv6, kvs6, pt6, pos6, layer=0, kv_bits=8, interpret=True))
bad = StepSpec(name="unfused-step", fn=unfused_fn, args=(q6,),
               fused_layers=1)
fs = audit_step(bad, rules=("fused_decode_single_dispatch",))
assert sorted({f.rule for f in fs}) == ["fused_decode_single_dispatch"], fs
msgs = " | ".join(f.message for f in fs)
assert "not on the fused path" in msgs, msgs
assert "non-fused pallas_call" in msgs, msgs

def sync_fn(q):
    out = fused_decode(q, kv6, kvs6, pt6, pos6, sm6, wo6, 0, kv_bits=8,
                       interpret=True)
    probe = jax.pure_callback(
        lambda o: np.float32(0.0),
        jax.ShapeDtypeStruct((), jnp.float32), out)
    return out + probe
synced = StepSpec(name="sync-step", fn=jax.jit(sync_fn), args=(q6,),
                  fused_layers=1)
f = only(audit_step(synced, rules=("fused_decode_single_dispatch",)),
         "fused_decode_single_dispatch")
assert "host" in f.message, f.message
print("SEEDED_FUSED_OK")

print("SEEDED_VIOLATIONS_OK")
"""


def test_seeded_violations_fire_exactly_their_rule_8dev():
    """For every compile-time contract, a deliberately broken step fires
    exactly that one rule (no rule is vacuous, none over-triggers)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["REPRO_TUNING_CACHE"] = os.path.join(
        tempfile.mkdtemp(prefix="audit-seeded-"), "cache.json")
    out = subprocess.run([sys.executable, "-c", _VIOLATIONS_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    for marker in ("SEEDED_XLA_OK", "SEEDED_PSUM_OK", "SEEDED_SCALE_OK",
                   "SEEDED_DONATE_OK", "SEEDED_TUNING_OK",
                   "SEEDED_VIOLATIONS_OK"):
        assert marker in out.stdout, (marker, out.stdout[-2000:])
