"""The compile-time contract checker: declarative rules over a traced +
compiled serving step.

Each rule inspects one artifact of a :class:`~repro.analysis.report.StepSpec`
and returns findings (empty == contract holds):

  ===============================  =========================================
  rule id                          contract
  ===============================  =========================================
  no_collectives                   pure-DP step compiles with ZERO
                                   collective ops (all-gather/all-reduce/...)
  pallas_call_present              every quantized-weight matmul dispatched
                                   a Pallas impl (engine dispatch events, not
                                   string matching) and a ``pallas_call``
                                   primitive landed in the jaxpr
  no_f32_upcast_of_quantized_operands
                                   no int8-family tensor is dequantized to
                                   float and fed to a dot_general outside a
                                   Pallas kernel (dtype dataflow walk)
  scale_shape_is_per_row           dynamic activation scales are (M, 1)
                                   per-row epilogue factors — never
                                   per-tensor (batch-coupled)
  cache_donated                    the compiled executable actually aliased
                                   the donated cache buffers
                                   (input_output_alias in the module header)
  tuning_cache_hit                 every per-shard tile key resolved from
                                   the tuning cache with zero misses/sweeps
  fused_decode_single_dispatch     the paged decode step traced exactly one
                                   fused-decode ``pallas_call`` per layer
                                   (1 scanned / n unrolled), no other
                                   pallas attention dispatch, and no
                                   host-callback primitive (host sync)
  ===============================  =========================================

The artifacts (dispatch events, jaxpr, compiled HLO text, tuning-stats
delta) are produced once per step by :func:`audit_step` and shared across
rules — tracing re-runs the python callable, so the engine's
``dispatch_trace`` hooks and tuning lookups fire at trace time with the
exact (shard-local) shapes the hot loop uses.
"""
from __future__ import annotations

import jax

from . import hlo as hlo_walker
from . import jaxpr_walker
from .report import Finding, StepSpec


class StepArtifacts:
    """Lazily computed trace/compile products of one step, shared by rules."""

    def __init__(self, spec: StepSpec):
        self.spec = spec
        self._jaxpr = None
        self._events = None
        self._tuning_delta = None
        self._hlo_text = None

    # -- trace-time artifacts (jaxpr + engine dispatch events + tuning) -----
    def _trace(self):
        if self._jaxpr is not None:
            return
        from repro.kernels import engine, tuning
        before = tuning.stats()
        with engine.dispatch_trace() as events:
            self._jaxpr = jax.make_jaxpr(self.spec.fn)(*self.spec.args)
        after = tuning.stats()
        self._events = list(events)
        self._tuning_delta = {k: after[k] - before.get(k, 0) for k in after}

    @property
    def jaxpr(self):
        self._trace()
        return self._jaxpr

    @property
    def events(self) -> list:
        self._trace()
        return self._events

    @property
    def tuning_delta(self) -> dict:
        self._trace()
        return self._tuning_delta

    # -- compile-time artifact (post-partitioning HLO text) -----------------
    @property
    def hlo_text(self) -> str:
        if self._hlo_text is None:
            # Trace first: lowering warms pjit's trace cache, after which
            # make_jaxpr would reuse the cached jaxpr without re-running the
            # python callable — and the engine dispatch events with it.
            self._trace()
            self._hlo_text = (self.spec.fn.lower(*self.spec.args)
                              .compile().as_text())
        return self._hlo_text


def _rule_no_collectives(art: StepArtifacts) -> list[Finding]:
    out = []
    comps = hlo_walker.parse_hlo(art.hlo_text)
    for op in hlo_walker.collective_ops(comps):
        out.append(Finding(
            rule="no_collectives", step=art.spec.name,
            message=f"pure-DP step compiled a {op.opcode} "
                    f"({op.out_bytes} bytes)",
            locus=op.line[:160]))
    return out


def _rule_pallas_call_present(art: StepArtifacts) -> list[Finding]:
    out = []
    matmul_events = [e for e in art.events if e.op == "qmatmul"]
    for e in matmul_events:
        if e.kind == "codes":
            # unpacked int8-codes storage (3-bit / misaligned K) has no
            # Pallas PE by design — the jnp fallback IS its registration
            continue
        if e.impl_backend != "pallas":
            out.append(Finding(
                rule="pallas_call_present", step=art.spec.name,
                message=f"qmatmul dispatched the {e.impl_backend!r} impl for "
                        f"kind={e.kind} a{e.a_bits}w{e.w_bits} "
                        f"(requested {e.requested_backend!r})",
                locus=f"dispatch m={e.m_rows} block={e.block}"))
    pallas_events = [e for e in matmul_events if e.impl_backend == "pallas"]
    if not matmul_events:
        out.append(Finding(
            rule="pallas_call_present", step=art.spec.name,
            message="no qmatmul dispatch events recorded — the step never "
                    "reached the kernel engine"))
    elif not out and pallas_events \
            and not jaxpr_walker.has_primitive(art.jaxpr, "pallas_call"):
        out.append(Finding(
            rule="pallas_call_present", step=art.spec.name,
            message="engine dispatched pallas impls but no pallas_call "
                    "primitive landed in the traced jaxpr"))
    return out


def _rule_no_upcast(art: StepArtifacts) -> list[Finding]:
    return [Finding(
        rule="no_f32_upcast_of_quantized_operands", step=art.spec.name,
        message="quantized (int8-family) operand dequantized to float and "
                f"consumed by {prim} outside a Pallas kernel",
        locus=excerpt)
        for prim, excerpt in jaxpr_walker.find_float_upcasts(art.jaxpr)]


def _rule_scale_per_row(art: StepArtifacts) -> list[Finding]:
    out = []
    for e in art.events:
        if e.op != "qmatmul" or e.a_scale_shape is None:
            continue
        if tuple(e.a_scale_shape) != (e.m_rows, 1):
            out.append(Finding(
                rule="scale_shape_is_per_row", step=art.spec.name,
                message=f"activation scale has shape {e.a_scale_shape} for "
                        f"M={e.m_rows} local rows — expected per-row "
                        f"({e.m_rows}, 1)",
                locus=f"dispatch kind={e.kind} a{e.a_bits}w{e.w_bits}"))
    return out


def _rule_cache_donated(art: StepArtifacts) -> list[Finding]:
    if hlo_walker.donated_aliases(art.hlo_text):
        return []
    return [Finding(
        rule="cache_donated", step=art.spec.name,
        message="no input_output_alias in the compiled module header — the "
                f"cache (argnums {art.spec.donate_argnums}) was not donated",
        locus=art.hlo_text.splitlines()[0][:160] if art.hlo_text else "")]


def _rule_tuning_cache_hit(art: StepArtifacts) -> list[Finding]:
    d = art.tuning_delta
    if d.get("misses", 0) == 0 and d.get("sweeps", 0) == 0:
        return []
    return [Finding(
        rule="tuning_cache_hit", step=art.spec.name,
        message=f"{d.get('misses', 0)} tuning-cache miss(es) and "
                f"{d.get('sweeps', 0)} sweep(s) while tracing — per-shard "
                "tile keys are not covered by the cache",
        locus=f"stats delta: {d}")]


# the name every fused-decode pallas_call is given (kernels/decode_fused.py)
_FUSED_KERNEL_NAME = "fused_decode_kernel"
# primitives that round-trip through the host mid-step (a decode step
# containing one cannot be a single async device dispatch)
_HOST_SYNC_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                    "callback")


def _rule_fused_decode_single_dispatch(art: StepArtifacts) -> list[Finding]:
    """The tentpole contract of the fused ragged decode path: the compiled
    paged decode step issues ONE fused pallas_call per layer — attention,
    KV dequant, and the wo projection together — and nothing else that
    dispatches attention or syncs through the host.  Under ``lax.scan`` over
    layers the fused kernel appears once (in the scan body sub-jaxpr);
    unrolled stacks show ``fused_layers`` of them."""
    spec = art.spec
    n_layers = int(spec.fused_layers or 0)
    fused = other = 0
    other_names: list[str] = []
    syncs: list[str] = []
    for eqn in jaxpr_walker.iter_eqns(art.jaxpr):
        name = eqn.primitive.name
        if name == "pallas_call":
            info = eqn.params.get("name") or ""
            if _FUSED_KERNEL_NAME in info:
                fused += 1
            else:
                other += 1
                other_names.append(info or "<unnamed>")
        elif name in _HOST_SYNC_PRIMS:
            syncs.append(name)
    out = []
    if fused not in (1, n_layers):
        out.append(Finding(
            rule="fused_decode_single_dispatch", step=spec.name,
            message=f"expected one fused-decode pallas_call per layer "
                    f"(1 scanned or {n_layers} unrolled), traced {fused} — "
                    "the decode step is not on the fused path"))
    if other:
        out.append(Finding(
            rule="fused_decode_single_dispatch", step=spec.name,
            message=f"{other} non-fused pallas_call dispatch(es) in the "
                    "decode step — attention + projection must land as one "
                    "fused dispatch per layer",
            locus=", ".join(sorted(set(other_names))[:4])))
    if syncs:
        out.append(Finding(
            rule="fused_decode_single_dispatch", step=spec.name,
            message=f"host-callback primitive(s) {sorted(set(syncs))} in the "
                    "decode step — the fused path must not sync through the "
                    "host mid-step"))
    return out


RULES = {
    "no_collectives": _rule_no_collectives,
    "pallas_call_present": _rule_pallas_call_present,
    "no_f32_upcast_of_quantized_operands": _rule_no_upcast,
    "scale_shape_is_per_row": _rule_scale_per_row,
    "cache_donated": _rule_cache_donated,
    "tuning_cache_hit": _rule_tuning_cache_hit,
    "fused_decode_single_dispatch": _rule_fused_decode_single_dispatch,
}


def audit_step(spec: StepSpec, rules=None) -> list[Finding]:
    """Check one serving step against its contracts.  ``rules`` defaults to
    the step's wiring-derived set (:meth:`StepSpec.default_rules`); unknown
    rule ids raise.  Returns findings — empty means every contract holds."""
    names = tuple(rules) if rules is not None else spec.default_rules()
    unknown = [r for r in names if r not in RULES]
    if unknown:
        raise KeyError(f"unknown rule(s) {unknown}; known: {sorted(RULES)}")
    art = StepArtifacts(spec)
    findings: list[Finding] = []
    for name in names:
        findings.extend(RULES[name](art))
    return findings
