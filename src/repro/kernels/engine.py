"""Unified precision-dispatch kernel engine.

The paper's framework instantiates a *unique logic configuration* per
(activation x weight) bit-width (§II, Table II); FINN-R argues the framework
must search that configuration space per workload.  This module is the TPU
analogue: a kernel **registry** keyed on

    (weight_kind, act_bits, weight_bits, backend)

with one public entry point, :func:`qmatmul`, that

  1. prepares activations for the config (dynamic symmetric quantization,
     sign-binarization + bit-packing for the 1x1 XNOR path, or float
     passthrough),
  2. resolves the kernel implementation from the registry (Pallas kernels on
     TPU / interpret-mode, pure-jnp reference semantics as the ``xla``
     backend that XLA fuses well on CPU),
  3. resolves Pallas block sizes through the autotuner cache
     (:mod:`repro.kernels.tuning`) — serving never re-tunes, it looks up.

``weight_kind`` is the *storage* kind: "int" / "ternary" / "binary" for
bit-packed int32 words, "codes" for the unpacked int8 fallback (3-bit,
TP-misaligned K).  ``act_bits == 0`` means float activations.

Callers (models/layers, models/cnn, runtime, benchmarks) go through
``qmatmul`` / ``fake_quant_dot`` only; the per-kernel modules are private to
this engine and their own tests.
"""
from __future__ import annotations

import contextlib
import os
from collections.abc import Callable
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.core.precision import (
    A_FLOAT,
    PrecisionConfig,
    W_BINARY,
    W_FLOAT,
    W_INT,
    W_TERNARY,
)
from repro.core.quantize import act_fake_quant, weight_fake_quant, weight_quant

from . import ref, tuning
from .binary_matmul import binary_matmul
from .packed_matmul import packed_matmul
from .ternary_matmul import ternary_matmul

BACKEND_PALLAS = "pallas"
BACKEND_XLA = "xla"

# storage kind for the unpacked int8-codes fallback (3-bit, misaligned K)
K_CODES = "codes"


# ---------------------------------------------------------------------------
# packed-weight container + packers
# ---------------------------------------------------------------------------
class PackedWeight(NamedTuple):
    """A quantized+packed weight ready for the kernels.

    wt_packed: (N, K*bits/32) int32 (W^T packed along K) — or (N, K) int8 when
               the config doesn't pack (e.g. 3-bit).
    scale:     (N,) float32 per-output-channel alpha/dequant scale.
    bits:      field width (2 for ternary, 1 for binary).
    mode:      W_INT | W_TERNARY | W_BINARY.
    k:         unpacked reduction length.
    """
    wt_packed: jnp.ndarray
    scale: jnp.ndarray
    bits: int
    mode: str
    k: int


def weight_bits(cfg: PrecisionConfig) -> int:
    if cfg.w_mode == W_BINARY:
        return 1
    if cfg.w_mode == W_TERNARY:
        return 2
    return cfg.w_bits


def pack_weight(w, cfg: PrecisionConfig) -> PackedWeight:
    """Quantize a float weight (K, N) per ``cfg`` and pack W^T along K."""
    k, n = w.shape
    codes, scale = weight_quant(w, cfg, axis=0)        # codes (K, N), scale (1, N)
    scale = scale.reshape(n)
    ct = codes.T                                       # (N, K)
    if cfg.w_mode == W_BINARY:
        if k % 32 == 0:
            return PackedWeight(packing.pack_binary_pm1(ct), scale, 1, W_BINARY, k)
        return PackedWeight(ct.astype(jnp.int8), scale, 1, W_BINARY, k)
    bits = weight_bits(cfg)
    if cfg.pack_weights and 32 % bits == 0 and k % (32 // bits) == 0:
        return PackedWeight(packing.pack(ct, bits), scale, bits, cfg.w_mode, k)
    return PackedWeight(ct, scale, bits, cfg.w_mode, k)   # unpacked int8 fallback


def as_packed_weight(p: dict, cfg: PrecisionConfig) -> PackedWeight:
    """View a serving param dict ``{"wt_packed", "scale"}`` (models/convert
    output) as a :class:`PackedWeight`."""
    wt = p["wt_packed"]
    bits = weight_bits(cfg)
    if wt.dtype == jnp.int32:
        k = wt.shape[-1] * (32 // bits)
    else:
        k = wt.shape[-1]
    return PackedWeight(wt, p["scale"], bits, cfg.w_mode, k)


def storage_kind(pw: PackedWeight) -> str:
    if pw.wt_packed.dtype != jnp.int32:
        return K_CODES
    return pw.mode


def hbm_bytes(pw: PackedWeight) -> int:
    """Weight bytes as resident in HBM — the paper's storage saving, measurable."""
    return int(np.prod(pw.wt_packed.shape)) * pw.wt_packed.dtype.itemsize


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
KernelKey = tuple[str, int, int, str]        # (weight_kind, act_bits, weight_bits, backend)
_REGISTRY: dict[KernelKey, Callable] = {}

ACT_BITS_RANGE = range(0, 9)                 # 0 == float activations


def register_kernel(weight_kind: str, act_bits, w_bits, backend: str):
    """Decorator registering an implementation for one or more keys.

    ``act_bits`` / ``w_bits`` may be ints or iterables of ints."""
    a_list = (act_bits,) if isinstance(act_bits, int) else tuple(act_bits)
    w_list = (w_bits,) if isinstance(w_bits, int) else tuple(w_bits)

    def deco(fn):
        for a in a_list:
            for w in w_list:
                _REGISTRY[(weight_kind, a, w, backend)] = fn
        return fn
    return deco


def resolve_entry(weight_kind: str, act_bits: int, w_bits: int,
                  backend: str) -> tuple[Callable, KernelKey]:
    """Exact key first, then the ``xla`` backend as the universal fallback
    (e.g. binary weights with multi-bit activations have no Pallas PE).
    Returns ``(fn, matched_key)`` — the key's backend field is the backend
    that actually dispatched, which is how the invariant auditor
    (``repro.analysis``) tells a tuned Pallas impl from a silent xla
    fallback without string-matching function names."""
    for key in ((weight_kind, act_bits, w_bits, backend),
                (weight_kind, act_bits, w_bits, BACKEND_XLA)):
        fn = _REGISTRY.get(key)
        if fn is not None:
            return fn, key
    raise KeyError(
        f"no kernel for (weight_kind={weight_kind!r}, act_bits={act_bits}, "
        f"weight_bits={w_bits}, backend={backend!r}); registered: "
        f"{sorted(set((k[0], k[3]) for k in _REGISTRY))}")


def resolve(weight_kind: str, act_bits: int, w_bits: int, backend: str) -> Callable:
    return resolve_entry(weight_kind, act_bits, w_bits, backend)[0]


def available_kernels() -> dict[KernelKey, str]:
    return {k: fn.__name__ for k, fn in sorted(_REGISTRY.items())}


_BACKEND_OVERRIDE: str | None = None


def set_default_backend(backend: str | None) -> None:
    """Force the registry backend for every call that doesn't pass one
    explicitly; ``None`` restores the platform default.  The ``REPRO_BACKEND``
    environment variable does the same for subprocesses (e.g. HLO tests that
    exercise the Pallas interpret path on CPU)."""
    global _BACKEND_OVERRIDE
    if backend is not None and backend not in (BACKEND_PALLAS, BACKEND_XLA):
        raise ValueError(f"unknown backend {backend!r}")
    _BACKEND_OVERRIDE = backend


def default_backend() -> str:
    if _BACKEND_OVERRIDE is not None:
        return _BACKEND_OVERRIDE
    env = os.environ.get("REPRO_BACKEND")
    if env in (BACKEND_PALLAS, BACKEND_XLA):
        return env
    return BACKEND_PALLAS if jax.default_backend() == "tpu" else BACKEND_XLA


# ---------------------------------------------------------------------------
# dispatch trace (repro.analysis hook)
# ---------------------------------------------------------------------------
class DispatchEvent(NamedTuple):
    """One engine dispatch, recorded at trace time inside
    :func:`dispatch_trace`.  ``impl_backend`` is the registry key that
    actually matched (``xla`` when the requested backend silently fell back),
    so the contract checker never has to string-match HLO for kernel names.
    ``a_scale_shape`` is the dynamic activation scale's shape (None for
    float/pre-quantized inputs) against ``m_rows`` local rows — the per-row
    ``(M, 1)`` invariant from the scale-representation fix."""
    op: str                     # "qmatmul" | "decode_attention" | "paged_attention"
    kind: str                   # storage kind / attn kind
    requested_backend: str
    impl_backend: str
    a_bits: int                 # act bits (matmul) / kv_bits (attention)
    w_bits: int
    m_rows: int                 # local M rows (trace-time, shard-local)
    a_scale_shape: tuple[int, ...] | None
    block: tuple[int, int, int] | None


_DISPATCH_SINK: list | None = None
_DISPATCH_LISTENER = None


@contextlib.contextmanager
def dispatch_trace():
    """Collect every :class:`DispatchEvent` the engine emits while tracing
    under this context (``jax.make_jaxpr`` / ``.lower()`` of a step function
    re-runs the python callable, so dispatches fire here at zero runtime
    cost).  Nesting restores the previous sink on exit."""
    global _DISPATCH_SINK
    prev, _DISPATCH_SINK = _DISPATCH_SINK, []
    try:
        yield _DISPATCH_SINK
    finally:
        _DISPATCH_SINK = prev


def set_dispatch_listener(cb) -> None:
    """Install a persistent :class:`DispatchEvent` observer (or ``None`` to
    remove it).  Unlike :func:`dispatch_trace`, the listener survives across
    traces — the serving flight recorder (:mod:`repro.runtime.tracing`) uses
    it to put kernel dispatches on the serving timeline.  Dispatches still
    fire at jit trace time, so listener events mark (re)compiles."""
    global _DISPATCH_LISTENER
    _DISPATCH_LISTENER = cb


def _record_dispatch(**kw) -> None:
    if _DISPATCH_SINK is None and _DISPATCH_LISTENER is None:
        return
    ev = DispatchEvent(**kw)
    if _DISPATCH_SINK is not None:
        _DISPATCH_SINK.append(ev)
    if _DISPATCH_LISTENER is not None:
        _DISPATCH_LISTENER(ev)


# ---------------------------------------------------------------------------
# implementations.  Signature:
#     fn(x, pw, scale, bias, *, block, out_dtype, interpret,
#        a_scale=None) -> (M, N)
# ``x`` is pre-prepared by qmatmul (codes / float / packed pm1 bits);
# ``scale`` is the (N,) weight dequant scale; ``a_scale`` is the (M, 1)
# per-row dynamic activation scale (None for float/pre-quantized inputs).
# Epilogue order everywhere: acc * w_scale * a_scale + bias -> out_dtype,
# so Pallas and xla paths stay bit-identical for the integer kernels.
# ---------------------------------------------------------------------------
def _pad_rows(x, multiple):
    m = x.shape[0]
    pad = (-m) % multiple
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x, m


def _row_epilogue(out, a_scale, bias, out_dtype):
    """Post-kernel per-row dequant: applied AFTER slicing padded rows, with
    the bias held out of the kernel so the order matches the references."""
    out = out.astype(jnp.float32) * a_scale
    if bias is not None:
        out = out + bias[None, :]
    return out.astype(out_dtype)


@register_kernel(W_INT, ACT_BITS_RANGE, (2, 4, 8), BACKEND_PALLAS)
def _int_packed_pallas(x, pw, scale, bias, *, block, out_dtype, interpret,
                       a_scale=None):
    bm, bn, bk = block
    x_p, m0 = _pad_rows(x, bm)
    k_bias = bias if a_scale is None else None
    k_dtype = out_dtype if a_scale is None else jnp.float32
    out = packed_matmul(x_p, pw.wt_packed, scale, k_bias, bits=pw.bits,
                        bm=bm, bn=bn, bk=bk, out_dtype=k_dtype,
                        interpret=interpret)
    out = out[:m0]
    if a_scale is not None:
        out = _row_epilogue(out, a_scale, bias, out_dtype)
    return out


@register_kernel(W_INT, ACT_BITS_RANGE, tuple(range(1, 9)), BACKEND_XLA)
def _int_packed_xla(x, pw, scale, bias, *, block, out_dtype, interpret,
                    a_scale=None):
    return ref.packed_matmul_ref(x, pw.wt_packed, scale, pw.bits,
                                 bias=bias, out_dtype=out_dtype,
                                 row_scale=a_scale)


@register_kernel(W_TERNARY, ACT_BITS_RANGE, 2, BACKEND_PALLAS)
def _ternary_pallas(x, pw, scale, bias, *, block, out_dtype, interpret,
                    a_scale=None):
    bm, bn, bk = block
    x_p, m0 = _pad_rows(x, bm)
    k_bias = bias if a_scale is None else None
    k_dtype = out_dtype if a_scale is None else jnp.float32
    out = ternary_matmul(x_p, pw.wt_packed, scale, bias=k_bias,
                         bm=bm, bn=bn, bk=bk, out_dtype=k_dtype,
                         interpret=interpret)
    out = out[:m0]
    if a_scale is not None:
        out = _row_epilogue(out, a_scale, bias, out_dtype)
    return out


@register_kernel(W_TERNARY, ACT_BITS_RANGE, 2, BACKEND_XLA)
def _ternary_xla(x, pw, scale, bias, *, block, out_dtype, interpret,
                 a_scale=None):
    return ref.ternary_matmul_ref(x, pw.wt_packed, scale,
                                  bias=bias, out_dtype=out_dtype,
                                  row_scale=a_scale)


@register_kernel(W_BINARY, 1, 1, BACKEND_PALLAS)
def _binary_xnor_pallas(x, pw, scale, bias, *, block, out_dtype, interpret,
                        a_scale=None):
    """x: (M, K/32) int32 pm1 bits.  XNOR + popcount PE."""
    bm, bn, bk = block
    bkw = max(bk // 32, 1)
    x_p, m0 = _pad_rows(x, bm)
    k_dtype = out_dtype if a_scale is None else jnp.float32
    out = binary_matmul(x_p, pw.wt_packed, alpha=scale, k=pw.k,
                        bm=bm, bn=bn, bkw=bkw, out_dtype=k_dtype,
                        interpret=interpret)
    out = out[:m0]
    if a_scale is not None:
        return _row_epilogue(out, a_scale, bias, out_dtype)
    if bias is not None:
        out = (out + bias[None, :]).astype(out_dtype)
    return out


@register_kernel(W_BINARY, 1, 1, BACKEND_XLA)
def _binary_xnor_xla(x, pw, scale, bias, *, block, out_dtype, interpret,
                     a_scale=None):
    out = ref.binary_matmul_ref(x, pw.wt_packed, pw.k, alpha=scale,
                                out_dtype=jnp.float32, row_scale=a_scale)
    if bias is not None:
        out = out + bias[None, :]
    return out.astype(out_dtype)


@register_kernel(W_BINARY, tuple(a for a in range(0, 9) if a != 1), 1, BACKEND_XLA)
def _binary_dequant_xla(x, pw, scale, bias, *, block, out_dtype, interpret,
                        a_scale=None):
    """Binary weights with multi-bit/float activations (8xB): decode pm1
    codes and run the int/float dot — no XNOR trick applies."""
    if x.dtype == jnp.int32:                       # pre-packed pm1 activations
        return _binary_xnor_xla(x, pw, scale, bias, block=block,
                                out_dtype=out_dtype, interpret=interpret,
                                a_scale=a_scale)
    codes = packing.unpack_binary_pm1(pw.wt_packed)             # (N, K) int8
    if jnp.issubdtype(x.dtype, jnp.integer):
        acc = jax.lax.dot_general(x.astype(jnp.int8), codes,
                                  dimension_numbers=(((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        out = acc.astype(jnp.float32) * scale[None, :]
    else:
        out = jnp.dot(x.astype(jnp.float32),
                      codes.T.astype(jnp.float32)) * scale[None, :]
    if a_scale is not None:
        out = out * a_scale
    if bias is not None:
        out = out + bias[None, :]
    return out.astype(out_dtype)


@register_kernel(K_CODES, ACT_BITS_RANGE, tuple(range(1, 9)), BACKEND_XLA)
def _codes_xla(x, pw, scale, bias, *, block, out_dtype, interpret,
               a_scale=None):
    """Unpacked int8 codes storage (3-bit / TP-misaligned K)."""
    wt = pw.wt_packed                                           # (N, K) int8
    if jnp.issubdtype(x.dtype, jnp.integer):
        acc = jnp.dot(x.astype(jnp.int32), wt.T.astype(jnp.int32),
                      preferred_element_type=jnp.int32).astype(jnp.float32)
    else:
        acc = jnp.dot(x.astype(jnp.float32), wt.T.astype(jnp.float32))
    out = acc * scale[None, :]
    if a_scale is not None:
        out = out * a_scale
    if bias is not None:
        out = out + bias[None, :]
    return out.astype(out_dtype)


# ---------------------------------------------------------------------------
# activation preparation
# ---------------------------------------------------------------------------
def _prep_activations(x2, pw: PackedWeight, a_bits: int):
    """Returns (x_prepped, a_scale or None).  Integer inputs are taken as
    ready-made codes (the caller owns their scale); float inputs are
    dynamically quantized per the config (symmetric PER-ROW — the decode hot
    path can't afford a calibration pass).

    The per-row (per-token) scale is the fine-grained granularity that makes
    the whole serving stack batch-shape-independent: each row's codes and
    dequant depend only on that row, so shard_map over a local batch, a
    different M bucket, or a batch-1 recompute all reproduce the same values
    bit-exactly.  a_scale has shape (M, 1) — batch-SHAPED but never
    batch-COUPLED, and it shards row-wise alongside the activations
    (parallel.sharding.act_scale_specs).

    Activations are bit-packed for the XNOR kernel only when the weights are
    packed too (int32 storage): the unaligned-K binary fallback stores int8
    +/-1 codes, whose sign codes feed the plain integer dot directly."""
    xnor = pw.mode == W_BINARY and pw.wt_packed.dtype == jnp.int32
    if jnp.issubdtype(x2.dtype, jnp.integer):
        if xnor and a_bits == 1 and x2.dtype != jnp.int32:
            return packing.pack_binary_pm1(x2), None
        return x2, None
    if a_bits == 0:
        return x2, None
    if a_bits == 1:
        a_scale = jnp.maximum(
            jnp.mean(jnp.abs(x2), axis=1, keepdims=True), 1e-8)
        xq = jnp.where(x2 >= 0, 1, -1).astype(jnp.int8)
        if xnor:
            return packing.pack_binary_pm1(xq), a_scale
        return xq, a_scale
    qmax = (1 << (min(a_bits, 8) - 1)) - 1
    a_scale = jnp.maximum(
        jnp.max(jnp.abs(x2), axis=1, keepdims=True), 1e-8) / qmax
    xq = jnp.clip(jnp.round(x2 / a_scale), -qmax, qmax).astype(jnp.int8)
    return xq, a_scale


# ---------------------------------------------------------------------------
# the single public dispatch point
# ---------------------------------------------------------------------------
def qmatmul(x, pw: PackedWeight, cfg: PrecisionConfig, *, bias=None,
            out_dtype=jnp.float32, backend: str | None = None,
            block: tuple[int, int, int] | None = None,
            interpret: bool | None = None):
    """``x @ W`` with quantized/packed ``W`` under ``cfg``.

    x        : (..., K) float activations, int8 codes, or (binary) int32
               pm1-packed bits.  Leading dims are flattened and restored.
    pw       : :func:`pack_weight` / :func:`as_packed_weight` output.
    backend  : "pallas" | "xla"; default picks Pallas on TPU, the jnp
               reference semantics elsewhere.
    block    : explicit (bm, bn, bk) override; default consults the tuning
               cache (cache miss -> clipped default, never a sweep).
    """
    if cfg.w_mode == W_FLOAT:
        raise ValueError("qmatmul needs a quantized-weight config; "
                         "float weights are a plain jnp.dot")
    backend = backend or default_backend()
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    a_bits = 0 if (cfg.a_mode == A_FLOAT or cfg.a_bits > 8) else cfg.a_bits
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    xq, a_scale = _prep_activations(x2, pw, a_bits)

    # weight scale (N,) and per-row act scale (M, 1) stay separate — folding
    # the act scale into the weight scale would re-couple the epilogue to the
    # batch; the kernels apply acc * scale * a_scale + bias per row.
    scale = pw.scale.reshape(-1).astype(jnp.float32)

    kind = storage_kind(pw)
    fn, matched = resolve_entry(kind, a_bits, pw.bits, backend)
    if block is None and backend == BACKEND_PALLAS and kind != K_CODES:
        # x2.shape[0] is the LOCAL row count when tracing inside shard_map,
        # matching the per-device keys serving_tune_plan(…, mesh=…) pre-tunes.
        block = tuning.get_block_sizes(
            x2.shape[0], int(scale.shape[0]), pw.k,
            kind=kind, a_bits=a_bits, w_bits=pw.bits, backend=backend)
    elif block is None:
        block = tuning.DEFAULT_BLOCK       # xla impls ignore tile sizes
    _record_dispatch(op="qmatmul", kind=kind, requested_backend=backend,
                     impl_backend=matched[3], a_bits=a_bits, w_bits=pw.bits,
                     m_rows=int(x2.shape[0]),
                     a_scale_shape=(None if a_scale is None
                                    else tuple(a_scale.shape)),
                     block=tuple(block))
    out = fn(xq, pw, scale, bias, block=tuple(block), out_dtype=out_dtype,
             interpret=interpret, a_scale=a_scale)
    return out.reshape(*lead, out.shape[-1])


def qmatmul_experts(x, p: dict, cfg: PrecisionConfig):
    """Per-expert serving matmul: x (E, C, K) @ W_e (K, N) with the experts'
    packed storage ``{"wt_packed": (E, N, KW), "scale": (E, N)}``.

    Experts share one decode+einsum (float path: expert buffers are gathered
    activations, per-expert dynamic scales would change routing semantics) —
    kept in the engine so the storage decode lives in exactly one place."""
    wt = p["wt_packed"]
    if wt.dtype == jnp.int32:
        bits = weight_bits(cfg)
        codes = (packing.unpack_binary_pm1(wt) if cfg.w_mode == W_BINARY
                 else packing.unpack(wt, bits, signed=True))       # (E, N, K)
    else:
        codes = wt                                                 # int8 codes
    acc = jnp.einsum("eck,enk->ecn", x.astype(jnp.float32),
                     codes.astype(jnp.float32))
    return (acc * p["scale"][:, None, :]).astype(x.dtype)


def fake_quant_dot(x, w, cfg: PrecisionConfig, *, axis=0):
    """QAT-form ``x @ fake_quant(w)`` — the train-time counterpart of
    :func:`qmatmul` (float dot, STE-quantized weights)."""
    if cfg.w_mode == W_FLOAT:
        return jnp.dot(x, w.astype(x.dtype))
    wq = weight_fake_quant(w.astype(jnp.float32), cfg, axis=axis).astype(x.dtype)
    return jnp.dot(x, wq)


# ---------------------------------------------------------------------------
# attention-kernel registry (serving decode hot path)
# ---------------------------------------------------------------------------
# A second, smaller registry for the cache-bound attention kernels, keyed on
#
#     (attn_kind, kv_bits, backend)
#
# attn_kind: "decode" (dense (B, S, KV, Dh) cache) | "paged" (block pool +
# page table).  kv_bits is the KV-cache storage width (16 = raw model dtype,
# 8/4 = int codes + scales).  Resolution falls back to the ``xla`` backend
# exactly like the matmul registry — the xla implementations reproduce the
# in-model jnp math bit-exactly, so registering the dispatch in the serving
# path is a no-op off-TPU.

ATTN_DECODE = "decode"
ATTN_PAGED = "paged"
ATTN_FUSED = "fused_decode"
AttnKey = tuple[str, int, str]
_ATTN_REGISTRY: dict[AttnKey, Callable] = {}


def register_attention(kind: str, kv_bits, backend: str):
    b_list = (kv_bits,) if isinstance(kv_bits, int) else tuple(kv_bits)

    def deco(fn):
        for b in b_list:
            _ATTN_REGISTRY[(kind, b, backend)] = fn
        return fn
    return deco


def resolve_attention_entry(kind: str, kv_bits: int,
                            backend: str) -> tuple[Callable, AttnKey]:
    for key in ((kind, kv_bits, backend), (kind, kv_bits, BACKEND_XLA)):
        fn = _ATTN_REGISTRY.get(key)
        if fn is not None:
            return fn, key
    raise KeyError(
        f"no attention kernel for (kind={kind!r}, kv_bits={kv_bits}, "
        f"backend={backend!r}); registered: {sorted(_ATTN_REGISTRY)}")


def resolve_attention(kind: str, kv_bits: int, backend: str) -> Callable:
    return resolve_attention_entry(kind, kv_bits, backend)[0]


def available_attention_kernels() -> dict[AttnKey, str]:
    return {k: fn.__name__ for k, fn in sorted(_ATTN_REGISTRY.items())}


@register_attention(ATTN_DECODE, (8, 4), BACKEND_XLA)
def _decode_attn_xla(q, k, ks, v, vs, pos, *, kv_bits, dtype, block,
                     interpret):
    from .decode_attention import decode_attention_serving_ref
    return decode_attention_serving_ref(q, k, ks, v, vs, pos,
                                        kv_bits=kv_bits, dtype=dtype)


@register_attention(ATTN_DECODE, 8, BACKEND_PALLAS)
def _decode_attn_pallas(q, k, ks, v, vs, pos, *, kv_bits, dtype, block,
                        interpret):
    from .decode_attention import decode_attention
    chunk = block[2] if block else 512
    s = k.shape[1]
    while s % chunk:
        chunk //= 2
    return decode_attention(q, k, ks, v, vs, pos, chunk=max(chunk, 1),
                            interpret=interpret).astype(dtype)


@register_attention(ATTN_PAGED, (16, 8, 4), BACKEND_XLA)
def _paged_attn_xla(q, kv, kvs, extras, *, kv_bits, dtype, block, interpret):
    from .paged_attention import paged_attention_ref
    page_table, pos, layer = extras
    return paged_attention_ref(q, kv, kvs, page_table, pos, layer,
                               kv_bits=kv_bits, out_dtype=dtype)


@register_attention(ATTN_PAGED, (16, 8, 4), BACKEND_PALLAS)
def _paged_attn_pallas(q, kv, kvs, extras, *, kv_bits, dtype, block,
                       interpret):
    from .paged_attention import paged_attention
    page_table, pos, layer = extras
    return paged_attention(q, kv, kvs, page_table, pos, layer,
                           kv_bits=kv_bits,
                           interpret=interpret).astype(dtype)


def decode_attention(q, k_codes, k_scale, v_codes, v_scale, pos, *,
                     kv_bits: int = 8, dtype=jnp.float32,
                     backend: str | None = None,
                     interpret: bool | None = None):
    """One-step dense-cache decode attention via the registry.

    q: (B, KV, G, Dh); codes (B, S, KV, Dh'); scales (B, S, KV, 1);
    pos scalar or (B,).  The Pallas path reads its KV chunk length from the
    tuning cache (``autotune_decode_attention`` sweeps it offline)."""
    backend = backend or default_backend()
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    fn, matched = resolve_attention_entry(ATTN_DECODE, kv_bits, backend)
    block = None
    if backend == BACKEND_PALLAS:
        b, kv, g, dh = q.shape
        block = tuning.get_block_sizes(
            b * g, dh, k_codes.shape[1], kind=f"attn_{ATTN_DECODE}",
            a_bits=kv_bits, w_bits=8, backend=backend)
    _record_dispatch(op="decode_attention", kind=ATTN_DECODE,
                     requested_backend=backend, impl_backend=matched[2],
                     a_bits=kv_bits, w_bits=8, m_rows=int(q.shape[0]),
                     a_scale_shape=None,
                     block=None if block is None else tuple(block))
    return fn(q, k_codes, k_scale, v_codes, v_scale, pos, kv_bits=kv_bits,
              dtype=dtype, block=block, interpret=interpret)


def paged_attention(q, kv_pool, kv_scale, page_table, pos, *, layer,
                    kv_bits: int = 8, dtype=jnp.float32,
                    backend: str | None = None,
                    interpret: bool | None = None):
    """One-step paged decode attention (block pool + page table) via the
    registry.  Pool leaves as :mod:`repro.kernels.kv_pool` stores them,
    stacked over layers (``layer`` picks one); page_table (B, n_blocks)."""
    backend = backend or default_backend()
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    fn, matched = resolve_attention_entry(ATTN_PAGED, kv_bits, backend)
    _record_dispatch(op="paged_attention", kind=ATTN_PAGED,
                     requested_backend=backend, impl_backend=matched[2],
                     a_bits=kv_bits, w_bits=8, m_rows=int(q.shape[0]),
                     a_scale_shape=None, block=None)
    return fn(q, kv_pool, kv_scale, (page_table, pos, layer),
              kv_bits=kv_bits, dtype=dtype, block=None, interpret=interpret)


def autotune_decode_attention(*, b: int, s: int, kv: int, g: int, dh: int,
                              kv_bits: int = 8, iters: int = 2,
                              interpret: bool | None = None,
                              force: bool = False, seed: int = 0) -> dict:
    """Sweep the flash-decode kernel's KV chunk length for one cache shape
    class and persist the winner (tuning-cache kind ``attn_decode``; the
    stored block is (1, dh, chunk))."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    from .decode_attention import decode_attention as kernel
    rng = np.random.default_rng(seed)
    qmax = (1 << (kv_bits - 1)) - 1
    q = jnp.asarray(rng.normal(size=(b, kv, g, dh)).astype(np.float32))
    codes = lambda: jnp.asarray(
        rng.integers(-qmax, qmax + 1, (b, s, kv, dh)).astype(np.int8))
    scales = lambda: jnp.asarray(
        rng.uniform(1e-3, 1e-1, (b, s, kv, 1)).astype(np.float32))
    kc, ks, vc, vs = codes(), scales(), codes(), scales()
    pos = jnp.full((b,), s - 1, jnp.int32)

    def measure(block):
        return tuning.time_fn(
            lambda: kernel(q, kc, ks, vc, vs, pos, chunk=block[2],
                           interpret=interpret), iters=iters)

    cands = [(1, dh, c) for c in (128, 256, 512, 1024)
             if c <= s and s % c == 0] or [(1, dh, s)]
    return tuning.autotune(b * g, dh, s, kind=f"attn_{ATTN_DECODE}",
                           a_bits=kv_bits, w_bits=8, backend=BACKEND_PALLAS,
                           measure=measure, candidates=cands, force=force)


def _random_pool(rng, n_pool: int, bs: int, kv: int, dh: int,
                 kv_bits: int):
    """A one-layer stacked pool of random codes and scales (floats at 16),
    as :mod:`repro.kernels.kv_pool` stores it: ``(codes, scales or
    None)``."""
    from . import kv_pool
    if kv_bits == 16:
        mk = lambda: rng.normal(size=(1, n_pool, bs, kv, dh)
                                ).astype(np.float32)
        pool = kv_pool.from_heads(jnp.asarray(mk()), None, jnp.asarray(mk()),
                                  None)
        return pool[kv_pool.CODES], None
    qmax = (1 << (min(kv_bits, 8) - 1)) - 1
    shape = (1, n_pool, bs, kv, kv_pool.store_dh(dh, kv_bits))
    mk = lambda: jnp.asarray(
        rng.integers(-qmax, qmax + 1, shape).astype(np.int8))
    ms = lambda: jnp.asarray(
        rng.uniform(1e-3, 1e-1, shape[:4] + (1,)).astype(np.float32))
    pool = kv_pool.from_heads(mk(), ms(), mk(), ms())
    return pool[kv_pool.CODES], pool[kv_pool.SCALES]


def autotune_kv_block_size(*, b: int, kv: int, g: int, dh: int, s_max: int,
                           kv_bits: int = 8, candidates=(16, 32, 64, 128),
                           iters: int = 2, interpret: bool | None = None,
                           force: bool = False, seed: int = 0) -> dict:
    """Sweep the paged-attention kernel over candidate KV **block sizes** —
    the pool's block size is itself the kernel's sequence tile, so the sweep
    recommends the block size a deployment should configure
    (``preferred_kv_block_size`` reads it back; ``--kv-block-size 0`` in
    launch.serve uses it)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    from .paged_attention import paged_attention as kernel
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, kv, g, dh)).astype(np.float32))
    pos = jnp.full((b,), s_max - 1, jnp.int32)

    def measure(block):
        bs = block[2]
        nb = s_max // bs
        n_pool = b * nb + 1
        kvp, kvs = _random_pool(rng, n_pool, bs, kv, dh, kv_bits)
        pt = jnp.asarray(
            rng.permutation(b * nb).reshape(b, nb).astype(np.int32) + 1)
        return tuning.time_fn(
            lambda: kernel(q, kvp, kvs, pt, pos, 0, kv_bits=kv_bits,
                           interpret=interpret), iters=iters)

    cands = [(1, dh, bs) for bs in candidates if s_max % bs == 0] \
        or [(1, dh, s_max)]
    return tuning.autotune(b * g, dh, s_max, kind=f"attn_{ATTN_PAGED}",
                           a_bits=kv_bits, w_bits=8, backend=BACKEND_PALLAS,
                           measure=measure, candidates=cands, force=force)


def preferred_kv_block_size(*, b: int, kv: int, g: int, dh: int, s_max: int,
                            kv_bits: int = 8, default: int = 16) -> int:
    """Tuned pool block size for a cache shape class (cache lookup only —
    returns ``default`` on a cold cache, never sweeps)."""
    entry = tuning.lookup(b * g, dh, s_max, kind=f"attn_{ATTN_PAGED}",
                          a_bits=kv_bits, w_bits=8, backend=BACKEND_PALLAS)
    if entry is None:
        return default
    bs = int(entry["block"][2])
    return bs if s_max % bs == 0 else default


# ---------------------------------------------------------------------------
# fused ragged decode: paged attention + output projection, live slots only
# ---------------------------------------------------------------------------
def _project_wo(x, wo_p: dict, pcfg: PrecisionConfig, model_dtype):
    """The decode output projection, op-for-op identical to the model's
    ``qlinear_apply(p["wo"], x, cfg)`` — every branch (packed serving
    weights, float weights, fake-quant training form) reproduces the layer's
    numerics exactly, so composing it after a ragged attention gather stays
    bit-identical to the padded in-layer path (all scales are per-row)."""
    if "wt_packed" in wo_p:
        pw = as_packed_weight(wo_p, pcfg)
        return qmatmul(x, pw, pcfg).astype(model_dtype)
    w = wo_p["qw"]
    if pcfg.w_mode == W_FLOAT:
        return jnp.dot(x, w.astype(x.dtype))
    if pcfg.a_mode != A_FLOAT:
        x = act_fake_quant(x.astype(jnp.float32), pcfg).astype(x.dtype)
    return fake_quant_dot(x, w, pcfg, axis=0)


def _wo_is_float(wo_p: dict, pcfg: PrecisionConfig) -> bool:
    return "wt_packed" not in wo_p and pcfg.w_mode == W_FLOAT


@register_attention(ATTN_FUSED, (16, 8, 4), BACKEND_XLA)
def _fused_decode_xla(q, kv, kvs, extras, *, kv_bits, dtype, block,
                      interpret):
    """Reference composition: gather live rows -> paged-attention oracle ->
    the model's wo projection.  Per-row numerics (attention per slot, per-row
    activation scales) make the gathered sub-batch bit-identical to the
    padded full-batch layer math."""
    from .paged_attention import paged_attention_ref
    page_table, pos, slot_map, wo_p, pcfg, layer = extras
    ql = q[slot_map]
    attn = paged_attention_ref(ql, kv, kvs, page_table[slot_map],
                               jnp.asarray(pos)[slot_map], layer,
                               kv_bits=kv_bits, out_dtype=dtype)
    flat = attn.reshape(ql.shape[0], 1, -1)              # (L, 1, KV*G*Dh)
    return _project_wo(flat, wo_p, pcfg, dtype)          # (L, 1, D)


@register_attention(ATTN_FUSED, (16, 8, 4), BACKEND_PALLAS)
def _fused_decode_pallas(q, kv, kvs, extras, *, kv_bits, dtype, block,
                         interpret):
    """Single-dispatch fused kernel for float ``wo``; quantized ``wo``
    configs compose the paged-attention kernel with the engine's own
    ``qmatmul`` epilogue instead (the per-row requant epilogue must never
    fork numerics from the registry matmul the rest of the model uses)."""
    page_table, pos, slot_map, wo_p, pcfg, layer = extras
    if not _wo_is_float(wo_p, pcfg):
        attend = resolve_attention(ATTN_PAGED, kv_bits, BACKEND_PALLAS)
        ql = q[slot_map]
        attn = attend(ql, kv, kvs,
                      (page_table[slot_map], jnp.asarray(pos)[slot_map],
                       layer),
                      kv_bits=kv_bits, dtype=dtype, block=None,
                      interpret=interpret)
        flat = attn.reshape(ql.shape[0], 1, -1)
        return _project_wo(flat, wo_p, pcfg, dtype)
    from .decode_fused import fused_decode
    out = fused_decode(q, kv, kvs, page_table, pos, slot_map, wo_p["qw"],
                       layer, kv_bits=kv_bits, interpret=interpret)
    return out[:, None, :].astype(dtype)                 # (L, 1, D)


def fused_paged_decode(q, kv_pool, kv_scale, page_table, pos, slot_map,
                       wo_p: dict, pcfg: PrecisionConfig, *, layer,
                       kv_bits: int = 8, dtype=jnp.float32,
                       backend: str | None = None,
                       interpret: bool | None = None):
    """Fused ragged decode step via the registry: paged attention over
    layer ``layer`` of the stacked pool for the **live slots only**
    (``slot_map`` (L,) int32 into the padded batch) with the wo output
    projection folded in.  Returns the padded (B, 1, D)
    projected output — live rows carry the projection, dead rows are exact
    zeros (their residual stream is ignored by the batcher anyway).

    ``slot_map`` may repeat slot ids (occupancy-bucket padding): duplicates
    compute identical rows and the scatter writes identical values."""
    backend = backend or default_backend()
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b = q.shape[0]
    if slot_map is None:
        slot_map = jnp.arange(b, dtype=jnp.int32)
    slot_map = jnp.asarray(slot_map, jnp.int32)
    fn, matched = resolve_attention_entry(ATTN_FUSED, kv_bits, backend)
    _record_dispatch(op="fused_paged_decode", kind=ATTN_FUSED,
                     requested_backend=backend, impl_backend=matched[2],
                     a_bits=kv_bits, w_bits=8,
                     m_rows=int(slot_map.shape[0]),
                     a_scale_shape=None, block=None)
    compact = fn(q, kv_pool, kv_scale,
                 (page_table, pos, slot_map, wo_p, pcfg, layer),
                 kv_bits=kv_bits, dtype=dtype, block=None,
                 interpret=interpret)                    # (L, 1, D)
    d = compact.shape[-1]
    out = jnp.zeros((b, 1, d), compact.dtype)
    return out.at[slot_map].set(compact)


def autotune_fused_block_size(*, b: int, kv: int, g: int, dh: int, d: int,
                              s_max: int, kv_bits: int = 8,
                              candidates=(16, 32, 64, 128), iters: int = 2,
                              interpret: bool | None = None,
                              force: bool = False, seed: int = 0) -> dict:
    """Sweep the fused decode kernel over candidate pool block sizes (the
    pool block is the fused kernel's sequence tile too).  Persisted under
    tuning kind ``attn_fused_decode`` next to ``attn_paged`` so deployments
    can compare which dispatch shape prefers which block size."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    from .decode_fused import fused_decode as kernel
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, kv, g, dh)).astype(np.float32))
    wo = jnp.asarray(
        rng.normal(size=(kv * g * dh, d)).astype(np.float32) * dh ** -0.5)
    slot_map = jnp.arange(b, dtype=jnp.int32)
    pos = jnp.full((b,), s_max - 1, jnp.int32)

    def measure(block):
        bs = block[2]
        nb = s_max // bs
        n_pool = b * nb + 1
        kvp, kvs = _random_pool(rng, n_pool, bs, kv, dh, kv_bits)
        pt = jnp.asarray(
            rng.permutation(b * nb).reshape(b, nb).astype(np.int32) + 1)
        return tuning.time_fn(
            lambda: kernel(q, kvp, kvs, pt, pos, slot_map, wo, 0,
                           kv_bits=kv_bits, interpret=interpret),
            iters=iters)

    cands = [(1, dh, bs) for bs in candidates if s_max % bs == 0] \
        or [(1, dh, s_max)]
    return tuning.autotune(b * g, dh, s_max, kind=f"attn_{ATTN_FUSED}",
                           a_bits=kv_bits, w_bits=8, backend=BACKEND_PALLAS,
                           measure=measure, candidates=cands, force=force)


# ---------------------------------------------------------------------------
# legacy entry point (pre-engine signature; tests/benches of the raw kernels)
# ---------------------------------------------------------------------------
def quantized_matmul(x, pw: PackedWeight, bias=None, *,
                     out_dtype=jnp.float32, use_pallas: bool = False,
                     interpret: bool = True,
                     bm: int = 128, bn: int = 128, bk: int = 512):
    """Pre-engine dispatch (kept for compatibility): binary weights always
    binarize the activations; explicit tile sizes.  New code should call
    :func:`qmatmul` with a :class:`PrecisionConfig`."""
    backend = BACKEND_PALLAS if use_pallas else BACKEND_XLA
    scale = pw.scale.reshape(-1).astype(jnp.float32)
    if storage_kind(pw) == K_CODES:
        return _codes_xla(x, pw, scale, bias, block=None,
                          out_dtype=out_dtype, interpret=interpret)
    if pw.mode == W_BINARY:
        a_packed = packing.pack_binary_pm1(x) if x.dtype != jnp.int32 else x
        fn = resolve(W_BINARY, 1, 1, backend)
        return fn(a_packed, pw, scale, bias, block=(bm, bn, bk),
                  out_dtype=out_dtype, interpret=interpret)
    fn = resolve(pw.mode, 8, pw.bits, backend)
    return fn(x, pw, scale, bias, block=(bm, bn, bk),
              out_dtype=out_dtype, interpret=interpret)


# ---------------------------------------------------------------------------
# autotuning entry points
# ---------------------------------------------------------------------------
def autotune_matmul(cfg: PrecisionConfig, m: int, n: int, k: int, *,
                    backend: str | None = None, interpret: bool | None = None,
                    candidates=None, iters: int = 2, force: bool = False,
                    seed: int = 0) -> dict:
    """Sweep Pallas tiles for one (M, N, K, precision) shape class, timing
    on-device (interpret-mode on CPU), and persist the winner to the tuning
    cache.  Returns the cache entry (block, us, default_us, swept)."""
    backend = backend or BACKEND_PALLAS
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    pw = pack_weight(w, cfg)
    a_bits = 0 if (cfg.a_mode == A_FLOAT or cfg.a_bits > 8) else cfg.a_bits
    if a_bits == 1 or (cfg.w_mode == W_BINARY and a_bits == 1):
        x = jnp.asarray(rng.choice([-1, 1], (m, k)).astype(np.int8))
    elif a_bits == 0:
        x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    else:
        qmax = (1 << (a_bits - 1)) - 1
        x = jnp.asarray(rng.integers(-qmax, qmax + 1, (m, k)).astype(np.int8))

    def measure(block):
        return tuning.time_fn(
            lambda: qmatmul(x, pw, cfg, backend=backend, block=block,
                            interpret=interpret),
            iters=iters)

    kind = storage_kind(pw)
    if kind == K_CODES:
        raise ValueError(f"{cfg.name}: unpacked int8 storage has no Pallas "
                         "tiles to tune")
    return tuning.autotune(m, n, k, kind=kind, a_bits=a_bits, w_bits=pw.bits,
                           backend=backend, measure=measure,
                           candidates=candidates, force=force)


def model_matmul_shapes(model_cfg, tp: int = 1) -> set:
    """(N, K) pairs of every qlinear in a transformer-family ModelConfig —
    the shapes serving will hit (attention projections + FFN).

    ``tp`` > 1 yields the PER-DEVICE shard shapes under the model-axis
    sharding policy of parallel/sharding.py: output-sharded projections
    (wq/wk/wv, w_up/w_gate) shrink N -> N/tp, contraction-sharded ones
    (wo, w_down) shrink K -> K/tp — each ONLY when the relevant head count /
    hidden dim divides tp (otherwise that matrix replicates and keeps its
    global shape)."""
    shapes = set()
    d = getattr(model_cfg, "d_model", None)
    if not d:
        return shapes
    h = getattr(model_cfg, "n_heads", 0)
    kv = getattr(model_cfg, "n_kv_heads", h)
    dh = getattr(model_cfg, "dh", 0)
    f = getattr(model_cfg, "d_ff", 0)

    def div(n):
        return tp > 1 and n > 0 and n % tp == 0

    if h and dh:
        q_n = h * dh // tp if div(h) else h * dh          # wq: N-sharded
        kv_n = kv * dh // tp if div(kv) else kv * dh      # wk/wv: N-sharded
        o_k = h * dh // tp if div(h) else h * dh          # wo: K-sharded
        shapes |= {(q_n, d), (kv_n, d), (d, o_k)}
    if f:
        f_loc = f // tp if div(f) else f
        shapes |= {(f_loc, d), (d, f_loc)}                # w_up/gate | w_down
    return shapes


def _tunable_k(pcfg: PrecisionConfig, k: int) -> bool:
    """Whether a matmul with contraction length ``k`` has Pallas tiles to
    tune under ``pcfg`` (packed int32 storage; unpacked int8-codes fallback
    and float weights dispatch to jnp and ignore tiles)."""
    if pcfg.w_mode == W_FLOAT:
        return False
    bits = weight_bits(pcfg)
    packable = ((pcfg.pack_weights or pcfg.w_mode == W_BINARY)
                and 32 % bits == 0)
    return packable and k % (32 // bits) == 0


# ---------------------------------------------------------------------------
# precision-variant registry (adaptive serving)
# ---------------------------------------------------------------------------
class PrecisionVariant(NamedTuple):
    """One precision variant of a model's weights, held for runtime
    precision switching: the serving-packed params pytree plus the
    PrecisionConfig its matmuls dispatch under.  The adaptive batcher
    registers its variants here so tuning plans, benchmarks and tests can
    enumerate what a server is holding."""
    name: str                  # variant key, e.g. "fp32", "2xT"
    pcfg: PrecisionConfig
    params: object             # packed serving param pytree


# model-name -> variant-name -> PrecisionVariant
_VARIANTS: dict[str, dict[str, PrecisionVariant]] = {}


def register_variant(model_name: str, name: str, pcfg: PrecisionConfig,
                     params) -> PrecisionVariant:
    """Register (or replace) a named precision variant of one model's
    weights.  Idempotent per (model_name, name): re-registration overwrites,
    so rebuilding a batcher does not accumulate stale param pytrees."""
    var = PrecisionVariant(name, pcfg, params)
    _VARIANTS.setdefault(model_name, {})[name] = var
    return var


def registered_variants(model_name: str) -> dict[str, PrecisionVariant]:
    """The variants currently registered for ``model_name`` (possibly {})."""
    return dict(_VARIANTS.get(model_name, {}))


def clear_variants(model_name: str | None = None) -> None:
    """Drop registered variants (all models when ``model_name`` is None) —
    releases the param pytrees they pin."""
    if model_name is None:
        _VARIANTS.clear()
    else:
        _VARIANTS.pop(model_name, None)


def variant_tune_plans(model_cfg, *, n_slots: int, chunk_size: int,
                       draft_window: int = 0, mesh=None) -> dict:
    """Per-variant serving tune plans for every variant registered under
    ``model_cfg.name``.  ``draft_window`` > 0 adds the self-speculative
    verify dispatch's row bucket (``n_slots * (draft_window + 1)`` rows —
    the (B, W) window flattens into the matmul M axis) to every variant's
    plan, so a tuned adaptive server never sweeps mid-request."""
    extra = (int(n_slots) * (int(draft_window) + 1),) if draft_window else ()
    return {
        name: serving_tune_plan(model_cfg, var.pcfg, n_slots=n_slots,
                                chunk_size=chunk_size, mesh=mesh,
                                extra_m=extra)
        for name, var in registered_variants(model_cfg.name).items()
    }


def serving_tune_plan(model_cfg, pcfg: PrecisionConfig, *, n_slots: int,
                      chunk_size: int, mesh=None, extra_m=()) -> list:
    """The (M, N, K) shape classes the continuous batcher will dispatch —
    what :func:`tune_serving_shapes` sweeps.

    Without a mesh: ``chunk_size`` rows per prefill chunk and ``n_slots``
    rows per decode step, against the model's global (N, K) grid.  With a
    mesh the plan ADDS the per-device shard shapes: the decode batch shards
    over the data axes (local M = n_slots / dp; the batch-1 admission chunk
    stays M = chunk_size), and tensor-parallel layers hold local N or K
    divided by the model-axis size (pure-DP models keep tp = 1).  The
    per-device keys are what the serving hot path actually looks up — every
    step function dispatches shard_map-first, so qmatmul traces with LOCAL
    shapes (quantized-act configs included, now that act scales are per-row).
    The global-shape keys stay in the plan for the no-mesh runtime and the
    non-pure-DP pjit fallbacks."""
    plan = set()
    m_rows = (int(chunk_size), int(n_slots)) + tuple(int(m) for m in extra_m)
    for (n, k) in model_matmul_shapes(model_cfg):
        for m in m_rows:
            plan.add((m, n, k))            # global: today's pjit dispatch
    if mesh is not None:
        from repro.parallel.sharding import serving_shard_factors
        dp, tp = serving_shard_factors(model_cfg, mesh, n_slots)
        for (n, k) in model_matmul_shapes(model_cfg, tp=tp):
            for m in (int(chunk_size), max(1, int(n_slots) // dp)) \
                    + tuple(int(m) for m in extra_m):
                plan.add((m, n, k))        # per-device: shard_map dispatch
    return sorted(plan)


def tune_serving_shapes(model_cfg, pcfg: PrecisionConfig, *, n_slots: int,
                        chunk_size: int, mesh=None, extra_m=(),
                        backend: str | None = None,
                        candidates=None, iters: int = 2) -> list:
    """Pre-tune the exact M-row buckets the continuous batcher dispatches
    (see :func:`serving_tune_plan` — with ``mesh``, per-device shard shapes
    alongside the global ones; ``extra_m`` adds rows such as the speculative
    verify window's flattened batch).  With these entries warm, the serving
    loop never sees a tuning-cache miss — the scheduler's shape bucketing
    and this sweep share the same grid."""
    out = []
    for (m, n, k) in serving_tune_plan(model_cfg, pcfg, n_slots=n_slots,
                                       chunk_size=chunk_size, mesh=mesh,
                                       extra_m=extra_m):
        if not _tunable_k(pcfg, k):
            continue                       # unpacked storage: nothing to tune
        out.append(autotune_matmul(pcfg, m, n, k, backend=backend,
                                   candidates=candidates, iters=iters))
    return out


def prime_serving_shapes(model_cfg, pcfg: PrecisionConfig, *, n_slots: int,
                         chunk_size: int, mesh=None, extra_m=(),
                         backend: str | None = None) -> int:
    """Insert default-block cache entries for every tunable shape class in
    :func:`serving_tune_plan` WITHOUT measuring (``tuning.prime``) — the
    zero-cost warm-up the invariant auditor uses so ``tuning_cache_hit``
    checks key *coverage* (per-shard keys resolve, zero sweeps) rather than
    tile quality.  Returns the number of shape classes primed/present."""
    backend = backend or BACKEND_PALLAS
    n = 0
    for (m, nn, k) in serving_tune_plan(model_cfg, pcfg, n_slots=n_slots,
                                        chunk_size=chunk_size, mesh=mesh,
                                        extra_m=extra_m):
        if not _tunable_k(pcfg, k):
            continue
        a_bits = 0 if (pcfg.a_mode == A_FLOAT or pcfg.a_bits > 8) \
            else pcfg.a_bits
        # _tunable_k already restricts to packed storage, where the cache
        # kind is exactly the weight mode (int / ternary / binary)
        kind = pcfg.w_mode
        tuning.prime(m, nn, k, kind=kind, a_bits=a_bits,
                     w_bits=weight_bits(pcfg), backend=backend, persist=False)
        n += 1
    return n


def tune_model_shapes(model_cfg, pcfg: PrecisionConfig, *, m_rows=(8, 128),
                      backend: str | None = None, candidates=None,
                      iters: int = 2) -> list:
    """Pre-tune every (M, N, K) a model's serving path will dispatch, so the
    serving process itself only ever hits the cache.  Returns the entries."""
    out = []
    for (n, k) in sorted(model_matmul_shapes(model_cfg)):
        if not _tunable_k(pcfg, k):
            continue                       # unpacked storage: nothing to tune
        for m in m_rows:
            out.append(autotune_matmul(pcfg, m, n, k, backend=backend,
                                       candidates=candidates, iters=iters))
    return out
