"""Serve smollm-135m at its published widths on a TPU, through the normal
entry points (``repro.launch.serve`` -> ``PagedBatcher`` -> engine -> Pallas
kernels), and check what comes out.

    python chip_smoke.py             # one chip: phases (a) and (b)
    python chip_smoke.py --chips 4   # four chips: SPMD streams vs one device

One chip runs two phases in this process, each serving 8 requests (prompts
of 126-128 tokens, 32 new tokens, 8 slots) to completion from seeded random
weights:

  (a) the paper's configuration, ``--precision 2xT --paged --kv-bits 8``:
      ternary ``qmatmul`` with 2-bit activations, and paged attention
      composed with the packed ``wo``;
  (b) float weights, ``--precision fp32 --paged --kv-bits 8``, where the
      fused decode kernel (attention + ``wo`` in one dispatch) runs.

Each phase then recomputes the first request's prefill and first decode
step, with full-precision f32 matmuls: once with every Pallas kernel run
beside its ``xla`` reference entry on the same inputs (each gap must be
within ``KERNEL_TOL``), and once wholly on the ``xla`` reference semantics,
whose last-position logits are compared with the Pallas path's (gated by
``LOGIT_TOL`` for float weights; reported for 2xT, see ``LOGIT_TOL``).

``--chips 4`` runs phase (a)'s requests on one device and on (4, 1) and
(2, 2) meshes, and checks that the greedy streams are identical.

The script fails (non-zero exit, no result line) when JAX sees no TPU, when
``REPRO_BACKEND`` is set, when any main-path dispatch resolved to an ``xla``
entry, when any serving weight is stored as int8 codes instead of packed
words, when a request is not served to completion, when a kernel or the
float phase's logits disagree with the reference beyond their tolerance,
or when a phase raises.  Its last line is a JSON
object naming the device.  Timings it prints are smoke timings from one
cold run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import functools
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "smollm-135m"
REQUESTS, SLOTS, PROMPT_LEN, GEN, CHUNK = 8, 8, 128, 32, 32
# Both checks run with f32 matmuls at full precision
# (``jax.default_matmul_precision("highest")``); at TPU's default one-pass
# bf16 the paged attention kernel and its reference differ by ~3e-3.
#
# KERNEL_TOL: relative L2 gap allowed between each Pallas registry entry and
# its xla entry on the SAME inputs, inside the first request's prefill and
# first decode step.  The integer matmul kernels are exact; attention
# differs by f32 reassociation only (online vs. whole softmax: 1.2e-6 on a
# v5e).  An entry whose output is in the model's bf16 is allowed one bf16
# ulp (``finfo.eps``, 2^-7): the two roundings to bf16 can land on
# neighbouring values, and XLA may keep either side in f32 where its
# consumer is f32 (fp32 phase on a v5e: 1.8e-3).  A wrong head, block, scale
# or tile is O(1).
KERNEL_TOL = 1e-5
# LOGIT_TOL: relative L2 gap allowed between the last-position logits of
# the Pallas path and of the xla path, run as two separate programs.  It
# gates the float-weight phase only.  With 2-bit activation codes the
# logits are a discontinuous function of rounding: the two programs fuse
# their float epilogues differently, one ulp flips a code, and 30 layers
# decorrelate the logits (on a v5e: rel_l2 1.38 at prefill while every
# kernel agreed exactly).  For that phase the logit gap is reported and the
# kernel check is the gate.  For float weights the two programs still
# round the bf16 residual stream differently by up to an ulp per layer;
# 2e-2 allows that over 30 layers and is far below the O(1) of a wrong
# kernel.
LOGIT_TOL = 2e-2


class SmokeFailure(RuntimeError):
    pass


def _compile_clock():
    """Seconds JAX has spent lowering and compiling so far (tracing is left
    out: nested traces would count twice)."""
    import jax
    total = [0.0]
    events = {"/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration"}

    def listen(event, duration, **_):
        if event in events:
            total[0] += duration
    jax.monitoring.register_event_duration_secs_listener(listen)
    return lambda: total[0]


def _serve_args(precision, kv_bits, mesh=None):
    from repro.launch import serve
    argv = ["--arch", ARCH, "--precision", precision, "--paged",
            "--kv-bits", str(kv_bits), "--requests", str(REQUESTS),
            "--slots", str(SLOTS), "--prompt-len", str(PROMPT_LEN),
            "--gen", str(GEN), "--chunk-size", str(CHUNK)]
    if mesh:
        argv += ["--mesh", mesh]
    return serve.build_parser().parse_args(argv)


def _check_packed(params):
    """Every quantized serving weight must be packed int32 words: the int8
    codes fallback (models/convert.py) has no Pallas kernel."""
    import jax
    codes = [jax.tree_util.keystr(path)
             for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
             if jax.tree_util.keystr(path).endswith("['wt_packed']")
             and leaf.dtype != "int32"]
    if codes:
        raise SmokeFailure(f"weights stored as int8 codes: {codes[:4]}")


def _dispatch_summary(label, events, required_ops):
    from repro.kernels import engine
    counts = collections.Counter(
        (e.op, e.kind, e.impl_backend, e.a_bits, e.w_bits, e.block)
        for e in events)
    for (op, kind, impl, a, w, block), n in sorted(counts.items(), key=str):
        print(f"[{label}] dispatch: {op} kind={kind} a{a}w{w} -> "
              f"{impl} block={block} (traced {n}x)")
    fallback = sorted({(op, kind) for (op, kind, impl, *_) in counts
                       if impl != engine.BACKEND_PALLAS})
    if fallback:
        raise SmokeFailure(f"{label}: dispatches fell back to xla: {fallback}")
    missing = set(required_ops) - {op for op, *_ in counts}
    if missing:
        raise SmokeFailure(f"{label}: no {sorted(missing)} dispatch traced")


def _first_request_logits(model, params, cfg, args, prompt, tok=None):
    """Last-position logits of ``prompt``'s chunked paged prefill, then of
    one decode step feeding ``tok`` (the prefill's argmax when None), on a
    fresh one-sequence pool — the step functions the batcher jits."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as tfm
    from repro.runtime.serving import bucket_length

    kv_bits, bs, chunk = args.kv_bits, args.kv_block_size, args.chunk_size
    n_blocks = bucket_length(PROMPT_LEN + GEN, bs) // bs
    pool = tfm.make_pool(cfg, n_blocks + 1, bs, kv_bits)
    pt = jnp.arange(1, n_blocks + 1, dtype=jnp.int32)[None]
    prefill = jax.jit(lambda p, t, pool, pos: model.prefill_chunk_paged(
        p, t, pool, pt, pos, kv_bits))
    decode = jax.jit(lambda p, t, pool, pos: model.decode_step_paged(
        p, t, pool, pt, pos, kv_bits))
    length = prompt.shape[1]
    padded = jnp.zeros((1, bucket_length(length, chunk)), jnp.int32)
    padded = padded.at[:, :length].set(jnp.asarray(prompt))
    for start in range(0, padded.shape[1], chunk):
        logits, pool = prefill(params, padded[:, start:start + chunk], pool,
                               jnp.int32(start))
    first = logits[0, (length - 1) % chunk].astype(jnp.float32)
    if tok is None:
        tok = int(jnp.argmax(first))
    logits, pool = decode(params, jnp.full((1, 1), tok, jnp.int32), pool,
                          jnp.full((1,), length, jnp.int32))
    return first, logits[0, 0].astype(jnp.float32), tok


def _paired(key, fn, ref, gaps, *args, **kw):
    """Run a Pallas registry entry and its xla entry on the same inputs;
    record their relative L2 gap (a host callback) and pass the Pallas
    output on."""
    import jax
    import jax.numpy as jnp
    out, want = fn(*args, **kw), ref(*args, **kw)
    a, b = out.astype(jnp.float32), want.astype(jnp.float32)
    gap = jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30)
    tol = (KERNEL_TOL if out.dtype == jnp.float32
           else float(jnp.finfo(out.dtype).eps))
    jax.debug.callback(lambda g: gaps[key, tol].append(float(g)), gap)
    return out


@contextlib.contextmanager
def _paired_kernels(gaps):
    """While active, every Pallas entry of the engine's registries is run
    beside its xla entry (see ``_paired``).  The fused entry with packed
    ``wo`` is a composition whose parts are entries themselves, and whose
    own output passes through an activation quantizer, so it is left out."""
    from repro.kernels import engine
    saved = []
    for reg, at in ((engine._REGISTRY, 3), (engine._ATTN_REGISTRY, 2)):
        for key, fn in list(reg.items()):
            ref = reg.get(key[:at] + (engine.BACKEND_XLA,))
            if key[at] != engine.BACKEND_PALLAS or ref is None:
                continue
            saved.append((reg, key, fn))
            paired = functools.partial(_paired, key, fn, ref, gaps)
            if key[0] == engine.ATTN_FUSED:
                def paired(*a, _fn=fn, _paired_fn=paired, **kw):
                    wo_p = a[3][3]
                    return (_fn if "wt_packed" in wo_p else _paired_fn)(*a, **kw)
            reg[key] = paired
    try:
        yield
    finally:
        for reg, key, fn in saved:
            reg[key] = fn


def _check_numerics(label, model, params, cfg, args, gate_logits):
    import jax
    import numpy as np
    from repro.kernels import engine
    from repro.launch.serve import synthetic_prompts
    prompt = synthetic_prompts(cfg, args)[0]
    gaps = collections.defaultdict(list)
    with jax.default_matmul_precision("highest"):
        with _paired_kernels(gaps):
            pf, dec, tok = _first_request_logits(model, params, cfg, args,
                                                 prompt)
            jax.effects_barrier()
        engine.set_default_backend(engine.BACKEND_XLA)
        try:
            pf_ref, dec_ref, _ = _first_request_logits(model, params, cfg,
                                                       args, prompt, tok)
        finally:
            engine.set_default_backend(None)
    if not gaps:
        raise SmokeFailure(f"{label}: no Pallas entry ran in the check")
    for (key, tol), g in sorted(gaps.items(), key=str):
        print(f"[{label}] kernel {key} vs xla on the same inputs: max "
              f"rel_l2 {max(g)!r} over {len(g)} calls (tolerance {tol})")
        if not max(g) <= tol:
            raise SmokeFailure(f"{label}: {key} rel_l2 {max(g)} > {tol}")
    for step, got, want in (("prefill", pf, pf_ref),
                            ("decode", dec, dec_ref)):
        got, want = np.asarray(got), np.asarray(want)
        if not (np.all(np.isfinite(got)) and got.shape == (cfg.vocab,)):
            raise SmokeFailure(f"{label} {step}: logits {got.shape} not "
                               "finite / not vocab-wide")
        rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        print(f"[{label}] logits {step} pallas vs xla: rel_l2 {rel!r}, "
              f"max_abs {float(np.max(np.abs(got - want)))!r}, argmax "
              f"{int(got.argmax())} vs {int(want.argmax())} "
              + (f"(tolerance rel_l2 <= {LOGIT_TOL})" if gate_logits else
                 "(not gated: 2-bit activation codes)"))
        if gate_logits and not rel <= LOGIT_TOL:
            raise SmokeFailure(f"{label} {step}: rel_l2 {rel} > {LOGIT_TOL}")


def _serve(label, args, required_ops, compile_clock):
    """Serve ``args``'s requests through the launcher; returns the model,
    params, config and every request's token stream."""
    import jax
    from repro.kernels import engine
    from repro.launch import serve
    events = []
    t0, c0 = time.perf_counter(), compile_clock()
    engine.set_dispatch_listener(events.append)
    try:
        model, params, cfg, mesh = serve.build(args)
        _check_packed(params)
        streams = serve.batcher_loop(model, params, cfg, args, mesh=mesh)
    finally:
        engine.set_dispatch_listener(None)
    wall, comp = time.perf_counter() - t0, compile_clock() - c0
    if len(streams) != REQUESTS or any(len(s) != GEN for s in streams):
        raise SmokeFailure(f"{label}: {len(streams)} requests returned, "
                           f"lengths {[len(s) for s in streams]}")
    _dispatch_summary(label, events, required_ops)
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"[{label}] smoke timing, not a benchmark: {wall!r} s wall, of "
          f"which {comp!r} s lowering+compiling and {wall - comp!r} s "
          f"building, tracing and serving; peak_bytes_in_use {peak}")
    return model, params, cfg, streams


def one_chip(compile_clock):
    phases = [("a:2xT", "2xT", ("qmatmul", "fused_paged_decode"), False),
              ("b:fp32", "fp32", ("fused_paged_decode",), True)]
    for label, precision, required, gate_logits in phases:
        args = _serve_args(precision, kv_bits=8)
        model, params, cfg, _ = _serve(label, args, required, compile_clock)
        _check_numerics(label, model, params, cfg, args, gate_logits)


def four_chips(compile_clock):
    required = ("qmatmul", "fused_paged_decode")
    *_, want = _serve("a:2xT one device", _serve_args("2xT", 8), required,
                      compile_clock)
    for mesh in ("4,1", "2,2"):
        *_, got = _serve(f"a:2xT mesh {mesh}", _serve_args("2xT", 8, mesh),
                         required, compile_clock)
        same = got == want
        print(f"[mesh {mesh}] greedy streams identical to one device: "
              f"{same} ({REQUESTS} requests x {GEN} tokens)")
        if not same:
            raise SmokeFailure(f"mesh {mesh}: streams differ from one device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    opts = ap.parse_args(argv)
    if os.environ.get("REPRO_BACKEND"):
        print("chip_smoke: REPRO_BACKEND is set; it would pick the engine "
              "backend by hand", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX sees no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < opts.chips:
        print(f"chip_smoke: --chips {opts.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache
    cache = Path(use_compile_cache())
    warm = cache.is_dir() and any(cache.iterdir())
    print(f"device: {dev.device_kind}, count {len(devices)}; compile cache "
          f"{cache} ({'warm' if warm else 'cold'})")
    clock = _compile_clock()
    try:
        (four_chips if opts.chips == 4 else one_chip)(clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
