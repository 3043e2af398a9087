"""PagedBatcher — continuous batching over the paged, quantized KV cache.

A drop-in :class:`repro.runtime.serving.ContinuousBatcher` whose KV state is
a global block pool + per-slot page tables instead of dense (n_slots, s_max)
slabs:

  * **Admission** looks the prompt up in the radix prefix cache; matched
    full blocks are referenced (refcount++) into the new request's page
    table and their prefill is SKIPPED — chunked prefill starts at the first
    uncached position.  Under ``reserve="prompt"`` (the default) admission
    reserves only the blocks the *prompt* needs; ``reserve="budget"`` keeps
    the old reserve-everything policy (every block through the generation
    budget up front, so decode never allocates and nothing is ever
    preempted — capacity stays budget-bound).
  * **Decode** allocates lazily: a slot crossing a block boundary takes one
    block from the pool right before the batched step.  When the pool is
    exhausted mid-flight, the scheduler **preempts** the lowest-priority
    running request — latest-admitted first, the mid-flight admission
    before any active slot — releasing its blocks and re-queuing it at the
    queue head with its generated tokens carried along; re-admission
    prefills prompt + generated tokens (chunked), so the stream continues
    bit-exactly without replaying a token.  The recompute is mostly radix
    hits because preemption and release both register the victim's full
    (prompt + generated) block-aligned prefix.  With ``preemption="off"``
    an allocation-starved slot instead *stalls* (its dead write deflects to
    the null block; the token is re-fed once a block frees) — and the
    scheduler raises if every active slot is stalled with no admission in
    flight, since no progress is then possible.
  * **Generated-suffix sharing**: ``_release_slot`` and preemption register
    decode-written blocks in the radix tree (kind ``suffix``) for EVERY
    config — dynamic activation quantization is per-row
    (engine._prep_activations), so decode KV is a per-position function of
    the token stream and a B=1 recompute reproduces it bit-exactly,
    quantized-act precisions included.
  * **Prefill chunks** write their KV directly into the owning blocks
    through the page table (no separate admission cache, no slot-join copy).
  * **kv_bits** ∈ {16, 8, 4}: blocks store raw model-dtype KV or int8/int4
    codes + per-position scales (the dense cache's quantizer, so paged-8
    streams are bit-identical to the dense batcher with ``cfg.kv_bits=8``,
    and paged-16 to the unquantized dense batcher).

Exactness: with greedy sampling and ``s_max`` aligned to
lcm(chunk, block_size), paged generations are bit-identical to the dense
batcher's REGARDLESS of preemption timing — the recompute prefill sees the
identical token sequence chunk-aligned (matches align down to
lcm(block, chunk) boundaries), per-position attention and per-row activation
quantization are row-consistent across chunk and decode dispatch shapes, and
a prefix/suffix-cache hit never changes outputs: matched blocks hold exactly
the KV the skipped prefill would have recomputed.

Progress: the earliest-admitted active request is never a preemption victim
(victims are strictly later-admitted) and a sole resident request never
needs more than ``blocks_per_seq`` blocks — which the constructor guarantees
the pool holds — so every admitted request eventually finishes even on a
pool overcommitted far below the workload's aggregate budget.
"""
from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.errors import PoolFootprintError
from repro.runtime.serving import (ContinuousBatcher, Request, ServingConfig,
                                   _Admission, _coerce_config, _sample_rows,
                                   bucket_length)

from .pool import BlockPool
from .radix import RadixPrefixCache

KV_BITS_CHOICES = (16, 8, 4)
RESERVE_CHOICES = ("prompt", "budget")
PREEMPTION_CHOICES = ("recompute", "off")


def _select_paged(logits, greedy, slot_map, tok, pos, nout,
                  temps, topks, seeds, rids):
    """Paged counterpart of serving's dense select step: the decode step
    returned COMPACT (L, 1, V) logits for the slots in ``slot_map``, so the
    sampling params are gathered by slot id and the full device-resident
    buffers are scatter-updated at those rows only.  Bucket padding repeats
    a live slot id — every update is an idempotent ``.set`` (same key, same
    inputs, same value), so duplicates are exact no-ops.  Returns the full
    (n_slots,) next-token vector (dead rows keep their previous token)."""
    nxt = _sample_rows(logits[:, 0], greedy, temps[slot_map],
                       topks[slot_map], seeds[slot_map], rids[slot_map],
                       nout[slot_map])
    tok2 = tok.at[slot_map].set(nxt[:, None])
    pos2 = pos.at[slot_map].set(pos[slot_map] + 1)
    nout2 = nout.at[slot_map].set(nout[slot_map] + 1)
    return tok2[:, 0], tok2, pos2, nout2


def _attn_layers(cfg) -> int:
    return sum(1 for m in cfg.layer_pattern if m.startswith("attn")) \
        * cfg.n_periods


def paged_block_bytes(cfg, block_size: int, kv_bits: int) -> int:
    """HBM bytes one physical block costs across the whole layer stack, as
    the pool is laid out on the TPU (``repro.kernels.kv_pool``: lanes pad to
    128, rows to the tile height; a block's codes tile and its share of a
    scale tile) — the denominator of the effective-capacity claim."""
    from repro.kernels import kv_pool
    tile = kv_pool.scale_tile_blocks(block_size, cfg.n_kv_heads)
    return _attn_layers(cfg) * kv_pool.pool_bytes(
        tile, block_size, cfg.n_kv_heads, cfg.dh, kv_bits, cfg.dtype) // tile


def paged_capacity_blocks(cfg, pool_bytes: int, block_size: int,
                          kv_bits: int) -> int:
    """Allocatable blocks (excluding the null block) a byte budget buys: the
    most blocks whose pool, laid out, fits the budget."""
    from repro.kernels import kv_pool
    nb = pool_bytes // paged_block_bytes(cfg, block_size, kv_bits)
    while nb > 0 and _attn_layers(cfg) * kv_pool.pool_bytes(
            nb, block_size, cfg.n_kv_heads, cfg.dh, kv_bits,
            cfg.dtype) > pool_bytes:
        nb -= 1
    return max(nb - 1, 0)


class PagedBatcher(ContinuousBatcher):
    """Slot-based continuous batching over a paged KV pool.

    Extra knobs over the dense batcher:
      kv_bits      : 16 (raw) | 8 | 4 (codes + per-position scales)
      block_size   : positions per physical block (s_max rounds up to it)
      num_blocks   : pool size incl. the null block (default: every slot can
                     hold a full sequence, plus one sequence of slack for
                     the prefix cache)
      pool_bytes   : alternative to num_blocks — size the pool to a byte
                     budget via :func:`paged_capacity_blocks`
      prefix_cache : enable radix prefix sharing (on by default)
      reserve      : "prompt" (default) — admission reserves prompt blocks
                     only, decode allocates on demand; "budget" — reserve
                     the whole generation budget up front (never preempts)
      preemption   : "recompute" (default) — on pool exhaustion, preempt the
                     latest-admitted request and recompute it via chunked
                     prefill at re-admission; "off" — starved slots stall
                     until blocks free up
    """

    def __init__(self, model, params,
                 config: ServingConfig | None = None, *,
                 metrics=None, tracer=None, **legacy):
        config = _coerce_config(config, legacy, type(self).__name__)
        if config.kv_bits not in KV_BITS_CHOICES:
            raise ValueError(f"kv_bits must be one of {KV_BITS_CHOICES}, "
                             f"got {config.kv_bits}")
        if config.reserve not in RESERVE_CHOICES:
            raise ValueError(f"reserve must be one of {RESERVE_CHOICES}, "
                             f"got {config.reserve!r}")
        if config.preemption not in PREEMPTION_CHOICES:
            raise ValueError(f"preemption must be one of "
                             f"{PREEMPTION_CHOICES}, got {config.preemption!r}")
        if model.decode_step_paged is None:
            raise ValueError(
                f"{model.cfg.name}: the paged KV cache needs an "
                "attention-only token LM (SSM state has no sequence dim to "
                "page; embeds/enc-dec stacks have no token stream to share)")
        if model.cfg.kv_bits:
            raise ValueError(
                "paged serving owns KV quantization (kv_bits=...); build the "
                "model with cfg.kv_bits=0")
        self.kv_bits = int(config.kv_bits)
        self.block_size = int(config.block_size)
        # fused ragged decode (read in _build_runtime, which super().__init__
        # invokes): one engine dispatch per layer for attention + wo, over
        # live-slot occupancy buckets instead of the padded batch
        self._fused = bool(config.fused_decode)
        self._ragged = bool(config.ragged_decode)
        self.prefix_cache = bool(config.prefix_cache)
        self.reserve = config.reserve
        self.preemption = config.preemption
        self._num_blocks_arg = config.num_blocks
        self._pool_bytes_arg = config.pool_bytes
        # cross-lane byte budget (runtime.adaptive wires one in; None = the
        # lane's own pool is the only limit)
        self._ledger = None
        # generated-suffix blocks register for every precision: decode KV is
        # a per-position function of the token stream because dynamic act
        # quantization is per-row (batch-shape-free numerics), so a B=1
        # recompute reproduces decode-written blocks bit-exactly
        from repro.core.precision import W_FLOAT, get_precision, signed
        pcfg = signed(get_precision(model.cfg.precision))
        self._share_suffix = True
        # ---- self-speculative decoding (draft with a low-bit weight
        # variant, verify with the full-precision weights in ONE windowed
        # decode step; bit-identical to the sequential fp stream) ----------
        self.spec = bool(config.speculative)
        self.spec_k = int(config.draft_k)
        self.draft_precision = config.draft_precision
        if self.spec:
            if config.mesh is not None:
                raise ValueError(
                    "speculative decoding is single-host for now (the "
                    "windowed verify step has no sharded dispatch)")
            if self.spec_k < 1:
                raise ValueError(f"draft_k must be >= 1, got {self.spec_k}")
            if model.decode_window_paged is None:
                raise ValueError(
                    f"{model.cfg.name}: speculative decoding needs the "
                    "windowed paged decode path (attention-only token LM)")
            if pcfg.w_mode != W_FLOAT:
                raise ValueError(
                    f"{model.cfg.precision}: self-speculative serving needs "
                    "a float-weight primary — float weights are what the "
                    "draft variant packs down from.  (Quantized-act "
                    "primaries are fine: per-row act scales keep the verify "
                    "window's rows bit-identical to sequential decode.)")
            get_precision(self.draft_precision)   # unknown name raises here
        super().__init__(model, params, config, metrics=metrics,
                         tracer=tracer)

    # ------------------------------------------------------------- runtime
    def _build_runtime(self, model, cfg, mesh):
        if not self.chunk_size:
            raise ValueError(
                f"{cfg.name}: paged serving admits prompts through chunked "
                "prefill; pass a chunk_size > 0")
        bs = self.block_size
        self.s_pad = bucket_length(self.s_max, bs)
        self.blocks_per_seq = self.s_pad // bs
        if self._num_blocks_arg is not None:
            num_blocks = int(self._num_blocks_arg)
        elif self._pool_bytes_arg is not None:
            num_blocks = 1 + paged_capacity_blocks(
                cfg, self._pool_bytes_arg, bs, self.kv_bits)
        else:
            num_blocks = 1 + (self.n_slots + 1) * self.blocks_per_seq
        # budget reservation should serve ANY admissible request, so the
        # pool must hold the worst-case lifetime footprint (an s_max-1
        # prompt writes through position s_max-1 -> blocks_per_seq blocks);
        # prompt reservation only needs per-request footprints to fit, and
        # ``submit`` checks those request by request
        min_blocks = 1 + (self.blocks_per_seq if self.reserve == "budget"
                          else 1)
        if num_blocks < min_blocks:
            raise ValueError(
                f"pool of {num_blocks} blocks cannot hold one "
                + (f"{self.blocks_per_seq}-block sequence "
                   if self.reserve == "budget" else "block ")
                + f"(s_max={self.s_max}, block_size={bs}, "
                  f"reserve={self.reserve!r})")
        self.num_blocks = num_blocks

        self.pool_meta = BlockPool(num_blocks)
        self.radix = RadixPrefixCache(self.pool_meta, bs) \
            if self.prefix_cache else None
        from repro.models import transformer as tfm
        self.pool = tfm.make_pool(cfg, num_blocks, bs, self.kv_bits,
                                  mesh=mesh)
        self._pt = np.zeros((self.n_slots, self.blocks_per_seq), np.int32)
        self._slot_blocks: list[list[int] | None] = [None] * self.n_slots
        # admission order = preemption priority (earlier admitted wins)
        self._slot_seq = np.zeros(self.n_slots, np.int64)
        self._seq_counter = 0
        # rid -> positions computed before its preemption (decode-written,
        # or chunk-prefilled for a mid-admission victim): the re-admission's
        # recomputed_tokens debt, net of whatever the radix serves back
        self._recompute_debt = {}
        self.metrics.on_kv_blocks(0, num_blocks - 1)

        if self.config.autotune and self._ragged:
            # the ragged dispatch compiles one decode program per occupancy
            # bucket: warm the tuning cache for every bucket's M rows too,
            # so no compiled shape ever sweeps mid-request (the base
            # autotune in ContinuousBatcher.__init__ covered n_slots only)
            from repro.core.precision import get_precision, signed
            from repro.kernels import engine
            engine.tune_serving_shapes(
                cfg, signed(get_precision(cfg.precision)),
                n_slots=self.n_slots, chunk_size=self.chunk_size,
                extra_m=self._occupancy_buckets(), mesh=mesh)

        kv_bits = self.kv_bits
        fused = self._fused

        def _decode_fn(p, t, pool, pt, pos_vec, slot_map):
            # ragged live-slot dispatch: gather the live rows up front so
            # EVERY per-layer matmul (qkv, ffn, lm head) runs at the
            # occupancy-bucket batch, not the padded n_slots — and the
            # fused kernel's grid walks exactly those rows.  Bucket padding
            # repeats a live slot: its duplicate row recomputes identical
            # values and rewrites its KV row with the identical bytes.
            logits, new_pool = model.decode_step_paged(
                p, t[slot_map], pool, pt[slot_map], pos_vec[slot_map],
                kv_bits, fused=fused)
            return logits, jnp.argmax(logits[:, 0], axis=-1), new_pool

        self._decode_fn = _decode_fn
        # on one device every program states each argument's device: the
        # pool programs pin the pool's layout, which commits what they
        # return, and a jit that is left to infer shardings keys each
        # argument on whether it is committed, so a token vector made on
        # the device and one staged from the host would compile twice
        self._one = None if mesh is not None else \
            jax.sharding.SingleDeviceSharding(jax.devices()[0])
        self._select_paged = jax.jit(_select_paged, in_shardings=self._one)
        self._pt_dirty = True              # host page table changed
        self._pt_dev = None                # device-resident page table
        chunk_fn = lambda p, t, pool, pt, pos: \
            model.prefill_chunk_paged(p, t, pool, pt, pos, kv_bits)
        # every program that takes the pool declares the layout make_pool
        # placed it in, for its argument and its (donated) result, so no
        # program relays the pool in or out
        self._pool_fmt = tfm.pool_formats(self.pool, cfg, mesh)
        if mesh is None:
            self._decode = self._pool_jit(_decode_fn, 6, 3)
            self._prefill_chunk = self._pool_jit(chunk_fn, 5, 2)
        else:
            # Sharded paged serving.  Pure-DP models dispatch shard_map-FIRST
            # with fully replicated specs: the pool cannot DP-shard (decode
            # appends write every slot's block — per-device partial writes on
            # a replicated pool would diverge; per-shard pools + sharded page
            # tables are the multi-host open item), so each device computes
            # the full step locally — same data layout as the replicated pjit
            # it replaces, but qmatmul now traces INSIDE shard_map, so the
            # tuned Pallas tiles fire for quantized-act configs instead of
            # XLA partitioning the reference ops.  Non-pure-DP (TP) models
            # keep pjit: the pool shards KV heads over 'model' (pool_specs —
            # block/position dims stay shard-local per the append rule) and
            # the step internals need the partitioner's collectives.
            from jax.sharding import NamedSharding, PartitionSpec as P
            shd = self._shd
            rep = NamedSharding(mesh, P())
            pool_specs = shd.pool_specs(self.pool, cfg, mesh)
            pool_sh = self._pool_fmt
            vspec = tuple(shd.logits_spec(cfg, mesh, 1))[-1]
            logits_sh = NamedSharding(mesh, P(None, None, vspec))
            decode_fn, jit_chunk_fn = _decode_fn, chunk_fn
            if shd.pure_dp(cfg, mesh):
                from jax import shard_map
                rep_params = jax.tree_util.tree_map(
                    lambda l: P(*(None,) * len(l.shape)), self.params)
                decode_fn = shard_map(
                    _decode_fn, mesh=mesh,
                    in_specs=(rep_params, P(None, None), pool_specs,
                              P(None, None), P(None), P(None)),
                    out_specs=(P(None, None, None), P(None), pool_specs),
                    check_vma=False)
                jit_chunk_fn = shard_map(
                    chunk_fn, mesh=mesh,
                    in_specs=(rep_params, P(None, None), pool_specs,
                              P(None, None), P()),
                    out_specs=(P(None, None, None), pool_specs),
                    check_vma=False)
            self._decode = jax.jit(
                decode_fn, donate_argnums=(2,),
                in_shardings=(self._psh, rep, pool_sh, rep, rep, rep),
                out_shardings=(logits_sh, rep, pool_sh))
            self._prefill_chunk = jax.jit(
                jit_chunk_fn, donate_argnums=(2,),
                in_shardings=(self._psh, rep, pool_sh, rep, rep),
                out_shardings=(logits_sh, pool_sh))

        if self.spec:
            self._build_speculative(model, cfg, kv_bits)

    def _build_speculative(self, model, cfg, kv_bits):
        """Draft-variant wiring: pack the fp weights down to the draft
        precision, register both variants with the kernel engine (so tuning
        and introspection see every precision the server can dispatch), and
        jit the draft decode + windowed fp verify step."""
        from repro.core.precision import get_precision, signed
        from repro.kernels import engine
        from repro.models import build_model, to_serving
        draft_cfg = dataclasses.replace(cfg, precision=self.draft_precision)
        self._draft_model = build_model(draft_cfg)
        self._draft_params = to_serving(self.params, draft_cfg)
        engine.register_variant(cfg.name, "primary",
                                signed(get_precision(cfg.precision)),
                                self.params)
        engine.register_variant(cfg.name, self.draft_precision,
                                signed(get_precision(self.draft_precision)),
                                self._draft_params)
        if self.config.autotune:
            # the verify window flattens (n_slots, k+1) rows into the matmul
            # M axis — pre-tune that bucket plus the draft variant's grid so
            # the speculative loop never sweeps mid-request
            extra = (self.n_slots * (self.spec_k + 1),)
            engine.tune_serving_shapes(
                cfg, signed(get_precision(cfg.precision)),
                n_slots=self.n_slots, chunk_size=self.chunk_size,
                extra_m=extra)
            engine.tune_serving_shapes(
                draft_cfg, signed(get_precision(self.draft_precision)),
                n_slots=self.n_slots, chunk_size=self.chunk_size)
        draft_model = self._draft_model

        def _draft_fn(p, t, pool, pt, pos_vec):
            logits, new_pool = draft_model.decode_step_paged(
                p, t, pool, pt, pos_vec, kv_bits)
            return jnp.argmax(logits[:, 0], axis=-1), new_pool

        def _verify_fn(p, t, pool, pt, pos_vec):
            logits, new_pool = model.decode_window_paged(
                p, t, pool, pt, pos_vec, kv_bits)
            return logits, jnp.argmax(logits, axis=-1), new_pool

        self._draft_decode = self._pool_jit(_draft_fn, 5, 2)
        self._verify = self._pool_jit(_verify_fn, 5, 3)

    def _pool_jit(self, fn, n_args: int, n_out: int):
        """``fn(params, tokens, pool, ...)`` jitted on one device, the pool
        donated and pinned to its layout as argument and as the last of
        ``n_out`` results, every other argument and result on the device."""
        one = self._one
        ins = [one] * n_args
        ins[2] = self._pool_fmt
        return jax.jit(fn, donate_argnums=(2,), in_shardings=tuple(ins),
                       out_shardings=(one,) * (n_out - 1) + (self._pool_fmt,))

    # ---------------------------------------------------------------- audit
    def audit_steps(self) -> list:
        """Paged step functions for the compile-time contract checker:
        batched decode + chunk append over the block pool, plus the
        speculative draft/verify pair when wired.  Step names carry a
        ``paged:`` prefix so audit reports distinguish them from the dense
        batcher's steps."""
        from repro.analysis.report import StepSpec
        from repro.core.precision import W_FLOAT, get_precision, signed
        flags = self._audit_flags()
        pt = jnp.asarray(self._pt)
        pos = jnp.asarray(self.pos)
        toks = jnp.asarray(self.tokens)
        slot_map = jnp.arange(self.n_slots, dtype=jnp.int32)
        # the fused single-dispatch contract binds only where the REAL fused
        # kernel fires: fused wiring on, pallas backend, float wo (the
        # quantized-wo epilogue stays in the engine's two-dispatch
        # composition fallback so its numerics never fork from qmatmul)
        pcfg = signed(get_precision(self.model.cfg.precision))
        fused_layers = self.model.cfg.n_layers \
            if (self._fused and flags["backend"] == "pallas"
                and pcfg.w_mode == W_FLOAT) else None
        steps = [
            StepSpec(name="paged:decode", fn=self._decode,
                     args=(self.params, toks, self.pool, pt, pos, slot_map),
                     donate_argnums=(2,), fused_layers=fused_layers,
                     **flags),
            StepSpec(name="paged:chunk", fn=self._prefill_chunk,
                     args=(self.params,
                           jnp.zeros((1, self.chunk_size), jnp.int32),
                           self.pool,
                           # admission page-table row shape (writes deflect
                           # to the null block under an all-zeros row)
                           jnp.zeros((1, self.blocks_per_seq), jnp.int32),
                           jnp.int32(0)),
                     donate_argnums=(2,), **flags),
        ]
        if self.spec:
            from repro.core.precision import A_FLOAT, W_FLOAT, \
                get_precision, signed
            draft_pcfg = signed(get_precision(self.draft_precision))
            draft_flags = dict(
                flags, quantized_weights=draft_pcfg.w_mode != W_FLOAT,
                quantized_acts=draft_pcfg.w_mode != W_FLOAT
                and draft_pcfg.a_mode != A_FLOAT and draft_pcfg.a_bits <= 8)
            steps.append(StepSpec(
                name="paged:draft_decode", fn=self._draft_decode,
                args=(self._draft_params, toks, self.pool, pt, pos),
                donate_argnums=(2,), **draft_flags))
            steps.append(StepSpec(
                name="paged:verify", fn=self._verify,
                args=(self.params,
                      jnp.zeros((self.n_slots, self.spec_k + 1), jnp.int32),
                      self.pool, pt, pos),
                donate_argnums=(2,), **flags))
        steps.append(self._select_audit_step(
            "paged:select", flags, self._select_paged, slot_map))
        return steps

    # -------------------------------------------------------------- submit
    def _blocks_needed(self, length: int, max_new: int) -> int:
        """Blocks covering every position the request can ever write.

        The decode chain retires a slot once its position counter reaches
        s_max-1, so decode writes stop at position s_max-2 — EXCEPT the
        first decode write at position L itself, which activation never
        caps: a fresh prompt of exactly s_max-1 tokens still writes
        position s_max-1.  Hence the cap is max(L+1, s_max-1) positions,
        not the old flat s_max (which reserved a phantom block whenever
        s_max ≡ 1 mod block_size and made ``submit`` reject budget-heavy
        requests the pool could in fact serve) and not a flat s_max-1
        (which would strand that first decode write)."""
        n_pos = min(length + max_new - 1, max(length + 1, self.s_max - 1))
        return -(-n_pos // self.block_size)

    def _validate(self, req: Request):
        super()._validate(req)
        # lifetime capacity check — it applies under BOTH reserve
        # policies: even with dynamic allocation + preemption, a sole
        # resident request must eventually hold its whole footprint at
        # once (recompute re-admission prefills prompt + generated), so
        # a request needing more blocks than the pool holds could never
        # finish and would livelock the scheduler
        length = req.tokens.shape[-1]
        need = self._blocks_needed(length, req.max_new)
        if need > self.num_blocks - 1:
            raise PoolFootprintError(
                f"request {req.rid}: needs {need} KV blocks "
                f"(prompt {length} + max_new {req.max_new} at "
                f"block_size {self.block_size}) but the pool holds only "
                f"{self.num_blocks - 1} allocatable blocks",
                rid=req.rid, required_blocks=need,
                available_blocks=self.num_blocks - 1)

    # ----------------------------------------------------------- admission
    def _resume_prompt(self, req: Request) -> np.ndarray:
        """Admission token view: the original prompt — plus, for a request
        re-queued by preemption, every token it already generated, so the
        recompute prefill rebuilds the KV its released blocks held (and
        writes the KV of the last generated token, which decode had not
        gotten to yet)."""
        if not req.output:
            return req.tokens
        gen = np.asarray(req.output, np.int32)[None]
        return np.concatenate([req.tokens, gen], axis=1)

    def _match_prefix(self, tokens: np.ndarray) -> list[tuple[int, bool]]:
        """Radix lookup of (block, is_suffix) pairs, capped so (a) at least
        the last token is still prefilled (its logits seed generation) and
        (b) the match ends on a chunk boundary as well as a block boundary
        (per-chunk dynamic activation quantization must see the same chunk
        contents a fresh prefill would).  Metrics are recorded by the caller
        on a SUCCESSFUL admission only — a pool-exhausted request is
        re-matched every scheduler step while it waits, and those retries
        must not inflate the lookup/hit counters."""
        if self.radix is None:
            return []
        length = tokens.shape[-1]
        matched = self.radix.match_with_kinds(tokens.reshape(-1))
        align = math.lcm(self.block_size, self.chunk_size)
        max_match = (length - 1) // align * align
        return matched[:max_match // self.block_size]

    def _advance_admission(self):
        if self._adm is None:
            slot = self._free_slot()
            if not self.queue or slot is None:
                return
            req = self.queue[0]
            toks = self._resume_prompt(req)
            length = toks.shape[1]
            matched = self._match_prefix(toks)
            shared = [bid for bid, _ in matched]
            for bid in shared:                   # hold before any eviction
                self.pool_meta.acquire(bid)
            if self.reserve == "prompt":
                need_total = -(-length // self.block_size)
            else:
                need_total = self._blocks_needed(
                    length, req.max_new - len(req.output))
            need = need_total - len(shared)
            blocks = self._alloc(need)
            if blocks is None:
                # pool exhausted by resident requests: stay queued (running
                # requests finish — or get preempted — and release)
                for bid in shared:
                    self.pool_meta.release(bid)
                return
            self.queue.popleft()
            readmission = req.started_at != 0.0   # preempted earlier
            req.started_at = time.perf_counter()
            self.metrics.on_admit(req, n_prompt_tokens=length,
                                  resumed=readmission)
            start = len(shared) * self.block_size
            if self.tracer.enabled:
                self.tracer.instant(
                    "admit", "scheduler", track=self.trace_track,
                    rid=req.rid, slot=slot, prompt_tokens=length,
                    resumed=readmission, prefix_hit_tokens=start)
                # a re-admission continues the request's existing flow
                self.tracer.flow("t" if readmission else "s", req.rid,
                                 track=self.trace_track)
            if self.radix is not None:
                n_sfx = sum(1 for _, sfx in matched if sfx)
                self.metrics.on_prefix_lookup(
                    (len(shared) - n_sfx) * self.block_size, length,
                    suffix_tokens=n_sfx * self.block_size)
            debt = self._recompute_debt.pop(req.rid, 0)
            if debt:
                # positions re-prefilled that were computed before the
                # preemption (decode-written for a mid-stream victim,
                # chunk-prefilled for a mid-admission one) — radix hits
                # shrink this, often to zero
                self.metrics.on_recompute(max(0, debt - start))
            owned = shared + blocks
            self._slot_blocks[slot] = owned
            self._slot_seq[slot] = self._seq_counter
            self._seq_counter += 1
            # the slot's live page-table row (self._pt) stays ZEROED until
            # activation: the interleaved batched decode writes a dead KV
            # row for every not-yet-active slot, and those writes must
            # deflect to the null block instead of corrupting the freshly
            # allocated (or shared!) blocks mid-prefill.  Chunks use the
            # admission's private row.
            row = np.zeros((1, self.blocks_per_seq), np.int32)
            row[0, :len(owned)] = owned
            self._adm_row = row
            self._gauge()
            l_pad = bucket_length(length - start, self.chunk_size)
            padded = np.zeros((1, l_pad), np.int32)
            padded[:, :length - start] = toks[:, start:]
            self._adm = _Admission(req, slot, padded, length, start=start)
            self.slots[slot] = req               # reserve (done stays True)

        adm = self._adm
        c = self.chunk_size
        chunk = jnp.asarray(adm.tokens[:, adm.next_pos:adm.next_pos + c])
        self.metrics.prefill_chunks += 1
        tr = self.tracer
        if tr.enabled:
            tr.flow("t", adm.req.rid, track=self.trace_track)
        with tr.span("prefill_chunk", "scheduler", track=self.trace_track,
                     rid=adm.req.rid, pos=adm.start + adm.next_pos):
            if self.profiler is None:
                logits, self.pool = self._prefill_chunk(
                    self.params, chunk, self.pool,
                    jnp.asarray(self._adm_row),
                    jnp.int32(adm.start + adm.next_pos))
            else:
                with self.profiler.step("prefill_chunk"):
                    logits, self.pool = self._prefill_chunk(
                        self.params, chunk, self.pool,
                        jnp.asarray(self._adm_row),
                        jnp.int32(adm.start + adm.next_pos))
                    jax.block_until_ready(logits)
        adm.next_pos += c
        if adm.next_pos >= adm.tokens.shape[1]:
            row = logits[0, (adm.length - 1 - adm.start) % c]
            self._adm = None
            self._register_written(adm.req, adm.slot, adm.length)
            self._pt[adm.slot, :] = self._adm_row[0]
            self._pt_dirty = True
            self._activate(adm.req, adm.slot, None, row)

    def _alloc(self, n: int) -> list[int] | None:
        """Pool alloc with LRU radix eviction as the fallback; ``None`` only
        when resident requests genuinely hold the pool.  Eviction targets
        FREEABLE leaves only (radix-only references): dropping a reference
        on a block an active request still holds frees nothing and would
        just strip-mine the cache on an allocation that cannot succeed."""
        if n <= 0:
            return []
        if self._ledger is not None and not self._ledger.affords(self, n):
            # the cross-lane byte budget is exhausted even though this
            # lane's own pool has room: reclaim freeable radix blocks from
            # EVERY lane (cheapest bytes first), then re-check.  A refusal
            # here behaves exactly like pool exhaustion — admission stays
            # queued, decode falls back to preemption within this lane.
            self._ledger.reclaim(self, n)
            if not self._ledger.affords(self, n):
                return None
        blocks = self.pool_meta.alloc(n)
        if blocks is None and self.radix is not None and len(self.radix):
            # feasibility first: an infeasible allocation (queue head
            # retrying every scheduler step) must not strip the warm cache.
            # A radix block at refcount 1 has no slot-held descendant (a
            # held child implies a held parent), so every such block is
            # eventually freeable — their count bounds what eviction buys.
            freeable = sum(1 for b in self.radix.blocks()
                           if self.pool_meta.refcount(b) == 1)
            if self.pool_meta.free_blocks + freeable < n:
                return None
            while blocks is None:
                dropped = self.radix.evict(
                    max(n - self.pool_meta.free_blocks, 1),
                    freeable_only=True)
                self.metrics.on_evictions(dropped)
                if dropped and self.tracer.enabled:
                    self.tracer.instant("evict", "kvcache",
                                        track=self.trace_track,
                                        blocks=dropped)
                if dropped == 0:
                    break
                blocks = self.pool_meta.alloc(n)
        return blocks

    def _gauge(self):
        """Refresh the pool-occupancy metrics; the pool's own ``peak_used``
        watermark is folded in because it also sees the transient highs
        inside an allocate-then-preempt wave that a post-wave gauge read
        would miss."""
        self.metrics.on_kv_blocks(self.pool_meta.used_blocks,
                                  self.num_blocks - 1)
        self.metrics.kv_blocks_peak = max(self.metrics.kv_blocks_peak,
                                          self.pool_meta.peak_used)
        if self.tracer.enabled:
            self.tracer.counter("kv_blocks", "kvcache",
                                track=self.trace_track,
                                in_use=self.pool_meta.used_blocks,
                                total=self.num_blocks - 1)

    def _register_written(self, req: Request, slot: int, n_written: int):
        """Publish the slot's computed KV — the full blocks of the first
        ``n_written`` positions of (prompt + generated) — to the radix tree.
        Called at activation (prompt' complete and immutable), at preemption
        (so the recompute prefill radix-hits what the victim already
        computed), and at release (so agent-style follow-up prompts reuse
        generated suffixes).  Blocks past the original prompt register as
        kind ``suffix``."""
        if self.radix is None:
            return
        toks = self._resume_prompt(req).reshape(-1)[:n_written]
        n_prompt = req.tokens.shape[1] // self.block_size
        full = n_written // self.block_size
        if full:
            self.radix.insert(toks, self._slot_blocks[slot][:full],
                              suffix_from=n_prompt)

    def _join_slot(self, slot: int, one_cache):
        pass                  # prefill chunks already wrote the slot's blocks

    def _admit_full(self):
        raise NotImplementedError(
            "paged serving always admits through chunked prefill")

    # ------------------------------------------------------------- decode
    def _pre_decode(self):
        """Dynamic allocation: hand every active slot crossing a block
        boundary one fresh block before the batched step.  On exhaustion,
        preempt latest-admitted-first (the mid-flight admission, then active
        slots) — but never a request admitted before the one asking, so the
        earliest-admitted request always advances and the system always
        drains."""
        if self.reserve != "prompt":
            return
        order = sorted((i for i in range(self.n_slots)
                        if not self.done[i] and self.slots[i] is not None),
                       key=lambda i: self._slot_seq[i])
        moved = False
        for i in order:
            if self.done[i]:                # preempted by an earlier slot
                continue
            self.stalled[i] = False
            b_idx = int(self.pos[i]) // self.block_size
            if self._pt[i, b_idx] != 0:
                continue
            blk = self._alloc(1)
            while blk is None:
                victim = self._lowest_priority_after(int(self._slot_seq[i]))
                if victim is None or self.preemption != "recompute":
                    break
                self._preempt(victim)
                moved = True
                blk = self._alloc(1)
            if blk is None:
                if self.preemption == "recompute":
                    # the asking slot is itself the lowest priority left
                    self._preempt(("slot", i))
                    moved = True
                else:
                    self.stalled[i] = True
                    if self.tracer.enabled:
                        self.tracer.instant(
                            "stall", "scheduler", track=self.trace_track,
                            rid=self.slots[i].rid, slot=i)
                continue
            self._slot_blocks[i].append(blk[0])
            self._pt[i, b_idx] = blk[0]
            self._pt_dirty = True
            moved = True
        if moved:
            self._gauge()
        if self.preemption != "recompute":
            active = [i for i in range(self.n_slots)
                      if not self.done[i] and self.slots[i] is not None]
            if active and all(self.stalled[i] for i in active) \
                    and self._adm is None:
                raise RuntimeError(
                    f"pool deadlock: all {len(active)} active slots are "
                    "stalled on block allocation and nothing can release "
                    "(preemption='off'); use preemption='recompute' or a "
                    "larger pool")

    def _lowest_priority_after(self, seq: int):
        """The preemption victim for a request admitted at ``seq``: the
        mid-flight admission if any (admission is serialized, so it is
        always the most recent), else the latest-admitted active slot —
        and only ever one admitted strictly AFTER ``seq``."""
        if self._adm is not None:
            return ("adm", self._adm)
        best = None
        for j in range(self.n_slots):
            if self.done[j] or self.slots[j] is None:
                continue
            if self._slot_seq[j] > seq and (
                    best is None or self._slot_seq[j] > self._slot_seq[best]):
                best = j
        return None if best is None else ("slot", best)

    def _preempt(self, victim):
        """Release a victim back to the queue head: register its computed
        full blocks (cheap recompute), drop its references, zero its live
        page-table row, and re-queue it with stream state intact."""
        kind, v = victim
        if kind == "adm":
            adm = v
            req, slot = adm.req, adm.slot
            # chunks already prefilled → full blocks are registrable
            n_written = min(adm.start + adm.next_pos, adm.length)
            self._adm = None
        else:
            slot = v
            req = self.slots[slot]
            n_written = int(self.pos[slot])   # decode wrote [0, pos)
        self._register_written(req, slot, n_written)
        self._recompute_debt[req.rid] = n_written
        for bid in self._slot_blocks[slot] or ():
            self.pool_meta.release(bid)
        self._slot_blocks[slot] = None
        self._pt[slot, :] = 0               # dead decode writes -> null block
        self._pt_dirty = True
        self._requeue(req, slot)
        self.metrics.on_preempt(req)
        if self.tracer.enabled:
            self.tracer.instant("preempt", "scheduler",
                                track=self.trace_track, rid=req.rid,
                                slot=slot, n_written=n_written)
            self.tracer.flow("t", req.rid, track=self.trace_track)
        self._gauge()

    def _occupancy_bucket(self, n_live: int) -> int:
        """Compiled batch shape for ``n_live`` live slots: the smallest
        power of two >= n_live, capped at n_slots — so occupancy churn
        cycles through O(log n_slots) compiled decode programs instead of
        one per occupancy (or one padded shape computing dead rows)."""
        b = 1
        while b < n_live:
            b *= 2
        return min(b, self.n_slots)

    def _occupancy_buckets(self) -> tuple[int, ...]:
        """Every batch shape the ragged dispatch can compile."""
        return tuple(sorted({self._occupancy_bucket(n)
                             for n in range(1, self.n_slots + 1)}))

    def _stage_loop_state(self, live: list[int]):
        """Paged staging: the dense buffers plus the live-slot index map,
        padded up to its occupancy bucket by REPEATING the last live slot
        (duplicate rows recompute identical values; their KV/pt writes are
        idempotent)."""
        super()._stage_loop_state(live)
        if self._ragged:
            sm = list(live)
            sm += [sm[-1]] * (self._occupancy_bucket(len(sm)) - len(sm))
        else:
            sm = list(range(self.n_slots))
        self._dev["slot_map"] = jnp.asarray(np.asarray(sm, np.int32))

    def _dispatch_decode(self):
        if self._pt_dirty:
            self._pt_dev = jnp.asarray(self._pt)
            self._pt_dirty = False
        d = self._dev
        logits, greedy, self.pool = self._decode(
            self.params, d["tok"], self.pool, self._pt_dev, d["pos"],
            d["slot_map"])
        nxt, d["tok"], d["pos"], d["nout"] = self._select_paged(
            logits, greedy, d["slot_map"], d["tok"], d["pos"], d["nout"],
            d["temps"], d["topks"], d["seeds"], d["rids"])
        return nxt

    def _tick(self):
        if not self.tick:
            return
        active = sum(1 for i in range(self.n_slots)
                     if self.slots[i] is not None and not self.done[i])
        self.metrics.on_step(
            len(self.queue) + (1 if self._adm is not None else 0),
            pool_in_use=self.pool_meta.used_blocks,
            pool_total=self.num_blocks - 1, active=active)

    # -------------------------------------------- self-speculative decode
    def _extend_windows(self) -> np.ndarray:
        """Opportunistically back each active slot's draft window: positions
        ``pos .. pos + draft_k`` need their blocks resident for the window's
        KV writes to land (an unbacked position's write deflects to the null
        block and its verify row is garbage).  Allocation here NEVER
        preempts — a short window this round just means fewer drafts, not a
        lost slot.  Returns the per-slot usable draft count (0 = plain
        decode for that slot: row 0 of the verify window is exactly the
        sequential decode step)."""
        limits = np.zeros(self.n_slots, np.int32)
        for i in range(self.n_slots):
            req = self.slots[i]
            if req is None or self.done[i] or self.stalled[i]:
                continue
            p = int(self.pos[i])
            # cap by the sequence budget (decode retires at s_max-1) and by
            # the request's remaining token budget (drafting past the last
            # token it can emit is pure waste)
            lim = min(self.spec_k, self.s_max - 1 - p,
                      req.max_new - len(req.output) - 1)
            if lim <= 0:
                continue
            b0, b_last = p // self.block_size, (p + lim) // self.block_size
            for b in range(b0 + 1, min(b_last, self.blocks_per_seq - 1) + 1):
                if self._pt[i, b] != 0:
                    continue
                blk = self._alloc(1)
                if blk is None:
                    break
                self._slot_blocks[i].append(blk[0])
                self._pt[i, b] = blk[0]
                self._pt_dirty = True
            bb = b0
            while bb < b_last and bb + 1 < self.blocks_per_seq \
                    and self._pt[i, bb + 1] != 0:
                bb += 1
            backed_end = (bb + 1) * self.block_size - 1
            limits[i] = min(lim, backed_end - p)
        if limits.any():
            self._gauge()
        return limits

    def _spec_round(self, limits: np.ndarray):
        """One draft/verify round replacing the plain batched decode step.

        The draft variant decodes ``k`` tokens per slot sequentially (its
        approximate KV lands in the SAME pool the fp path uses), then ONE
        windowed fp decode over (last_token, d_1..d_k) recomputes exact KV
        at every window position — overwriting the draft's — and yields the
        exact greedy token after each prefix.  Emission accepts the longest
        draft prefix the fp greedies confirm, so every emitted token is the
        token the sequential fp stream would have produced (losslessness);
        stale KV past the acceptance point is either overwritten before
        anything attends it (next round's window) or causally masked."""
        w = self.spec_k + 1
        base_pos = self.pos.copy()
        window = np.zeros((self.n_slots, w), np.int32)
        window[:, 0] = self.tokens[:, 0]
        toks = self.tokens
        tr = self.tracer
        n_draft = int(limits.max(initial=0))
        with tr.span("draft", "scheduler", track=self.trace_track,
                     rounds=n_draft):
            for j in range(n_draft):
                nxt, self.pool = self._draft_decode(
                    self._draft_params, jnp.asarray(toks), self.pool,
                    jnp.asarray(self._pt), jnp.asarray(base_pos + j))
                with tr.span("decode_fetch", "scheduler",
                             track=self.trace_track):
                    toks = np.asarray(nxt, np.int32).reshape(self.n_slots, 1)
                window[:, j + 1] = toks[:, 0]
        with tr.span("verify", "scheduler", track=self.trace_track):
            if self.profiler is None:
                logits, greedy, self.pool = self._verify(
                    self.params, jnp.asarray(window), self.pool,
                    jnp.asarray(self._pt), jnp.asarray(base_pos))
            else:
                with self.profiler.step("verify"):
                    logits, greedy, self.pool = self._verify(
                        self.params, jnp.asarray(window), self.pool,
                        jnp.asarray(self._pt), jnp.asarray(base_pos))
                    jax.block_until_ready((logits, greedy))
        with tr.span("decode_fetch", "scheduler", track=self.trace_track):
            greedy = np.asarray(greedy, np.int32)
        self.metrics.decode_steps += 1
        drafted = accepted = 0
        with tr.span("emit", "scheduler", track=self.trace_track):
            for i, req in enumerate(self.slots):
                if req is None or self.done[i] or self.stalled[i]:
                    continue
                lim = int(limits[i])
                drafted += lim
                j = 0
                while True:
                    tok = int(greedy[i, j]) if req.temperature <= 0.0 \
                        else self._sample(req, logits[i, j])
                    self.metrics.decode_slot_tokens += 1
                    self.pos[i] += 1
                    hit_eos = req.eos_id is not None and tok == req.eos_id
                    full = (len(req.output) + 1 >= req.max_new or hit_eos
                            or self.pos[i] >= self.s_max - 1)
                    self._emit(req, tok, full)
                    if full:
                        self._finish(req, i)
                        accepted += j
                        break
                    if j < lim and int(window[i, j + 1]) == tok:
                        # the draft predicted this very token: its
                        # successor row in the window already holds the
                        # exact fp continuation
                        j += 1
                        continue
                    self.tokens[i, 0] = tok
                    accepted += j
                    break
        self.metrics.on_spec_round(drafted, accepted)
        # the round mutated tokens/pos on the host: any later non-spec
        # decode dispatch must re-stage the device loop buffers
        self._loop_dirty = True
        if self.tracer.enabled:
            self.tracer.instant("spec_round", "scheduler",
                                track=self.trace_track,
                                drafted=drafted, accepted=accepted)

    def _step_impl(self):
        if not self.spec:
            return super()._step_impl()
        self._tick()
        self._admit()
        self._allocate()
        if not all(self.done):
            self._spec_round(self._extend_windows())
        finished, self._just_finished = self._just_finished, []
        return finished

    # -------------------------------------------------------------- finish
    def _release_slot(self, req: Request, slot: int):
        # decode wrote [0, L + g - 1): the final emitted token's KV was
        # never written (the loop ends before feeding it)
        self._register_written(
            req, slot, req.tokens.shape[1] + len(req.output) - 1)
        for bid in self._slot_blocks[slot] or ():
            self.pool_meta.release(bid)
        self._slot_blocks[slot] = None
        self._pt[slot, :] = 0               # dead decode writes -> null block
        self._pt_dirty = True
        self._gauge()

    # ---------------------------------------------------------- invariants
    def check_pool(self):
        """Cross-check the pool against every live holder (active slots' and
        the mid-flight admission's block lists, plus the radix tree) — the
        chaos harness calls this after every scheduler step."""
        self.pool_meta.check(
            (blocks for blocks in self._slot_blocks if blocks),
            self.radix.blocks() if self.radix is not None else ())
