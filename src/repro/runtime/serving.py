"""Continuous-batching serving scheduler (v2: chunked prefill).

Production serving loop around the model's prefill/decode step functions:
  * a bounded request queue; admission at prefill-*chunk* granularity — long
    prompts are split into fixed-size chunks interleaved with decode steps,
    so already-running requests keep producing tokens while a new prompt is
    being admitted (bounded ITL impact, no full-prefill stall);
  * bucketed shapes: prompts pad up to a multiple of the chunk size, so the
    compiled shape set is {one chunk, one decode step} and the Pallas tuning
    cache (pre-populated via ``autotune=True``) is always hit;
  * fixed-capacity decode slots (the compiled decode step has a static batch
    shape — slots are recycled, finished slots admit new requests);
  * per-slot sampling: greedy by default, temperature/top-k with a per-slot
    PRNG key (deterministic per (seed, rid, token index));
  * per-token streaming callbacks and EOS/budget handling;
  * latency accounting per request (queue / TTFT / inter-token) aggregated
    by :class:`repro.runtime.metrics.Metrics`.

The scheduler is host-side and model-agnostic: it owns a padded
(slots, s_max) cache built once and re-used; joins happen by writing a newly
prefilled request's KV into its slot (jax dynamic_update_slice on the batch
axis).  With ``mesh`` the same loop runs SPMD (DESIGN.md §5): params are
sharded with ``param_specs``, the slot cache with ``cache_specs`` (batch
over the data axes, KV heads over 'model' when they divide), logits with
``logits_spec``, and the three step functions are jit-compiled with explicit
``in_shardings``/``out_shardings`` so the cache never leaves the device mesh
between steps.  The admission (batch=1) cache replicates — chunk appends are
dynamic_update_slice over the sequence dim and must stay shard-local —
while the slot join is a per-slot compiled write (static slot index, so the
partitioner lowers it without gathering the sharded batch dim).

Exactness contract: with greedy sampling, generations are bit-identical to
isolated sequential runs for attention-only stacks (the property suite in
tests/test_serving.py enforces this).  SSM/hybrid stacks fall back to
whole-prompt admission (padding tokens would pollute the recurrent state).
Dynamic activation quantization is PER-ROW (engine._prep_activations), so
quantized-act configs share the full contract: each token's codes depend
only on its own row, making streams identical across batch sizes, shape
buckets, and shard-local (shard_map) vs global dispatch.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from collections import deque
from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .errors import (EmptyPromptError, InvalidBudgetError,
                     PromptTooLongError)
from .metrics import Metrics


# ---------------------------------------------------------------------------
# serving front door: typed configs (the API redesign)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class RequestOptions:
    """Per-request options.  Everything that used to be a loose ``Request``
    kwarg lives here; the scheduler-filled timing fields stay on the request
    itself.  ``slo`` names the service tier the adaptive server routes by
    (ignored by the plain batchers)."""
    max_new: int = 16
    eos_id: int | None = None
    # sampling: temperature <= 0 -> greedy; top_k 0 -> full distribution
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0
    # service tier for SLO-routed adaptive serving (runtime.adaptive)
    slo: str = "standard"
    # per-token streaming: called as on_token(req, token, finished)
    on_token: Callable[["Request", int, bool], None] | None = None


@dataclasses.dataclass
class ServingConfig:
    """Typed batcher configuration — one front door for the dense batcher,
    the paged batcher, and the adaptive server, replacing the old sprawl of
    constructor kwargs.  ``launch/serve.py`` maps its CLI flags 1:1 onto
    these fields.

    Paged-only fields (``kv_bits`` .. ``preemption``) are ignored by
    :class:`ContinuousBatcher`; adaptive-only fields (``slo_classes`` ..
    ``draft_k``) are read by :class:`repro.runtime.adaptive.AdaptiveServer`
    and by :class:`repro.runtime.kvcache.PagedBatcher` (speculative
    decoding)."""
    # ---- scheduler shape ------------------------------------------------
    n_slots: int = 8
    s_max: int = 128
    prompt_len: int | None = None
    chunk_size: int | None = None
    autotune: bool = False
    mesh: Any = None
    # ---- paged KV cache (PagedBatcher) ----------------------------------
    kv_bits: int = 16
    block_size: int = 16
    num_blocks: int | None = None
    pool_bytes: int | None = None
    prefix_cache: bool = True
    reserve: str = "prompt"
    preemption: str = "recompute"
    # fused ragged decode (PagedBatcher): run each decode layer's paged
    # attention + wo projection as ONE engine dispatch (fused_decode=False
    # keeps the legacy two-dispatch layer), and dispatch the decode step
    # over live slots only, bucketed to power-of-two occupancy shapes
    # (ragged_decode=False always pads to the full (n_slots, 1) batch)
    fused_decode: bool = True
    ragged_decode: bool = True
    # ---- adaptive precision serving (AdaptiveServer / speculative) ------
    slo_classes: dict[str, Any] | None = None   # name -> policy.SLOClass
    brownout: bool = False
    brownout_policy: Any = None                    # policy.BrownoutPolicy
    speculative: bool = False
    draft_precision: str | None = "2xT"         # PAPER_CONFIGS key
    draft_k: int = 3
    # ---- observability (runtime.tracing flight recorder) ----------------
    # a tracing.TraceConfig (or None): structured event tracing, periodic
    # metrics snapshots, and per-step device/host profiling
    trace: Any = None


# legacy constructor kwargs the back-compat shim still accepts (everything
# the pre-redesign ContinuousBatcher/PagedBatcher signatures took)
_LEGACY_BATCHER_KWARGS = (
    "n_slots", "s_max", "prompt_len", "chunk_size", "autotune", "mesh",
    "kv_bits", "block_size", "num_blocks", "pool_bytes", "prefix_cache",
    "reserve", "preemption")
_LEGACY_REQUEST_KWARGS = (
    "max_new", "eos_id", "temperature", "top_k", "seed", "on_token")


def _coerce_config(config, legacy: dict, cls_name: str) -> ServingConfig:
    """Build the ServingConfig a batcher runs on: the passed config, with
    any legacy kwargs folded in under a DeprecationWarning (the back-compat
    shim — new call sites pass a ServingConfig and no kwargs)."""
    unknown = set(legacy) - set(_LEGACY_BATCHER_KWARGS)
    if unknown:
        raise TypeError(f"{cls_name}: unexpected keyword arguments "
                        f"{sorted(unknown)}")
    if config is not None and not isinstance(config, ServingConfig):
        raise TypeError(f"{cls_name}: config must be a ServingConfig, got "
                        f"{type(config).__name__}")
    if legacy:
        warnings.warn(
            f"{cls_name}(n_slots=..., s_max=..., ...) constructor kwargs are "
            "deprecated; pass a ServingConfig instead: "
            f"{cls_name}(model, params, ServingConfig(...))",
            DeprecationWarning, stacklevel=3)
        config = dataclasses.replace(config or ServingConfig(), **legacy)
    if config is None:
        raise TypeError(f"{cls_name}: pass a ServingConfig "
                        f"({cls_name}(model, params, ServingConfig(...)))")
    return config


class Request:
    """One generation request: prompt tokens + :class:`RequestOptions`.

    The pre-redesign loose kwargs (``max_new=...``, ``on_token=...``, ...)
    are still accepted through a deprecation shim and fold into ``options``;
    the option values are readable both ways (``req.max_new`` delegates to
    ``req.options.max_new``).  Scheduler-filled timing fields live directly
    on the request."""

    def __init__(self, rid: int, tokens: np.ndarray,
                 options: RequestOptions | None = None, **legacy):
        unknown = set(legacy) - set(_LEGACY_REQUEST_KWARGS)
        if unknown:
            raise TypeError(f"Request: unexpected keyword arguments "
                            f"{sorted(unknown)}")
        if legacy:
            warnings.warn(
                "Request(max_new=..., eos_id=..., ...) kwargs are "
                "deprecated; pass options=RequestOptions(...)",
                DeprecationWarning, stacklevel=2)
            options = dataclasses.replace(options or RequestOptions(),
                                          **legacy)
        self.rid = rid
        self.tokens = tokens               # prompt (1, S_prompt)
        self.options = options if options is not None else RequestOptions()
        # filled by the scheduler:
        self.submitted_at = 0.0
        self.started_at = 0.0
        self.first_token_at = 0.0
        # None until a token lands: Metrics.on_token guards on `is not None`
        # (a 0.0 sentinel under a monkeypatched clock reads as a real
        # timestamp and fabricates huge ITL samples)
        self.last_token_at: float | None = None
        self.finished_at = 0.0
        self.output: list[int] = []

    # option views (read-only: mutate req.options, not the request)
    @property
    def max_new(self) -> int:
        return self.options.max_new

    @property
    def eos_id(self) -> int | None:
        return self.options.eos_id

    @property
    def temperature(self) -> float:
        return self.options.temperature

    @property
    def top_k(self) -> int:
        return self.options.top_k

    @property
    def seed(self) -> int:
        return self.options.seed

    @property
    def slo(self) -> str:
        return self.options.slo

    @property
    def on_token(self):
        return self.options.on_token

    @property
    def queue_ms(self):
        return (self.started_at - self.submitted_at) * 1e3

    @property
    def ttft_ms(self):
        return (self.first_token_at - self.submitted_at) * 1e3

    @property
    def total_ms(self):
        return (self.finished_at - self.submitted_at) * 1e3

    def __repr__(self):
        return (f"Request(rid={self.rid}, "
                f"prompt={self.tokens.shape[-1] if self.tokens.size else 0}, "
                f"slo={self.options.slo!r}, out={len(self.output)})")


@dataclasses.dataclass
class _Admission:
    """One request mid-chunked-prefill (its cache is not yet slot-resident)."""
    req: Request
    slot: int
    tokens: np.ndarray                 # (1, L_pad) bucket-padded prompt tail
    length: int                        # true prompt length L
    next_pos: int = 0                  # next chunk start (relative to start)
    start: int = 0                     # first position to prefill (> 0 when a
                                       # radix prefix-cache hit covers [0, start))


def supports_chunked_prefill(cfg) -> bool:
    """Chunk admission preserves exactness only when no recurrent state
    crosses padded positions: attention-only layer stacks over token ids."""
    return (getattr(cfg, "kind", "") == "lm"
            and getattr(cfg, "frontend", "none") == "none"
            and all(m.startswith("attn") for m in cfg.layer_pattern))


def bucket_length(length: int, chunk: int) -> int:
    """Pad a prompt length up to the next chunk multiple (its shape bucket)."""
    return -(-length // chunk) * chunk


# ---------------------------------------------------------------------------
# batched next-token selection (the jitted form of per-slot _sample)
# ---------------------------------------------------------------------------
def _sample_rows(lg, greedy, temps, topks, seeds, rids, nouts):
    """Next token for every row of an (R, V) logits block at once —
    the batched, jit-friendly form of :meth:`ContinuousBatcher._sample`,
    bit-identical row by row.

    Greedy rows (temperature <= 0) pass the decode step's fused argmax
    through untouched.  Sampled rows reproduce the per-slot reference math
    exactly: f32 logits / T; the top-k cutoff via descending ``jnp.sort`` at
    index k-1, which is the same float value ``jax.lax.top_k(...)[0][-1]``
    returns; and a categorical draw under the identical
    ``fold_in(fold_in(PRNGKey(seed), rid), n_out)`` key — PRNG bits are a
    deterministic function of the key data, so vmapping the draw cannot
    change any stream (tests/test_serving_ragged.py locks this in)."""
    def one(row, g, t, k, sd, rd, n):
        safe_t = jnp.where(t <= 0.0, jnp.float32(1.0), t)
        z = row.astype(jnp.float32) / safe_t
        kth = jnp.sort(z)[::-1][jnp.clip(k, 1, z.shape[-1]) - 1]
        z = jnp.where((k > 0) & (z < kth), -jnp.inf, z)
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(sd), rd), n)
        samp = jax.random.categorical(key, z)
        return jnp.where(t <= 0.0, g, samp).astype(jnp.int32)
    return jax.vmap(one)(lg, greedy, temps, topks, seeds, rids, nouts)


def _select_dense(logits, greedy, live, tok, pos, nout,
                  temps, topks, seeds, rids):
    """One batched post-decode selection step over the full padded batch:
    sample/choose every row's next token on device, advance the
    device-resident token/pos/n_out buffers for LIVE rows only, and return
    the (B,) next-token vector — the single value the host loop syncs on.
    Dead/stalled rows keep their previous token and position (their sampled
    value is masked out), so the buffers never drift from the host mirrors.
    All ops are per-row (mask + elementwise update), keeping the pure-DP
    sharded step collective-free."""
    nxt = _sample_rows(logits[:, 0], greedy, temps, topks, seeds, rids, nout)
    nxt = jnp.where(live, nxt, tok[:, 0])
    adv = live.astype(pos.dtype)
    return nxt, nxt[:, None], pos + adv, nout + adv


class ContinuousBatcher:
    """Slot-based continuous batching: chunked (or whole-prompt) prefill
    interleaved with batched decode."""

    def __init__(self, model, params, config: ServingConfig | None = None,
                 *, metrics: Metrics | None = None, tracer=None, **legacy):
        config = _coerce_config(config, legacy, type(self).__name__)
        self.config = config
        self.model = model
        self.params = params
        self.n_slots = config.n_slots
        self.s_max = config.s_max
        self.prompt_len = config.prompt_len or config.s_max
        self.mesh = mesh = config.mesh
        n_slots, s_max = self.n_slots, self.s_max
        prompt_len, chunk_size = config.prompt_len, config.chunk_size
        autotune = config.autotune
        cfg = model.cfg
        if mesh is not None:
            from repro.parallel import sharding as shd
            self._shd = shd
            self._psh = shd.named_shardings(
                mesh, shd.param_specs(params, cfg, mesh))
            self.params = jax.device_put(params, self._psh)

        # ---- chunked-prefill configuration -------------------------------
        chunkable = (supports_chunked_prefill(cfg)
                     and model.prefill_chunk is not None)
        if chunk_size is None:
            chunk_size = min(32, s_max) if chunkable else 0
        if chunk_size and not chunkable:
            raise ValueError(
                f"{cfg.name}: chunked prefill needs an attention-only token "
                "LM (recurrent state cannot cross padded chunk positions); "
                "pass chunk_size=0 for whole-prompt admission")
        self.chunk_size = int(chunk_size)
        # admission cache is rounded up so every chunk call is full-size
        self.s_adm = (bucket_length(s_max, self.chunk_size)
                      if self.chunk_size else s_max)

        if autotune:
            # Pre-tune the Pallas tiles for every matmul shape this model's
            # chunk-prefill/decode will dispatch, so the serving loop itself
            # only ever *hits* the tuning cache (never sweeps mid-request).
            # The mesh shrinks the tuned shapes to per-device shards: local
            # decode rows M = n_slots/dp and TP-local layer dims N, K / tp.
            from repro.core.precision import get_precision, signed
            from repro.kernels import engine
            engine.tune_serving_shapes(
                cfg, signed(get_precision(cfg.precision)),
                n_slots=n_slots,
                chunk_size=self.chunk_size or self.prompt_len,
                mesh=mesh)

        self.metrics = metrics if metrics is not None else Metrics(n_slots)
        # flight recorder (runtime.tracing): host-side only — tracer calls
        # wrap the jitted dispatches, never run inside them (the
        # tracing-in-jit astlint rule).  The adaptive server passes one
        # shared tracer into every lane; trace_track names this batcher's
        # timeline row.
        from .tracing import Tracer
        self.tracer = Tracer.from_config(config.trace) if tracer is None \
            else tracer
        self.trace_track = "scheduler"
        self.profiler = None
        if getattr(config.trace, "profile", False):
            from .profile import StepProfiler
            self.profiler = StepProfiler(self.tracer)
        # per-step controller-signal sampling (the adaptive server turns
        # this off per lane and emits one consolidated tick itself)
        self.tick = True
        self.queue: deque[Request] = deque()
        self.slots: list[Request | None] = [None] * n_slots
        self.pos = np.zeros(n_slots, np.int32)
        self.done = np.ones(n_slots, bool)
        # slots paused by the paged batcher (block-pool exhaustion with
        # preemption off): their decode write deflects to the null block and
        # the emit loop skips them until a block frees up
        self.stalled = np.zeros(n_slots, bool)
        self._adm: _Admission | None = None
        self._adm_cache = None             # reused (1, s_adm) admission cache
        self._just_finished: list[Request] = []
        # host-side MIRRORS of the decode loop state.  The hot loop runs on
        # device-resident buffers (self._dev) and only re-stages them from
        # these mirrors when the scheduler actually mutated loop state
        # (admission/finish/requeue/stall churn) — never every step.  The
        # emit loop keeps the mirrors current so a re-stage is always exact.
        self.tokens = np.zeros((n_slots, 1), np.int32)
        self._dev: dict | None = None      # device loop state (lazy)
        self._loop_dirty = True            # mirrors changed -> re-stage
        self._live_list: list[int] | None = None   # live set at last stage
        self._stage_count = 0              # host->device stagings (tests)
        self._build_runtime(model, cfg, mesh)
        self._select = jax.jit(_select_dense)

    # ------------------------------------------------------------- runtime
    def _build_runtime(self, model, cfg, mesh):
        """Cache construction + step-function jit wiring.  The paged batcher
        (runtime.kvcache.PagedBatcher) overrides this wholesale: its KV state
        is a block pool + page tables instead of dense per-slot slabs."""
        n_slots, s_max = self.n_slots, self.s_max
        from repro.models import transformer as tfm
        self._make_cache = lambda b, s: tfm.make_cache(cfg, b, s, mesh=mesh)
        self.cache = self._make_cache(n_slots, s_max)

        # decode fuses the greedy argmax into the step program: one dispatch
        # per step and only a (B,) token vector crosses back to the host
        # (sampling slots still read their logits row on demand); the slot
        # cache is donated — the step updates it in place instead of
        # memcpy-ing the whole cache every token
        def _decode_fn(p, t, c, pos_vec):
            logits, new_cache = model.decode_step(p, t, c, pos_vec)
            return logits, jnp.argmax(logits[:, 0], axis=-1), new_cache

        self._decode_fn = _decode_fn
        if mesh is None:
            self._prefill = jax.jit(
                lambda p, b: model.prefill(p, b, self.s_adm))
            self._decode = jax.jit(_decode_fn, donate_argnums=(2,))
            if self.chunk_size:
                # the admission cache is dead after each chunk (reassigned
                # from the output) — donate it so appends update in place
                self._prefill_chunk = jax.jit(
                    lambda p, t, c, pos: model.prefill_chunk(p, t, c, pos),
                    donate_argnums=(2,))
        else:
            self._jit_sharded(model, cfg, mesh)

        # per-slot cache writer: copy a 1-batch cache into slot i (the
        # admission cache may be longer than the slot cache — slice first)
        def write_slot(cache, one, i):
            def upd(c, o):
                o = o[tuple(slice(0, min(cs, os))
                            for cs, os in zip(c.shape, o.shape))]
                return jax.lax.dynamic_update_slice(
                    c, o.astype(c.dtype), (0, i) + (0,) * (c.ndim - 2))
            return jax.tree_util.tree_map(upd, cache, one)
        if mesh is None:
            self._write_slot = jax.jit(write_slot, donate_argnums=(0,))
        else:
            # static slot index: the update start on the sharded batch dim is
            # compile-time known, so the partitioner keeps the write local to
            # the owning shard (no gather of the slot cache)
            self._write_slot = jax.jit(
                write_slot, donate_argnums=(0,), static_argnums=(2,),
                in_shardings=(self._slot_cache_sh, self._adm_cache_sh),
                out_shardings=self._slot_cache_sh)

    def _jit_sharded(self, model, cfg, mesh):
        """SPMD jit wiring: explicit in/out shardings for the three compiled
        step functions, derived from parallel/sharding.py's serving specs."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models import transformer as tfm
        shd = self._shd
        rep = NamedSharding(mesh, P())

        # slot cache: batch over data axes; admission cache (B=1) replicated
        slot_tmpl = jax.eval_shape(
            lambda: tfm.make_cache(cfg, self.n_slots, self.s_max))
        self._slot_cache_sh = shd.named_shardings(mesh, shd.cache_specs(
            slot_tmpl, cfg, mesh, self.n_slots, allow_sp=False))
        adm_tmpl = jax.eval_shape(lambda: tfm.make_cache(cfg, 1, self.s_adm))
        self._adm_cache_sh = shd.named_shardings(mesh, shd.cache_specs(
            adm_tmpl, cfg, mesh, 1, allow_sp=False))

        baxes = shd._batch_axes(cfg, mesh, self.n_slots)
        tok_sh = NamedSharding(mesh, P(baxes, None))
        pos_sh = NamedSharding(mesh, P(baxes))
        dec_logits_sh = NamedSharding(mesh, shd.logits_spec(cfg, mesh, self.n_slots))
        one_logits_sh = NamedSharding(mesh, shd.logits_spec(cfg, mesh, 1))

        # shard_map-FIRST dispatch (pure-DP): every step function runs
        # shard-local so qmatmul traces with per-device shapes and the tuned
        # Pallas tiles from serving_tune_plan(…, mesh=…) actually fire —
        # quantized-act precisions included, since act scales are per-row
        # (batch-shape-free numerics).  Decode shards the slot batch over the
        # data axes; the batch-1 prefill/chunk steps run fully replicated
        # (each device computes the admission chunk locally instead of
        # letting the partitioner split the reference ops).  Non-pure-DP
        # (TP) models keep the pjit path: their step internals need the
        # partitioner's collectives.
        pure = shd.pure_dp(cfg, mesh)
        if pure:
            from jax import shard_map
            rep_params = jax.tree_util.tree_map(
                lambda l: P(*(None,) * len(l.shape)), self.params)
            adm_specs = shd.cache_specs(adm_tmpl, cfg, mesh, 1, allow_sp=False)
            prefill_fn = shard_map(
                lambda p, b: model.prefill(p, b, self.s_adm), mesh=mesh,
                in_specs=(rep_params, {"tokens": P(None, None)}),
                out_specs=(shd.logits_spec(cfg, mesh, 1), adm_specs),
                check_vma=False)
        else:
            prefill_fn = lambda p, b: model.prefill(p, b, self.s_adm)
        self._prefill = jax.jit(
            prefill_fn,
            in_shardings=(self._psh, {"tokens": rep}),
            out_shardings=(one_logits_sh, self._adm_cache_sh))

        # Pure-DP decode runs SHARD-LOCAL via shard_map: params replicate and
        # nothing in a decode step crosses batch rows, so each device steps
        # its local slots (including the per-token KV row write, which pjit
        # lowered as a cross-device scatter-gather — ROADMAP leftover) and
        # the compiled step is fully collective-free.
        decode_fn = self._decode_fn
        if self._shard_local_decode(cfg, mesh, baxes):
            from jax import shard_map
            cache_specs = shd.cache_specs(slot_tmpl, cfg, mesh, self.n_slots,
                                          allow_sp=False)
            decode_fn = shard_map(
                self._decode_fn, mesh=mesh,
                in_specs=(jax.tree_util.tree_map(
                              lambda l: P(*(None,) * len(l.shape)), self.params),
                          P(baxes, None), cache_specs, P(baxes)),
                out_specs=(shd.logits_spec(cfg, mesh, self.n_slots),
                           P(baxes), cache_specs),
                check_vma=False)
        self._decode = jax.jit(
            decode_fn, donate_argnums=(2,),
            in_shardings=(self._psh, tok_sh, self._slot_cache_sh, pos_sh),
            out_shardings=(dec_logits_sh, pos_sh, self._slot_cache_sh))
        if self.chunk_size:
            if pure:
                from jax import shard_map
                chunk_fn = shard_map(
                    lambda p, t, c, pos: model.prefill_chunk(p, t, c, pos),
                    mesh=mesh,
                    in_specs=(rep_params, P(None, None), adm_specs, P()),
                    out_specs=(shd.logits_spec(cfg, mesh, 1), adm_specs),
                    check_vma=False)
            else:
                chunk_fn = lambda p, t, c, pos: model.prefill_chunk(
                    p, t, c, pos)
            self._prefill_chunk = jax.jit(
                chunk_fn,
                donate_argnums=(2,),
                in_shardings=(self._psh, rep, self._adm_cache_sh, rep),
                out_shardings=(one_logits_sh, self._adm_cache_sh))

    # ---------------------------------------------------------------- submit
    def _shard_local_decode(self, cfg, mesh, baxes) -> bool:
        """Whether the batched decode step can run shard-local (shard_map):
        pure-DP (params replicated, no TP collectives inside the step) and
        the slot batch actually sharded.  No precision gate: dynamic
        activation quantization is per-row, so local-batch numerics equal
        global-batch numerics for every config."""
        return baxes is not None and self._shd.pure_dp(cfg, mesh)

    # ---------------------------------------------------------------- audit
    def _audit_flags(self) -> dict:
        """Shared StepSpec fields for this batcher's serving contracts:
        precision flags, the engine backend, and pure-DP-ness (mesh-less
        batchers are trivially collective-free)."""
        from repro.core.precision import A_FLOAT, W_FLOAT, get_precision, \
            signed
        from repro.kernels import engine
        pcfg = signed(get_precision(self.model.cfg.precision))
        qw = pcfg.w_mode != W_FLOAT
        return {
            "quantized_weights": qw,
            "quantized_acts": qw and pcfg.a_mode != A_FLOAT
            and pcfg.a_bits <= 8,
            "backend": engine.default_backend(),
            "pure_dp": self.mesh is None
            or self._shd.pure_dp(self.model.cfg, self.mesh),
            "mesh": self.mesh,
        }

    def audit_steps(self) -> list:
        """Enumerate this batcher's compiled step functions as
        :class:`repro.analysis.report.StepSpec`\\ s — the exact callables and
        argument shapes the hot loop dispatches, for the compile-time
        contract checker (``python -m repro.analysis audit``)."""
        from repro.analysis.report import StepSpec
        flags = self._audit_flags()
        steps = [
            StepSpec(name="decode", fn=self._decode,
                     args=(self.params, jnp.asarray(self.tokens), self.cache,
                           jnp.asarray(self.pos)),
                     donate_argnums=(2,), **flags),
            StepSpec(name="prefill", fn=self._prefill,
                     args=(self.params,
                           {"tokens": jnp.zeros((1, min(8, self.s_adm)),
                                                jnp.int32)}),
                     **flags),
        ]
        if self.chunk_size:
            adm_cache = self._adm_cache if self._adm_cache is not None \
                else self._make_cache(1, self.s_adm)
            steps.append(StepSpec(
                name="chunk", fn=self._prefill_chunk,
                args=(self.params,
                      jnp.zeros((1, self.chunk_size), jnp.int32),
                      adm_cache, jnp.int32(0)),
                donate_argnums=(2,), **flags))
        steps.append(self._select_audit_step(
            "select", flags, self._select, jnp.ones((self.n_slots,), bool)))
        return steps

    def _select_audit_step(self, name: str, flags: dict, fn, row_arg):
        """StepSpec for the batched post-decode select dispatch.  The
        precision flags are forced off: select touches logits and int
        buffers only (no qmatmul), so the Pallas/scale rules cannot bind —
        it is audited for collective-freedom under pure DP.  ``row_arg`` is
        the third positional arg: the dense live mask, or the paged
        batcher's slot map."""
        from repro.analysis.report import StepSpec
        n = self.n_slots
        v = getattr(self.model.cfg, "padded_vocab", self.model.cfg.vocab)
        sel_flags = dict(flags, quantized_weights=False, quantized_acts=False)
        return StepSpec(
            name=name, fn=fn,
            args=(jnp.zeros((n, 1, v), jnp.float32),
                  jnp.zeros((n,), jnp.int32), row_arg,
                  jnp.zeros((n, 1), jnp.int32), jnp.zeros((n,), jnp.int32),
                  jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.float32),
                  jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32),
                  jnp.zeros((n,), jnp.int32)),
            **sel_flags)

    def _validate(self, req: Request):
        """Admission validation; raises a typed AdmissionError subclass
        (each still a ValueError for pre-redesign except-clauses)."""
        if req.tokens.size == 0 or req.tokens.shape[-1] < 1:
            # bucket_length(0, chunk) == 0 would produce a zero-length
            # admission (no chunks, no first token) — reject up front
            raise EmptyPromptError(
                f"request {req.rid}: empty prompt (0 tokens); prompts must "
                "contain at least one token", rid=req.rid)
        if req.max_new < 1:
            # max_new=0 used to fall through the `max_new <= 1` finish check
            # in _activate and still emit one token — reject instead of
            # silently producing output against a zero budget
            raise InvalidBudgetError(
                f"request {req.rid}: max_new={req.max_new} must be >= 1 "
                "(the first token is sampled from the prefill logits, so "
                "every admitted request emits at least one token)",
                rid=req.rid, max_new=req.max_new)
        length = req.tokens.shape[-1]
        if length >= self.s_max:
            raise PromptTooLongError(
                f"request {req.rid}: prompt length {length} needs s_max > "
                f"{length} (got {self.s_max}); the cache budget admits "
                f"prompts up to {self.s_max - 1} tokens, so this prompt is "
                f"{length - (self.s_max - 1)} tokens over the remaining "
                "budget", rid=req.rid, length=length, s_max=self.s_max)

    def submit(self, req: Request):
        self._validate(req)
        if req.submitted_at == 0.0:
            # idempotent on re-submission: the adaptive server stamps and
            # counts the request when it enters the CENTRAL queue, and this
            # routing hop into a lane must not re-count it (queue_ms spans
            # the whole wait, not just the post-routing tail)
            req.submitted_at = time.perf_counter()
            self.metrics.on_submit(req)
        self.queue.append(req)

    # ---------------------------------------------------------- token stream
    def _emit(self, req: Request, tok: int, finished: bool):
        req.output.append(tok)
        first = req.first_token_at == 0.0
        now = time.perf_counter()
        if first:
            req.first_token_at = now
        self.metrics.on_token(req, first, now)
        req.last_token_at = now
        if first and self.tracer.enabled:
            self.tracer.instant("first_token", "scheduler",
                                track=self.trace_track, rid=req.rid, tok=tok)
            self.tracer.flow("t", req.rid, track=self.trace_track)
        if req.on_token is not None:
            req.on_token(req, tok, finished)

    def _sample(self, req: Request, logits_row) -> int:
        """Next token from one slot's (V,) logits row under the request's
        sampling params.  Greedy is the exactness-preserving default.

        This is the per-slot REFERENCE implementation: the hot loop samples
        every live slot in one jitted dispatch (:func:`_sample_rows`, bit-
        identical row by row — tests/test_serving_ragged.py locks the
        equivalence); this method remains for the speculative emit loop and
        as the oracle the regression tests compare against."""
        if req.temperature <= 0.0:
            return int(jnp.argmax(logits_row))
        lg = logits_row.astype(jnp.float32) / req.temperature
        if req.top_k > 0:
            kth = jax.lax.top_k(lg, min(req.top_k, lg.shape[-1]))[0][-1]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(req.seed), req.rid),
            len(req.output))
        return int(jax.random.categorical(key, lg))

    def _finish(self, req: Request, slot: int):
        req.finished_at = time.perf_counter()
        self.metrics.on_finish(req)
        if self.tracer.enabled:
            self.tracer.instant("finish", "scheduler", track=self.trace_track,
                                rid=req.rid, slot=slot,
                                n_out=len(req.output))
            self.tracer.flow("f", req.rid, track=self.trace_track)
        self._release_slot(req, slot)
        self.done[slot] = True
        self.slots[slot] = None
        self._loop_dirty = True
        self._just_finished.append(req)

    def _release_slot(self, req: Request, slot: int):
        """Dense slots hold no shared state; the paged batcher releases the
        request's block references (and registers its prefix) here."""

    def _requeue(self, req: Request, slot: int):
        """Preemption hook point: return an admitted request to the FRONT of
        the queue with its slot freed.  ``rid``, ``output`` and the
        ``on_token`` stream survive untouched — re-admission prefills
        prompt + already-generated tokens and the stream continues from the
        next token, never replaying one.  Victims are preempted
        latest-admitted-first, so successive appendlefts restore
        admission-order priority at the queue head."""
        self.slots[slot] = None
        self.done[slot] = True
        self.stalled[slot] = False
        self._loop_dirty = True
        self.queue.appendleft(req)

    # ----------------------------------------------------------------- admit
    def _free_slot(self) -> int | None:
        for i in range(self.n_slots):
            if self.done[i] and self.slots[i] is None:
                return i
        return None

    def _activate(self, req: Request, slot: int, one_cache, first_logits_row):
        """First token of this admission sampled, admission cache resident.

        A preemption-resumed request (non-empty ``output``) re-enters here
        mid-stream: ``length`` counts prompt + already-generated tokens, the
        budget check runs against the whole stream, and the cache-budget cap
        that the decode loop would have applied fires here instead — the
        resumed stream stops exactly where the uninterrupted one would
        have."""
        # the host blocks here on the admission's last logits
        with self.tracer.span("first_token", "scheduler",
                              track=self.trace_track, rid=req.rid):
            tok = self._sample(req, first_logits_row)
        resumed = bool(req.output)
        length = req.tokens.shape[1] + len(req.output)
        finished = (len(req.output) + 1 >= req.max_new
                    or (req.eos_id is not None and tok == req.eos_id)
                    or (resumed and length >= self.s_max - 1))
        self._emit(req, tok, finished)
        if finished:
            self._finish(req, slot)
            return
        self._join_slot(slot, one_cache)
        self.tokens[slot, 0] = tok
        self.pos[slot] = length
        self.done[slot] = False
        self._loop_dirty = True

    def _join_slot(self, slot: int, one_cache):
        """Copy the admission cache into slot ``slot`` (no-op for the paged
        batcher, whose prefill chunks write blocks in place)."""
        self.cache = self._write_slot(self.cache, one_cache, slot)

    def _advance_admission(self):
        """Chunked path: at most ONE prefill chunk per scheduler step, so
        active slots never wait longer than a chunk for their next decode."""
        if self._adm is None:
            slot = self._free_slot()
            if not self.queue or slot is None:
                return
            req = self.queue.popleft()
            req.started_at = time.perf_counter()
            self.metrics.on_admit(req)
            length = req.tokens.shape[1]
            if self.tracer.enabled:
                self.tracer.instant("admit", "scheduler",
                                    track=self.trace_track, rid=req.rid,
                                    slot=slot, prompt_tokens=length)
                self.tracer.flow("s", req.rid, track=self.trace_track)
            l_pad = bucket_length(length, self.chunk_size)
            padded = np.zeros((1, l_pad), np.int32)
            padded[:, :length] = req.tokens
            if self._adm_cache is None:
                self._adm_cache = self._make_cache(1, self.s_adm)
            self._adm = _Admission(req, slot, padded, length)
            self.slots[slot] = req         # reserve (done stays True)

        adm = self._adm
        c = self.chunk_size
        chunk = jnp.asarray(adm.tokens[:, adm.next_pos:adm.next_pos + c])
        self.metrics.prefill_chunks += 1
        tr = self.tracer
        if tr.enabled:
            tr.flow("t", adm.req.rid, track=self.trace_track)
        with tr.span("prefill_chunk", "scheduler", track=self.trace_track,
                     rid=adm.req.rid, pos=adm.next_pos):
            if self.profiler is None:
                logits, self._adm_cache = self._prefill_chunk(
                    self.params, chunk, self._adm_cache,
                    jnp.int32(adm.next_pos))
            else:
                with self.profiler.step("prefill_chunk"):
                    logits, self._adm_cache = self._prefill_chunk(
                        self.params, chunk, self._adm_cache,
                        jnp.int32(adm.next_pos))
                    jax.block_until_ready(logits)
        adm.next_pos += c
        if adm.next_pos >= adm.tokens.shape[1]:
            # final chunk always contains the last real position L-1
            row = logits[0, (adm.length - 1) % c]
            self._adm = None
            self._activate(adm.req, adm.slot, self._adm_cache, row)

    def _admit_full(self):
        """Whole-prompt admission (SSM/hybrid stacks, or chunk_size=0):
        exact-length prefill per request — stalls decode for its duration."""
        while self.queue:
            slot = self._free_slot()
            if slot is None:
                return
            req = self.queue.popleft()
            req.started_at = time.perf_counter()
            self.metrics.on_admit(req)
            self.metrics.prefill_full += 1
            self.slots[slot] = req
            tr = self.tracer
            if tr.enabled:
                tr.instant("admit", "scheduler", track=self.trace_track,
                           rid=req.rid, slot=slot,
                           prompt_tokens=req.tokens.shape[1])
                tr.flow("s", req.rid, track=self.trace_track)
            batch = {"tokens": jnp.asarray(req.tokens, jnp.int32)}
            with tr.span("prefill", "scheduler", track=self.trace_track,
                         rid=req.rid):
                logits, one_cache = self._prefill(self.params, batch)
            self._activate(req, slot, one_cache, logits[0, -1])

    # ----------------------------------------------------------------- step
    def _live_slots(self) -> list[int]:
        """Slots the decode step advances this iteration: occupied, not
        done, not stalled — computed AFTER ``_pre_decode`` so allocation
        stalls and preemptions are reflected."""
        return [i for i in range(self.n_slots)
                if self.slots[i] is not None and not self.done[i]
                and not self.stalled[i]]

    def _stage_loop_state(self, live: list[int]):
        """(Re)stage the decode-loop device buffers from the host mirrors:
        tokens, positions, per-slot output counts, the live mask, and the
        per-slot sampling params.  Called only when the scheduler mutated
        loop state (``_loop_dirty``) or the live set changed — the greedy
        steady state runs entirely on the device-resident buffers with zero
        host->device staging per step (``_stage_count`` counts stagings so
        tests can assert exactly that)."""
        n = self.n_slots
        nout = np.zeros(n, np.int32)
        temps = np.zeros(n, np.float32)
        topks = np.zeros(n, np.int32)
        seeds = np.zeros(n, np.int32)
        rids = np.zeros(n, np.int32)
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            nout[i] = len(req.output)
            temps[i] = req.temperature
            topks[i] = req.top_k
            seeds[i] = req.seed
            rids[i] = req.rid
        mask = np.zeros(n, bool)
        mask[live] = True
        self._dev = {
            "tok": jnp.asarray(self.tokens), "pos": jnp.asarray(self.pos),
            "nout": jnp.asarray(nout), "live": jnp.asarray(mask),
            "temps": jnp.asarray(temps), "topks": jnp.asarray(topks),
            "seeds": jnp.asarray(seeds), "rids": jnp.asarray(rids),
        }
        self._loop_dirty = False
        self._stage_count += 1

    def _dispatch_decode(self):
        """Decode + batched select on the device-resident loop state; the
        paged batcher overrides this with its pool/page-table plumbing."""
        d = self._dev
        logits, greedy, self.cache = self._decode(
            self.params, d["tok"], self.cache, d["pos"])
        nxt, d["tok"], d["pos"], d["nout"] = self._select(
            logits, greedy, d["live"], d["tok"], d["pos"], d["nout"],
            d["temps"], d["topks"], d["seeds"], d["rids"])
        return nxt

    def _decode_call(self, live: list[int]) -> np.ndarray:
        """One decode + select dispatch for the live slots.  Returns the
        full (n_slots,) np.int32 next-token vector — the host loop's ONLY
        per-step device sync; dead/stalled rows repeat their previous
        token.  Sampling (greedy and temperature/top-k alike) happened on
        device in the jitted select step, so there are no per-slot
        round-trips regardless of sampling params (the old non-greedy path
        blocked once per sampled slot per token)."""
        tr = self.tracer
        if self._loop_dirty or live != self._live_list:
            with tr.span("stage", "scheduler", track=self.trace_track):
                self._stage_loop_state(live)
            self._live_list = list(live)
        with tr.span("decode", "scheduler", track=self.trace_track):
            if self.profiler is None:
                nxt = self._dispatch_decode()
            else:
                # the device-sync boundary: the next-token vector is the
                # host loop's only data dependency — block inside the
                # bracket so the profiler splits device time from the host
                # gap before the next dispatch
                with self.profiler.step("decode"):
                    nxt = self._dispatch_decode()
                    jax.block_until_ready(nxt)
        with tr.span("decode_fetch", "scheduler", track=self.trace_track):
            return np.asarray(nxt, np.int32)

    def _pre_decode(self):
        """Hook before the batched decode dispatch.  The paged batcher's
        dynamic allocation lives here: lazily allocate the next block of
        every slot about to cross a block boundary, preempting
        lowest-priority requests when the pool is exhausted.  May retire
        slots (preemption re-queues them), so the caller re-checks
        ``done``."""

    def _tick(self):
        """Per-scheduler-step controller-signal sample (queue depth, pool
        utilization).  Runs every step — never only on admission — so the
        brownout controller's window keeps moving while the queue idles.
        The adaptive server disables per-lane ticks (``tick = False``) and
        emits one consolidated sample itself."""
        if not self.tick:
            return
        active = sum(1 for i in range(self.n_slots)
                     if self.slots[i] is not None and not self.done[i])
        self.metrics.on_step(
            len(self.queue) + (1 if self._adm is not None else 0),
            active=active)

    def step(self):
        """One scheduler iteration: a prefill chunk (if a request is being
        admitted) plus one decode step for every active slot.  Returns the
        requests finished this step.

        This is the flight-recorder wrapper — the step span, the tuning-
        cache counter sample, and the metrics-snapshot cadence — around
        :meth:`_step_impl`, which subclasses override for their scheduling
        variants (the paged batcher's speculative rounds)."""
        tr = self.tracer
        with tr.span("step", "scheduler", track=self.trace_track,
                     queue_depth=len(self.queue)):
            finished = self._step_impl()
        tr.maybe_tuning_counter()
        if self.tick and tr.snapshotter is not None:
            tr.tick_snapshot(self.metrics)
        return finished

    def _admit(self):
        """This step's admission work: one prefill chunk, or whole-prompt
        prefills."""
        with self.tracer.span("admit", "scheduler", track=self.trace_track):
            if self.chunk_size:
                self._advance_admission()
            else:
                self._admit_full()

    def _allocate(self):
        """Block allocation (and preemption) before the decode dispatch."""
        if not all(self.done):
            with self.tracer.span("pre_decode", "scheduler",
                                  track=self.trace_track):
                self._pre_decode()

    def _step_impl(self):
        self._tick()
        self._admit()
        self._allocate()
        # stalled slots took no block this step: their write deflected to
        # the null block and their logits would be meaningless — they stay
        # out of the live set and re-feed the same token once a block frees
        live = self._live_slots()
        if live:
            nxt = self._decode_call(live)
            self.metrics.decode_steps += 1
            with self.tracer.span("emit", "scheduler",
                                  track=self.trace_track):
                for i in live:
                    req = self.slots[i]
                    tok = int(nxt[i])
                    self.metrics.decode_slot_tokens += 1
                    self.pos[i] += 1
                    hit_eos = req.eos_id is not None and tok == req.eos_id
                    full = (len(req.output) + 1 >= req.max_new or hit_eos
                            or self.pos[i] >= self.s_max - 1)
                    self._emit(req, tok, full)
                    if full:
                        self._finish(req, i)
                    else:
                        self.tokens[i, 0] = tok
        finished, self._just_finished = self._just_finished, []
        return finished

    @property
    def idle(self) -> bool:
        return not self.queue and self._adm is None and bool(all(self.done))

    def run(self, max_steps: int = 10_000):
        """Drain the queue; returns all finished requests.  On any exception
        the flight recorder dumps its ring next to the crash before
        re-raising."""
        out = []
        try:
            for _ in range(max_steps):
                out.extend(self.step())
                if self.idle:
                    break
        except BaseException:
            self.tracer.on_crash()
            raise
        return out
