"""Pallas block-size autotuner with a persistent JSON cache.

The paper's point (§II, Tables IV/V) is that each (activation x weight)
bit-width deserves its *own* hardware configuration — FINN-R generalizes this
to "search the configuration space per workload".  On TPU the per-width
configuration knob is the Pallas tile: (bm, bn, bk) block sizes trade VMEM
residency against grid overhead differently for a 1-bit XNOR kernel than for
an 8-bit unpack-to-MXU kernel.  This module owns that search:

  * ``candidate_blocks`` enumerates tiles valid for a given
    (M, N, K, weight_kind, w_bits) — the pack word imposes ``bk % (32/bits)``,
    the XNOR kernel counts K in 32-bit words, and Mosaic tiles the last two
    dims of every block in (8, 128) units (see :func:`_valid_block`);
  * ``autotune`` times a caller-supplied ``measure(block)`` over the
    candidates (interpret-mode on CPU, compiled on TPU) and records the
    winner;
  * winners persist to a JSON cache (``~/.cache/repro/tuning.json``,
    override with ``REPRO_TUNING_CACHE``) keyed by device kind and shape
    class, so serving processes only ever *look up* — they never re-sweep,
    and a tile timed on one device kind is never served on another.

``get_block_sizes`` is the hot-path entry: cache hit returns the tuned tile,
miss returns a safe clipped default (and counts a miss — it does NOT sweep;
sweeping is an explicit, offline act).
"""
from __future__ import annotations

import json
import os
import time
import warnings
from collections.abc import Callable, Sequence

Block = tuple[int, int, int]

DEFAULT_BLOCK: Block = (128, 128, 512)

# In-memory cache state.  ``_cache is None`` means "not loaded yet"; loading
# is lazy so importing the engine never touches the filesystem.
_cache: dict[str, dict] | None = None
_cache_src: str | None = None
# keys this process actually MEASURED (vs merely loaded from disk): only
# these may overwrite a concurrent writer's fresher on-disk entry in _save
_dirty: set = set()

_STATS = {"hits": 0, "misses": 0, "sweeps": 0}


# ---------------------------------------------------------------------------
# cache file handling
# ---------------------------------------------------------------------------
def cache_path() -> str:
    """Tuning-cache location; override with ``REPRO_TUNING_CACHE``."""
    env = os.environ.get("REPRO_TUNING_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro", "tuning.json")


def _sane_entry(entry) -> bool:
    """Structural validity of one cache entry (a corrupt/hand-edited file
    must degrade to a miss, never an exception on the serving hot path)."""
    if not isinstance(entry, dict):
        return False
    block = entry.get("block")
    return (isinstance(block, (list, tuple)) and len(block) == 3
            and all(isinstance(v, int) and v > 0 for v in block))


def _read_entries(path: str) -> dict[str, dict]:
    """Sane entries currently on disk (no in-memory cache involvement)."""
    entries: dict[str, dict] = {}
    try:
        with open(path) as f:
            data = json.load(f)
        if isinstance(data, dict):
            raw = data.get("entries", {})
            if isinstance(raw, dict):
                # drop structurally-invalid entries (truncated / corrupted /
                # hand-edited cache) so every consumer sees sane dicts only
                entries = {k: v for k, v in raw.items() if _sane_entry(v)}
    except (OSError, ValueError):
        # unreadable or torn JSON (e.g. a writer killed mid-write on a
        # filesystem without atomic rename): serve from defaults
        entries = {}
    return entries


def _load() -> dict[str, dict]:
    global _cache, _cache_src
    path = cache_path()
    if _cache is not None and _cache_src == path:
        return _cache
    _cache, _cache_src = _read_entries(path), path
    return _cache


def _save() -> None:
    global _cache
    path = cache_path()
    try:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        # Merge-on-write: another process may have tuned (and persisted)
        # different shape classes since we loaded — a blind read-modify-write
        # would drop its entries (last writer wins).  Re-read the file under
        # the atomic replace and union it with our in-memory entries.  On a
        # key conflict, our entry wins only if we MEASURED it this session
        # (``_dirty``) — entries we merely loaded at startup must not
        # resurrect over a concurrent re-tune's fresher measurement.
        merged = _read_entries(path)
        for key, entry in _load().items():
            if key in _dirty or key not in merged:
                merged[key] = entry
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"version": 1, "entries": merged}, f, indent=1,
                      sort_keys=True)
        os.replace(tmp, path)
        _cache = merged
    except OSError as e:
        # unwritable cache: tuned tiles still serve from memory this process;
        # they just won't persist for the next one
        warnings.warn(f"tuning cache not persisted to {path}: {e}",
                      RuntimeWarning, stacklevel=2)


def reset(clear_stats: bool = True) -> None:
    """Drop the in-memory cache (tests; forces re-read of the JSON file)."""
    global _cache, _cache_src
    _cache, _cache_src = None, None
    _dirty.clear()
    if clear_stats:
        for k in _STATS:
            _STATS[k] = 0


def stats() -> dict[str, int]:
    return dict(_STATS)


# ---------------------------------------------------------------------------
# shape classes and candidate tiles
# ---------------------------------------------------------------------------
def _pow2_bucket(m: int, cap: int = 1024) -> int:
    b = 8
    while b < m and b < cap:
        b *= 2
    return b


def shape_class(m: int, n: int, k: int) -> tuple[int, int, int]:
    """(N, K) are structural (layer dims); M varies per batch — bucket it to
    the next power of two so prefill/decode of nearby batch sizes share a
    tuning entry."""
    return (_pow2_bucket(m), n, k)


def device_kind() -> str:
    """The kind of device the process computes on (``TPU v5 lite``, ``cpu``):
    a tile timed on one kind says nothing about another, so it is part of
    every cache key."""
    import jax
    return jax.devices()[0].device_kind


def cache_key(kind: str, a_bits: int, w_bits: int, backend: str,
              m: int, n: int, k: int) -> str:
    mb, nn, kk = shape_class(m, n, k)
    return (f"{backend}|{device_kind()}|{kind}|a{a_bits}w{w_bits}"
            f"|m{mb}n{nn}k{kk}")


def _bk_align(kind: str, w_bits: int) -> int:
    """bk must cover whole pack words: 32/bits codes per int32 word."""
    if kind == "binary":
        return 32
    if kind == "ternary":
        return 16
    if 32 % max(w_bits, 1) == 0:
        return 32 // w_bits
    return 1


# Mosaic lays the last two dims of every block out in (8, 128) tiles: each
# block dim there must be a multiple of its tile dim or span the whole array.
SUBLANE, LANE = 8, 128


def _bk_step(kind: str, w_bits: int) -> int:
    """Smallest legal bk short of the whole K: the packed weight block
    ``(bn, bk / codes_per_word)`` must keep a lane multiple of 128 words."""
    return LANE * _bk_align(kind, w_bits)


def _valid_block(m: int, n: int, k: int, kind: str, w_bits: int,
                 block: Block) -> bool:
    """A tile the kernels accept AND Mosaic can lay out.  The blocks are
    x ``(bm, bk)`` (XNOR: ``(bm, bk/32)`` words), weight ``(bn, bkw)``,
    scale ``(1, bn)`` and out ``(bm, bn)``, so: bm a multiple of 8 (the
    engine pads M up to it), bn a multiple of 128 dividing N or all of N,
    and bk all of K or a multiple of ``_bk_step`` dividing K."""
    bm, bn, bk = block
    return (bm % SUBLANE == 0 and bm <= max(256, _pow2_bucket(m))
            and (bn == n or (bn % LANE == 0 and n % bn == 0))
            and (bk == k or (bk % _bk_step(kind, w_bits) == 0
                             and k % bk == 0)))


def fallback_block(m: int, n: int, k: int, kind: str, w_bits: int) -> Block:
    """The hand-wired default, clipped to a Mosaic-legal tile for this
    shape: bk is the largest legal step multiple up to the default that
    divides K, else all of K."""
    bm, bn, bk = DEFAULT_BLOCK
    bm = min(bm, _pow2_bucket(m))
    if n % bn:
        bn = n
    step = _bk_step(kind, w_bits)
    bk = max((b for b in range(step, max(bk, step) + 1, step) if k % b == 0),
             default=k)
    return (bm, bn, bk)


def candidate_blocks(m: int, n: int, k: int, kind: str, w_bits: int,
                     ) -> list[Block]:
    """MXU-aligned sweep grid; always contains the clipped default."""
    step = _bk_step(kind, w_bits)
    bks = sorted({k} | {step * i for i in (1, 2, 4, 8) if step * i < k})
    cands = []
    for bm in (8, 16, 32, 64, 128, 256):
        for bn in sorted({128, 256, 512, n}):
            for bk in bks:
                b = (bm, bn, bk)
                if _valid_block(m, n, k, kind, w_bits, b):
                    cands.append(b)
    fb = fallback_block(m, n, k, kind, w_bits)
    if fb not in cands:
        cands.insert(0, fb)
    return cands


# ---------------------------------------------------------------------------
# lookup (hot path) and sweep (explicit/offline)
# ---------------------------------------------------------------------------
def get_block_sizes(m: int, n: int, k: int, *, kind: str, a_bits: int,
                    w_bits: int, backend: str = "pallas") -> Block:
    """Cache lookup only — never sweeps.  Miss returns the clipped default
    so serving latency is deterministic even with a cold cache."""
    cache = _load()
    key = cache_key(kind, a_bits, w_bits, backend, m, n, k)
    entry = cache.get(key)
    if entry is not None:
        b = tuple(entry["block"])
        if _valid_block(m, n, k, kind, w_bits, b):
            _STATS["hits"] += 1
            return b  # type: ignore[return-value]
        # stale/foreign entry (e.g. hand-edited cache): evict so an explicit
        # autotune can re-sweep instead of being shadowed forever
        cache.pop(key, None)
    _STATS["misses"] += 1
    return fallback_block(m, n, k, kind, w_bits)


def lookup(m: int, n: int, k: int, *, kind: str, a_bits: int, w_bits: int,
           backend: str = "pallas") -> dict | None:
    """Raw cache entry for a shape class, or None on a miss (no fallback
    synthesis, no stats) — for callers that need to distinguish a tuned
    recommendation from the default (e.g. the paged-KV block-size pick)."""
    entry = _load().get(cache_key(kind, a_bits, w_bits, backend, m, n, k))
    return entry if entry is not None and _sane_entry(entry) else None


def autotune(m: int, n: int, k: int, *, kind: str, a_bits: int, w_bits: int,
             backend: str, measure: Callable[[Block], float],
             candidates: Sequence[Block] | None = None,
             force: bool = False, persist: bool = True) -> dict:
    """Sweep ``candidates`` (default: :func:`candidate_blocks`) with the
    caller's ``measure(block) -> seconds`` and persist the winner.

    Returns the cache entry ``{"block", "us", "default_us", "swept"}``.
    A pre-existing entry short-circuits (zero re-sweeps) unless ``force``.
    """
    key = cache_key(kind, a_bits, w_bits, backend, m, n, k)
    cache = _load()
    if key in cache and not force:
        _STATS["hits"] += 1
        return cache[key]

    cands = list(candidates) if candidates is not None else \
        candidate_blocks(m, n, k, kind, w_bits)
    default = fallback_block(m, n, k, kind, w_bits)
    if default not in cands:
        cands.insert(0, default)

    swept = []
    for block in cands:
        secs = measure(block)
        swept.append({"block": list(block), "us": secs * 1e6})
    _STATS["sweeps"] += 1
    best = min(swept, key=lambda e: e["us"])
    default_us = next(e["us"] for e in swept
                      if tuple(e["block"]) == default)
    entry = {"block": best["block"], "us": best["us"],
             "default_us": default_us, "swept": swept}
    cache[key] = entry
    _dirty.add(key)
    if persist:
        _save()
    return entry


def prime(m: int, n: int, k: int, *, kind: str, a_bits: int, w_bits: int,
          backend: str = "pallas", block: Block | None = None,
          persist: bool = True) -> dict:
    """Insert a cache entry for one shape class WITHOUT measuring anything —
    the clipped default block (or an explicit ``block``) at zero cost.

    This is how the invariant auditor (``repro.analysis``) warms a scratch
    cache before tracing: the ``tuning_cache_hit`` contract only cares that
    the serving hot path resolves every per-shard tile key with zero sweeps,
    not that the tiles are optimal.  A pre-existing entry is left alone."""
    key = cache_key(kind, a_bits, w_bits, backend, m, n, k)
    cache = _load()
    if key in cache:
        return cache[key]
    b = tuple(block) if block is not None \
        else fallback_block(m, n, k, kind, w_bits)
    entry = {"block": list(b), "us": 0.0, "default_us": 0.0, "swept": []}
    cache[key] = entry
    _dirty.add(key)
    if persist:
        _save()
    return entry


def time_fn(fn: Callable[[], object], iters: int = 3) -> float:
    """Median wall-clock seconds of ``fn`` after one warmup (compile) call."""
    import jax
    jax.block_until_ready(fn())
    ts = []
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]
