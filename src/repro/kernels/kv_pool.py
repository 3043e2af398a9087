"""The paged KV pool's storage layout, defined in one place.

Every reader and writer of the pool takes the layout from here: the model's
allocation and per-step row writes (``models.layers``), the paged kernels
(:mod:`repro.kernels.paged_attention`, :mod:`repro.kernels.decode_fused`),
the jnp reference gather, ``parallel.sharding.pool_specs`` and the batcher's
byte budget (``runtime.kvcache.paged_block_bytes``).

One attention layer's pool, stacked over the period axis ``L``:

  "kv"       : (L, NB, bs/P, P*W)     int8 codes (kv_bits 8; kv_bits 4 packs
                                      two codes per byte, Dh' = Dh/2), or the
                                      model dtype at kv_bits 16
  "kv_scale" : (L, NBR, R*2*KV*bs)    f32 per-(position, head) scales, ``R``
                                      blocks a row, NB rounded up to a
                                      multiple of 8*R (kv_bits < 16 only)

A position's codes are ``W = 2*KV*Dh'`` lanes, head-major, each KV head's
keys beside its values.  A stored row holds ``P`` consecutive positions
(:func:`positions_per_row`), interleaved within each head: head ``h``'s
keys of position ``r*P + p`` fill lanes ``[(2h*P + p)*Dh', .. + Dh')`` of
stored row ``r`` and its values the same lanes of side ``2h + 1``.  A scale
row holds ``R`` consecutive blocks (:func:`blocks_per_scale_row`): block
``b``'s row is ``b // R``, and the scales of side ``s`` of its positions
(head ``h``'s keys are side ``2h``, its values ``2h + 1``) fill lanes
``[(s*R + b % R)*bs, .. + bs)``, one lane per position.  A block of one layer is one
``(bs/P, P*W)`` tile of codes, and its scales ``bs`` lanes of one row of an
``(8, R*2*KV*bs)`` tile that holds ``8*R`` blocks (the f32 tile height: a
block of fewer rows than 8 is no Mosaic block, and a unit row dim would
leave XLA's row writes in a layout of their own).  The kernels block over
exactly these tiles: nothing is reshaped between the stored arrays and the
kernels.  Sharding the lane dim in whole heads splits keys, values and
scales alike.

On the TPU the two minor dims are tiled: lanes pad to 128 and rows to the
tile height (:func:`laid_out_bytes`).  ``P`` is the packing of positions
that pads least: smollm-135m's kv8 rows are 384 lanes, one position a row,
and its kv4 rows of 192 lanes would pad to 256 where two positions fill
384.  ``R`` makes a scale row whole 128-lane tiles (4 blocks of 96 lanes
there), so that neither leaf pads its lanes and the row-major layout the
kernels read is also the chip's own default layout for each leaf: a leaf
whose lanes pad gets the block dim minor by default, and a program loaded
from the persistent compile cache handed its pinned result back in that
default layout, which the next program refused.  :func:`formats` pins the
row-major layout on every program that takes the pool.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

CODES = "kv"
SCALES = "kv_scale"
SCALE_FILL = 1e-6          # the scale of a never-written position
SCALE_ROWS = 8             # rows per scale tile (the f32 tile height)
LANE_DIM = -1              # the dim whose even slices are whole heads


def store_dh(dh: int, kv_bits: int) -> int:
    """Stored width of one head's key (or value) row."""
    return dh // 2 if kv_bits == 4 else dh


def positions_per_row(block_size: int, width: int, dtype) -> int:
    """Positions ``P`` one stored row of codes holds: among the powers of
    two that divide ``block_size``, the one whose ``(bs/P, P*width)`` block
    lays out in the fewest bytes, the smallest on a tie."""
    ps = [1 << i for i in range(block_size.bit_length())
          if block_size % (1 << i) == 0]
    return min(ps, key=lambda p: (laid_out_bytes(
        (block_size // p, p * width), dtype), p))


def blocks_per_scale_row(block_size: int, kv_heads: int) -> int:
    """Blocks ``R`` one scale row holds: the fewest whose ``R*2*KV*bs``
    scales fill whole 128-lane tiles."""
    return 128 // math.gcd(128, 2 * kv_heads * block_size)


def scale_tile_blocks(block_size: int, kv_heads: int) -> int:
    """Blocks one ``(8, lanes)`` tile of scale rows holds: the unit in which
    the scale leaf grows."""
    return SCALE_ROWS * blocks_per_scale_row(block_size, kv_heads)


def block_positions(codes, kv_heads: int, dh_s: int) -> int:
    """Positions per block of stored codes whose heads are ``dh_s`` wide."""
    return codes.shape[-2] * codes.shape[-1] // (2 * kv_heads * dh_s)


def leaf_specs(num_blocks: int, block_size: int, kv_heads: int, dh: int,
               kv_bits: int, dtype, stacked: int | None = None) -> dict:
    """``{name: ShapeDtypeStruct}`` of one attention layer's pool."""
    lead = (stacked,) if stacked else ()
    cdt = jnp.dtype(dtype) if kv_bits == 16 else jnp.dtype(jnp.int8)
    width = 2 * kv_heads * store_dh(dh, kv_bits)
    per = positions_per_row(block_size, width, cdt)
    specs = {CODES: jax.ShapeDtypeStruct(
        lead + (num_blocks, block_size // per, per * width), cdt)}
    if kv_bits < 16:
        per_row = blocks_per_scale_row(block_size, kv_heads)
        tile = SCALE_ROWS * per_row
        specs[SCALES] = jax.ShapeDtypeStruct(
            lead + (-(-num_blocks // tile) * SCALE_ROWS,
                    per_row * 2 * kv_heads * block_size), jnp.float32)
    return specs


def make(num_blocks: int, block_size: int, kv_heads: int, dh: int,
         kv_bits: int, dtype, stacked: int | None = None) -> dict:
    """An empty pool: zero codes, scales at :data:`SCALE_FILL`."""
    return {name: jnp.full(s.shape, SCALE_FILL if name == SCALES else 0,
                           s.dtype)
            for name, s in leaf_specs(num_blocks, block_size, kv_heads, dh,
                                      kv_bits, dtype, stacked).items()}


def from_heads(k, ks, v, vs) -> dict:
    """Pool leaves from per-head ``(..., NB, bs, KV, Dh')`` codes and
    ``(..., NB, bs, KV, 1)`` scales (``ks``/``vs`` None at kv_bits 16)."""
    *lead, nb, bs, kv, dh_s = k.shape
    per = positions_per_row(bs, 2 * kv * dh_s, k.dtype)
    kv2 = jnp.stack([k, v], axis=-2)                     # (.., bs, KV, 2, Dh')
    kv2 = kv2.reshape(*lead, nb, bs // per, per, kv, 2, dh_s)
    pool = {CODES: jnp.moveaxis(kv2, -4, -2).reshape(*lead, nb, bs // per,
                                                      -1)}
    if ks is not None:
        per_row = blocks_per_scale_row(bs, kv)
        sc = jnp.stack([ks[..., 0], vs[..., 0]], axis=-1)   # (.., bs, KV, 2)
        pad = [(0, 0)] * len(lead) + [(0, -nb % (SCALE_ROWS * per_row))] \
            + [(0, 0)] * 3
        sc = jnp.pad(sc, pad, constant_values=SCALE_FILL)
        sc = sc.reshape(*lead, -1, per_row, bs, kv, 2)     # (.., R, bs, KV, 2)
        sc = jnp.moveaxis(sc, (-2, -1), (-4, -3))          # (.., KV, 2, R, bs)
        pool[SCALES] = sc.reshape(*sc.shape[:-4], -1)
    return pool


def write(pool: dict, layer, phys, off, k, ks, v, vs) -> dict:
    """Write ``N`` positions' rows into physical block ``phys[n]`` at
    in-block offset ``off[n]`` of layer ``layer``.  ``k``/``v``: (N, KV, Dh')
    codes (or float rows at kv_bits 16); ``ks``/``vs``: (N, KV, 1) scales or
    None.  Both leaves take whole stored rows, scattered into the stacked
    leaves in place, so only the rows written move: XLA keeps a whole-row
    scatter in the kernels' layout, where one of lane slices it would lay
    out anew, and copy the pool for, in every layer.

    Where a stored row holds more than one position (a block's scale row
    always does), each update writes the whole row with every update to
    that row applied (:func:`_merge_rows`)."""
    n, kv, dh_s = k.shape
    codes = pool[CODES]
    per = codes.shape[-1] // (2 * kv * dh_s)
    bs = codes.shape[-2] * per
    rows = jnp.stack([k, v], axis=2).astype(codes.dtype)   # (N, KV, 2, Dh')
    if per == 1:
        new = {CODES: codes.at[layer, phys, off].set(rows.reshape(n, -1))}
    else:
        side = jnp.arange(2 * kv, dtype=off.dtype).reshape(kv, 2)
        lane = ((side[None] * per + (off % per)[:, None, None])[..., None]
                * dh_s + jnp.arange(dh_s, dtype=off.dtype))
        new = {CODES: _merge_rows(codes, (layer, phys, off // per),
                                  phys * (bs // per) + off // per,
                                  lane.reshape(n, -1), rows.reshape(n, -1))}
    if ks is not None:
        sc = jnp.stack([ks[..., 0], vs[..., 0]], axis=2).reshape(n, 2 * kv)
        per_row = pool[SCALES].shape[-1] // (2 * kv * bs)
        row = phys // per_row
        lane = (off + bs * (phys % per_row))[:, None] \
            + per_row * bs * jnp.arange(2 * kv, dtype=off.dtype)
        new[SCALES] = _merge_rows(pool[SCALES], (layer, row), row, lane, sc)
    return new


def _merge_rows(leaf, at, key, lane, val):
    """``leaf`` with lanes ``lane[n]`` of stored row ``leaf[at][n]`` set to
    ``val[n]``, where updates of one row share its ``key``.  Each update
    takes the first update of its row as the row's owner: the owners' old
    rows gather every update of their row (one of them wins where two set
    one lane, as in the null block that out-of-range positions deflect to),
    and every update writes its owner's row back, whole.  Cost is linear in
    the updates' lanes, plus one (N, N) key compare."""
    first = jnp.argmax(key[:, None] == key[None, :], axis=1)
    rows = leaf[at].at[first[:, None], lane].set(val.astype(leaf.dtype))
    return leaf.at[at].set(rows[first])


def gather(codes, scales, page_table, layer, kv_heads: int, dh_s: int):
    """Dense per-sequence view of layer ``layer`` of the stacked leaves
    through ``page_table`` (B, n_blocks), in the models' per-head form:
    ``k, ks, v, vs`` with codes (B, n_blocks*bs, KV, Dh') (``dh_s`` = Dh')
    and scales (B, n_blocks*bs, KV, 1) (None at kv_bits 16).  Only the
    sequences' blocks are read, never a whole layer."""
    g = codes[layer, page_table]                     # (B, nblk, bs/P, P*W)
    b, nblk, rows, lanes = g.shape
    per = lanes // (2 * kv_heads * dh_s)
    g = jnp.moveaxis(g.reshape(b, nblk, rows, kv_heads, 2, per, dh_s), 5, 3)
    g = g.reshape(b, nblk * rows * per, kv_heads, 2, dh_s)
    k, v = g[:, :, :, 0], g[:, :, :, 1]
    if scales is None:
        return k, None, v, None
    bs = rows * per
    per_row = scales.shape[-1] // (2 * kv_heads * bs)
    gs = scales[layer, page_table // per_row].reshape(
        b, nblk, kv_heads, 2, per_row, bs)
    sub = (page_table % per_row)[:, :, None, None, None, None]
    gs = jnp.take_along_axis(gs, sub, axis=4)[:, :, :, :, 0]
    gs = jnp.moveaxis(gs, -1, 2).reshape(b, nblk * bs, kv_heads, 2)
    return k, gs[..., 0:1], v, gs[..., 1:2]


def laid_out_bytes(shape, dtype) -> int:
    """Bytes an array of ``shape`` takes on the TPU in row-major order: the
    minor dim pads to 128 lanes, the second-minor to the tile height XLA
    picks for it (at most 8 rows, at least the rows one 32-bit word packs)."""
    itemsize = jnp.dtype(dtype).itemsize
    *lead, rows, lanes = shape
    height = max(max(1, 4 // itemsize), min(8, 1 << (rows - 1).bit_length()))
    return (math.prod(lead) * -(-rows // height) * height
            * -(-lanes // 128) * 128 * itemsize)


def pool_bytes(num_blocks: int, block_size: int, kv_heads: int, dh: int,
               kv_bits: int, dtype) -> int:
    """Laid-out bytes of one layer's pool of ``num_blocks`` blocks."""
    return sum(laid_out_bytes(s.shape, s.dtype) for s in leaf_specs(
        num_blocks, block_size, kv_heads, dh, kv_bits, dtype).values())


def formats(pool, shardings):
    """Pinned row-major :class:`jax.experimental.layout.Format` per leaf of
    ``pool`` (arrays or shapes) under ``shardings`` (one sharding for every
    leaf, or a matching tree)."""
    from jax.experimental.layout import Format, Layout
    if not isinstance(shardings, dict):
        shardings = jax.tree_util.tree_map(lambda _: shardings, pool)
    return jax.tree_util.tree_map(
        lambda leaf, sh: Format(Layout(tuple(range(len(leaf.shape)))), sh),
        pool, shardings)
