"""Pallas TPU kernels for PQTopK scoring (Algorithm 1, TPU-native form).

Two kernels:

* ``pq_scores_kernel``     — scores only: for a tile of TN items, expand each
  split's codes to one-hot via iota comparison (in VMEM, never in HBM) and
  accumulate ``S_k @ onehot_k^T`` on the MXU.  HBM traffic: 1 byte/item/split
  for int8/uint8 codes (4 for int32) vs 2*d bytes/item for dense scoring.

* ``pq_topk_fused_kernel`` — additionally reduces each (batch tile × item
  tile) block to its local top-K so only (n_slots, B, K) candidates ever
  reach HBM; the final merge over tile winners happens outside.

  1. **Batch tiling** — grid is (tile slot, batch tile), so B is unbounded:
     each step sees a (TB, m, b) slice of S instead of the whole batch.
  2. **In-VMEM top-k under a carried threshold** — max-extract passes
     over the (TB, TN) score tile (``_tile_topk``), built only from
     operations Mosaic lowers, with the value-then-lowest-index order of
     ``lax.top_k``.  Each row's running top-K order keys from the tiles
     already scored stay in a (B, K) int32 VMEM scratch, carried across
     the slot axis (both grid axes are ``"arbitrary"``: one core visits a
     batch tile's slots in order); theta is the row's lowest key.  A tile
     masks every score whose key is <= theta, counts the survivors per
     row, and runs ``min(K, max over rows of the count)`` passes, so once
     a few tiles are seen most tiles run none or one.  Exact, ties
     included: slots visit tiles in ascending order on every route
     (identity table, compacted lists, each row of the grouped table), so
     the K items behind theta all have lower global ids than any item of
     the tile, and a score <= theta ranks after all K of them.  The
     survivors come out in the order they always did; lanes past a row's
     survivors read -inf with the sentinel id ``n_items``, like sentinel
     slots.  The scratch is 4*B*K bytes, at most :data:`MAX_RUN_KEYS`
     keys (2 MiB); a larger batch runs as several calls.
  3. **Compacted tile indices** — the item-tile axis is indirected through a
     scalar-prefetched index array (``PrefetchScalarGridSpec``), so the
     pruned retrieval route can run the same kernel over only the tiles
     that survive the upper-bound cascade: codes HBM traffic drops from
     O(N*m) to O(N_survive*m).  The exhaustive route passes the identity
     map.  Slots mapping to the sentinel tile (fully past ``n_items``)
     emit -inf candidates and never reach the final top-k.

     The index array may also be **2D** ``(n_batch_tiles, n_slots)`` (the
     per-query grouped cascade, PR 5): each kernel batch tile then walks
     its OWN compacted slot row — slot i of batch tile j scores codes tile
     ``tile_idx[j, i]`` — so a mixed batch whose query groups survive
     disjoint catalogue regions does ``sum_g B_g * S_g`` work instead of
     ``B * |union|``.  The grid flips to (n_batch_tiles, n_slots), slots
     innermost, so each group's S block stays resident in VMEM while its
     slot row streams codes tiles; ``-1`` sentinels keep the same
     early-exit + clamp-to-block-0 contract per row.

Block layout (1D: grid = (n_slots, n_batch_tiles), batch innermost so each
codes tile is fetched once; 2D: grid = (n_batch_tiles, n_slots), slots
innermost so each group's S block is fetched once):
  tile_idx (n_slots,) i32     -> scalar prefetch (SMEM)
           or (n_batch_tiles, n_slots) i32
  codes (N, m) i8/u8/u16/i32  -> block (TN, m)          @ row tile_idx[...]
  s     (B, m, b) f32         -> block (TB, m, b)       @ batch tile j
  live  (N/TN, 1, TN) i8      -> block (None, 1, TN)    @ row tile_idx[...]
  out_v (n_slots, B, K) f32   -> block (None, TB, K)    @ (i, j)
  out_i (n_slots, B, K) i32   -> block (None, TB, K)    @ (i, j)

VMEM working set per step (TN=2048, b=256, TB=128, f32):
  onehot 2048*256*4 = 2 MiB, scores TB*TN*4 = 1 MiB, S m*b*TB*4 <= 1 MiB.
MXU shapes: (TB, b) @ (b, TN) — b=256 and TN multiples of 128 line up with
the 128x128 systolic array.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import compat
from repro.core.scoring import tree_sum_lazy

DEFAULT_TILE = 2048
DEFAULT_BATCH_TILE = 128
NEG_INF = float("-inf")
#: The order key below every score's (``_order_key(-inf)`` is above it):
#: a running top-K slot that holds no item yet, or a masked score.
NO_KEY = -2 ** 31
#: Most (B_pad x K) running keys the carried threshold may hold: 2 MiB of
#: int32 VMEM scratch.  With the tile working set (~12.2 MiB at TB=128,
#: b=512) it stays inside the 16 MiB of scoped VMEM a v5e kernel gets by
#: default (B=4096 at K=128 compiles; 4 MiB of keys does not).
MAX_RUN_KEYS = 2 ** 19


def _bf16_split(x):
    """f32 -> (hi, mid, lo) bf16 with ``hi + mid + lo == x`` exactly: each
    piece holds the next 8 significant bits of f32's 24, and each
    remainder is exact in f32."""
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _tile_scores(codes_ref, s_ref):
    """Shared body: one-hot MXU scoring of one item tile. -> (TB, TN) f32.

    ``codes_ref`` may be int8/uint8 (b <= 128 / 256), uint16 or int32; the
    widen to int32 happens in VMEM, so narrow dtypes cut HBM code traffic.

    Exact in f32: the one-hot matrix is exact in bf16, and S goes through
    the MXU as three bf16 pieces (:func:`_bf16_split`), so each product
    selects one piece exactly and ``(hi + mid) + lo`` rebuilds the f32
    entry of S bit for bit.  A single f32 matmul at the default precision
    rounds S to bf16 (measured on a v5e: scores off by up to 2.6e-2);
    ``Precision.HIGHEST`` takes six passes and was still off by an ulp.
    """
    codes = codes_ref[...].astype(jnp.int32)          # (TN, m)
    s = s_ref[...].astype(jnp.float32)                # (TB, m, b)
    tn, m = codes.shape
    b = s.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (tn, b), 1)

    def split_scores(k):                              # -> (TB, TN)
        onehot = (codes[:, k:k + 1] == iota).astype(jnp.bfloat16)  # (TN, b)
        hi, mid, lo = (jax.lax.dot_general(
            p, onehot, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
            for p in _bf16_split(s[:, k, :]))
        return (hi + mid) + lo

    # Each split's scores are exact, so the only rounding happens in the
    # cross-split reduction — tree_sum's order keeps it bit-identical to
    # score_pqtopk / the jnp oracle; the lazy form keeps few (TB, TN)
    # partial sums in VMEM at once.
    return tree_sum_lazy(split_scores, m)


def pq_scores_kernel(codes_ref, s_ref, out_ref):
    out_ref[...] = _tile_scores(codes_ref, s_ref)


def _order_key(x):
    """f32 -> i32 key with the total order ``lax.top_k`` sorts by (-0.0
    below +0.0).  The map is its own inverse on the int32 bits."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _tile_topk(scores, k: int, run):
    """Exact top-k of one VMEM-resident (TB, TN) score tile that can still
    enter each row's running top-k, in (value descending, column
    ascending) order — what ``lax.top_k`` returns, ties included.

    ``run`` (TB, K) int32 holds each row's running top-K order keys from
    the tiles already scored (unsorted; :data:`NO_KEY` where none yet);
    theta is its row minimum.  A score whose key is <= theta is masked:
    the K items behind theta all sit in earlier tiles, at lower global
    ids, so such a score ranks after all K of them and cannot be in the
    answer.  The loop runs ``min(K, max over rows of the survivors)``
    passes, a trip count read from the tile itself.  Pass j keeps only
    what ranks after pass j-1's pick (a lower key, or the same key at a
    higher column), takes the row max, and the lowest column holding it;
    a pick above the row's running minimum replaces it (the lowest lane
    holding it).  Lanes past a row's survivors are not found: they read
    ``-inf``, with ``found`` false and no column.  So the survivors are a prefix
    of the tile's own order, and with ``run`` all :data:`NO_KEY` the
    result is the tile's exact top-k (``-inf`` padding included, in
    ascending column order like real ties).  Built from compares, selects
    and lane max/min/sum reductions: operations Mosaic lowers
    (``lax.top_k``, ``take_along_axis`` and 3-D lane reshapes are not).
    Scores are never NaN.  Requires k <= TN.
    -> (vals (TB, K) f32, cols (TB, K) i32, found (TB, K) bool, run).
    """
    tb, tn = scores.shape
    theta = jnp.min(run, axis=1, keepdims=True)
    key = _order_key(scores)
    key = jnp.where(key > theta, key, NO_KEY)
    alive = jnp.sum((key > NO_KEY).astype(jnp.int32), axis=1, keepdims=True)
    n_pass = jnp.minimum(jnp.max(alive), k)
    col = jax.lax.broadcasted_iota(jnp.int32, (tb, tn), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tb, k), 1)

    def body(j, carry):
        keys, cols, run, prev_k, prev_c = carry
        after = (key < prev_k) | ((key == prev_k) & (col > prev_c))
        kj = jnp.max(jnp.where(after, key, NO_KEY), axis=1, keepdims=True)
        cj = jnp.min(jnp.where(after & (key == kj), col, tn), axis=1,
                     keepdims=True)
        low = jnp.min(run, axis=1, keepdims=True)
        at = jnp.min(jnp.where(run == low, lane, k), axis=1, keepdims=True)
        run = jnp.where((lane == at) & (kj > low), kj, run)
        return (jnp.where(lane == j, kj, keys), jnp.where(lane == j, cj, cols),
                run, kj, cj)

    init = (jnp.full((tb, k), NO_KEY, jnp.int32),
            jnp.zeros((tb, k), jnp.int32), run,
            jnp.full((tb, 1), jnp.iinfo(jnp.int32).max, jnp.int32),
            jnp.full((tb, 1), -1, jnp.int32))
    keys, cols, run, _, _ = jax.lax.fori_loop(0, n_pass, body, init)
    found = keys > NO_KEY
    vals = jnp.where(found, jax.lax.bitcast_convert_type(_order_key(keys),
                                                         jnp.float32),
                     NEG_INF)
    return vals, cols, found, run


def pq_topk_fused_kernel(idx_ref, codes_ref, s_ref, *rest,
                         k: int, tile: int, n_items: int,
                         has_live: bool = False):
    if has_live:
        # Tombstone route (mutable catalogues): a (1, TN) int8 live row
        # rides along each codes tile under the SAME clamped index map, so
        # delisted items are masked to -inf inside the tile top-k — before
        # they can crowd a live winner out of the per-tile candidate set.
        live_ref, out_v_ref, out_i_ref, run_ref = rest
    else:
        out_v_ref, out_i_ref, run_ref = rest
    if len(idx_ref.shape) == 2:
        # Grouped route: grid (n_batch_tiles, n_slots) — batch tile j's
        # slot i reads its own row of the 2D (group, slot) table.
        bt, slot = pl.program_id(0), pl.program_id(1)
        tile_id = idx_ref[bt, slot]
    else:
        slot, bt = pl.program_id(0), pl.program_id(1)
        tile_id = idx_ref[slot]
    tb = out_v_ref.shape[0]
    rows = pl.ds(pl.multiple_of(bt * tb, tb), tb)

    # A batch tile's running top-K keys start empty at its first slot and
    # are carried through its slots in order (the grid is sequential).
    @pl.when(slot == 0)
    def _start():
        run_ref[rows, :] = jnp.full((tb, k), NO_KEY, jnp.int32)

    # Sentinel slots (tile_id == -1): the in-graph pruned route's slot-
    # buffer padding.  Early-exit — no scoring, no top-k; and because the
    # sentinels sit contiguously at the buffer tail and their BlockSpec
    # index map pins them all to codes block 0 (see the clamp in
    # pq_topk_fused_call), the codes DMA is issued at most once for the
    # whole sentinel run.  The grid stays static; skipped slots cost ~no
    # DMA or compute.
    @pl.when(tile_id < 0)
    def _sentinel():
        out_v_ref[...] = jnp.full(out_v_ref.shape, NEG_INF, jnp.float32)
        out_i_ref[...] = jnp.full(out_i_ref.shape, n_items, jnp.int32)

    @pl.when(tile_id >= 0)
    def _score():
        scores = _tile_scores(codes_ref, s_ref)       # (TB, TN)
        tn = scores.shape[1]
        col = jax.lax.broadcasted_iota(jnp.int32, (tb, tn), 1)
        # Mask padding beyond the true catalogue size; legacy past-catalogue
        # sentinel tiles land entirely here.
        global_col = col + tile_id * tile
        ok = global_col < n_items
        if has_live:
            ok = ok & (live_ref[...] != 0)            # (1, TN) broadcast
        scores = jnp.where(ok, scores, NEG_INF)
        vals, cols, found, run = _tile_topk(scores, k, run_ref[rows, :])
        run_ref[rows, :] = run
        out_v_ref[...] = vals
        out_i_ref[...] = jnp.where(found, cols + tile_id * tile, n_items)


def pq_scores_call(codes: jax.Array, s: jax.Array, *, tile: int = DEFAULT_TILE,
                   interpret: bool = False) -> jax.Array:
    """codes (N, m) int, s (B, m, b) f32 -> scores (B, N) f32. N % tile == 0."""
    n, m = codes.shape
    bq, m2, b = s.shape
    assert m == m2, (m, m2)
    assert n % tile == 0, (n, tile)
    grid = (n // tile,)
    return pl.pallas_call(
        pq_scores_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, m), lambda i: (i, 0)),
            pl.BlockSpec((bq, m, b), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bq, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((bq, n), jnp.float32),
        interpret=interpret,
    )(codes, s)


def pq_topk_fused_call(codes: jax.Array, s: jax.Array, k: int, *,
                       tile_idx: jax.Array, n_items: int,
                       tile: int = DEFAULT_TILE,
                       batch_tile: int = DEFAULT_BATCH_TILE,
                       live: jax.Array = None,
                       interpret: bool = False):
    """-> (vals (n_slots, B, K), ids (n_slots, B, K)) per-slot winners with
    *global* item ids, slot-major; merge outside.  Slot-major because a
    TPU block's last two dims must be (8, 128)-divisible or whole: the
    (TB, K) block of one slot is, a (TB, 1, K) block of a (B, n_slots, K)
    array is not.

    ``tile_idx`` (n_slots,) int32 selects which codes tile each grid slot
    scores (identity for the exhaustive route, a compacted survivor list for
    the pruned route).  A 2D ``(B/batch_tile, n_slots)`` table gives every
    batch tile its own slot row (the per-query grouped route); the grid
    then iterates slots innermost so each group's S block is fetched once.
    ``-1`` entries are sentinel slots: their grid step early-exits via
    ``@pl.when`` and the index map clamps their codes block to 0 so the
    pipeline re-uses one already-fetched block instead of issuing per-slot
    DMAs.  ``codes`` rows must cover every indexed tile; ``s``'s batch
    must divide by ``batch_tile``.

    ``live`` (N/tile, 1, tile) int8 is the optional tombstone mask, row t
    the liveness of codes tile t (0 = delisted / padding).  It streams
    through VMEM as a whole (1, tile) block under the SAME clamped index
    map as the
    codes tile, so each slot masks ITS tile's dead items to -inf inside
    the tile top-k; the sentinel-clamp contract (``-1`` -> block 0) is
    unchanged.  Extra HBM traffic: 1 byte/item — noise next to the m
    bytes/item of codes.

    Each row's running top-K keys live in a (B, K) int32 VMEM scratch, so
    a batch of more than :data:`MAX_RUN_KEYS` / K rows runs as several
    calls of at most that many rows each.
    """
    n, m = codes.shape
    bq, m2, b = s.shape
    assert m == m2 and n % tile == 0
    assert bq % batch_tile == 0, (bq, batch_tile)
    assert k <= tile, (k, tile)
    rows = max(MAX_RUN_KEYS // k // batch_tile, 1) * batch_tile
    if bq > rows:
        parts = [pq_topk_fused_call(
            codes, s[a:a + rows], k, n_items=n_items, tile=tile,
            batch_tile=batch_tile, live=live, interpret=interpret,
            tile_idx=(tile_idx[a // batch_tile:(a + rows) // batch_tile]
                      if tile_idx.ndim == 2 else tile_idx))
            for a in range(0, bq, rows)]
        return [jnp.concatenate(p, axis=1) for p in zip(*parts)]
    n_bt = bq // batch_tile
    if live is not None:
        assert live.shape == (n // tile, 1, tile), (live.shape, n, tile)
    kern = functools.partial(pq_topk_fused_kernel, k=k, tile=tile,
                             n_items=n_items, has_live=live is not None)
    # The 1D and 2D layouts share every block shape; they differ only in
    # grid order (1D: batch innermost so each codes tile is fetched once;
    # 2D: slots innermost so each group's S block is fetched once) and in
    # how a grid step finds its codes tile.  `slot`/`bt` map a grid step
    # to its (slot, batch-tile) coordinates under either order.
    if tile_idx.ndim == 2:
        assert tile_idx.shape[0] == n_bt, (tile_idx.shape, n_bt)
        n_slots = tile_idx.shape[1]
        grid = (n_bt, n_slots)
        slot, bt = (lambda j, i: i), (lambda j, i: j)
        codes_block = lambda j, i, idx_ref: jnp.maximum(idx_ref[j, i], 0)
    else:
        n_slots = tile_idx.shape[0]
        grid = (n_slots, n_bt)
        slot, bt = (lambda i, j: i), (lambda i, j: j)
        codes_block = lambda i, j, idx_ref: jnp.maximum(idx_ref[i], 0)
    out_spec = pl.BlockSpec(
        (None, batch_tile, k), lambda a, c, idx_ref: (slot(a, c), bt(a, c), 0))
    in_specs = [
        pl.BlockSpec((tile, m),
                     lambda a, c, idx_ref: (codes_block(a, c, idx_ref),
                                            0)),
        pl.BlockSpec((batch_tile, m, b),
                     lambda a, c, idx_ref: (bt(a, c), 0, 0)),
    ]
    operands = [codes, s]
    if live is not None:
        # Same clamped tile index map as codes: sentinel slots re-read an
        # already-fetched live row exactly like they re-read codes block 0.
        in_specs.append(pl.BlockSpec(
            (None, 1, tile),
            lambda a, c, idx_ref: (codes_block(a, c, idx_ref), 0, 0)))
        operands.append(live)
    grid_spec = compat.prefetch_scalar_grid_spec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
        scratch_shapes=[compat.VMEM((bq, k), jnp.int32)],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_slots, bq, k), jnp.float32),
            jax.ShapeDtypeStruct((n_slots, bq, k), jnp.int32),
        ],
        # Sequential on one core: the running keys carry across slots.
        compiler_params=compat.tpu_params(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(tile_idx.astype(jnp.int32), *operands)
