"""Jitted public wrappers around the pqtopk Pallas kernels.

Handles padding (item tiles, batch tiles, the pruned route's sentinel
tile), interpret-mode selection (CPU containers run the kernel body in
Python), and the final cross-tile top-k merge.

``pq_topk_tiles`` is the pass-2 entry of the cascaded pruned route: it
scores only the tiles named by a compacted ``tile_idx`` list.  On TPU it
runs the scalar-prefetch Pallas kernel; off TPU it lowers to an XLA
gather + ``pq_scores`` + ``tiled_topk`` pipeline with identical numerics
(shared ``tree_sum`` accumulation order, same value-then-lowest-id tie
break), so CPU hosts get real compute savings instead of timing the
Pallas interpreter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import compat
from repro.core import topk as topk_lib
from repro.kernels.pqtopk import kernel as _k, ref as _ref

# A plain Python float, NOT a jnp scalar: this module is imported lazily
# (sometimes inside an active jit trace), and materialising a module-level
# jnp constant under a trace leaks a tracer.
NEG_INF = float("-inf")


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def effective_batch_tile(bq: int,
                         batch_tile: int = _k.DEFAULT_BATCH_TILE) -> int:
    """The batch-tile size the fused kernel will actually run for a batch
    of ``bq`` queries (small batches round up to 8, never past the
    default).  The grouped cascade builds its per-batch-tile slot table
    against this, so the compaction and the kernel grid must agree."""
    return min(batch_tile, _round_up(bq, 8))


def group_batch_tile(bq: int, n_groups: int,
                     batch_tile: int = _k.DEFAULT_BATCH_TILE) -> int:
    """Batch-tile size for the grouped route: small enough that the batch
    splits into ~``n_groups`` kernel batch tiles (each group gets its own
    slot row), floored at 8 rows (sublane minimum) and capped at the
    exhaustive-route tile.  Grouping trades per-step MXU batch width for
    scored-tile sparsity — the win condition is group survivor sets being
    (near-)disjoint, which is exactly the mixed-batch regime."""
    target = -(-bq // max(n_groups, 1))
    bt = 8
    while bt < target:
        bt *= 2
    return min(bt, effective_batch_tile(bq, batch_tile))


def n_tiles(n: int, tile: int) -> int:
    """Number of item tiles covering an N-item catalogue."""
    return -(-n // tile)


def sentinel_tile(n: int, tile: int) -> int:
    """Tile index used to pad a compacted survivor list: one all-padding
    tile appended past the catalogue, whose every global id is >= n and is
    therefore masked to -inf inside the kernel."""
    return n_tiles(n, tile)


def _pad_codes(codes: jax.Array, tile: int, *, sentinel: bool = False
               ) -> jax.Array:
    n = codes.shape[0]
    pad = (-n) % tile + (tile if sentinel else 0)
    if pad:
        codes = jnp.pad(codes, ((0, pad), (0, 0)))
    return codes


def _pad_batch(s: jax.Array, batch_tile: int) -> jax.Array:
    pad = (-s.shape[0]) % batch_tile
    if pad:
        s = jnp.pad(s, ((0, pad), (0, 0), (0, 0)))
    return s


def _merge_slot_winners(tv: jax.Array, ti: jax.Array, k: int, bq: int):
    """Slot-major (n_slots, B_pad, K) per-slot winners -> global (bq, k).
    Each row's candidates are laid out slot by slot in ascending tile
    order, so the stable ``lax.top_k`` over them breaks ties by lowest
    global id, matching the oracle."""
    slots, _, kk = tv.shape
    tv = jnp.transpose(tv[:, :bq], (1, 0, 2)).reshape(bq, slots * kk)
    ti = jnp.transpose(ti[:, :bq], (1, 0, 2)).reshape(bq, slots * kk)
    fv, fi = jax.lax.top_k(tv, k)
    return fv, jnp.take_along_axis(ti, fi, axis=1)


def _remap_dead(fv: jax.Array, fi: jax.Array, n: int):
    """Tombstone-route winner cleanup: any ``-inf`` winner (a dead item, a
    sentinel slot, or a catalogue with < k live items) gets the sentinel id
    ``n`` — callers see one uniform "no item here" id, never a dead row."""
    return fv, jnp.where(fv == NEG_INF, jnp.int32(n), fi)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def pq_scores(codes: jax.Array, s: jax.Array, *, tile: int = _k.DEFAULT_TILE,
              interpret: bool | None = None) -> jax.Array:
    """PQ scores for all items. codes (N,m), s (B,m,b) -> (B,N) f32."""
    if interpret is None:
        interpret = not compat.on_tpu()
    n = codes.shape[0]
    tile = min(tile, _round_up(n, 128))
    padded = _pad_codes(codes, tile)
    out = _k.pq_scores_call(padded, s, tile=tile, interpret=interpret)
    return out[:, :n]


@functools.partial(jax.jit, static_argnames=("k", "tile", "batch_tile",
                                             "interpret"))
def pq_topk(codes: jax.Array, s: jax.Array, k: int, *,
            tile: int = _k.DEFAULT_TILE,
            batch_tile: int = _k.DEFAULT_BATCH_TILE,
            interpret: bool | None = None):
    """Fused PQ scoring + hierarchical top-k over the whole catalogue.
    Exact (tile-local winners contain all global winners when k <= tile).
    Batch-tiled: any B; the grid covers ceil(B/batch_tile) batch tiles.
    -> (vals (B,k), ids (B,k))."""
    if interpret is None:
        interpret = not compat.on_tpu()
    n = codes.shape[0]
    bq = s.shape[0]
    tile = min(tile, _round_up(n, 128))
    if k > tile:
        raise ValueError(f"k={k} > tile={tile}")
    bt = effective_batch_tile(bq, batch_tile)
    # Named scopes tag each part's device ops in the HLO's op_name
    # metadata.  The TPU compiler names the kernel's custom call after the
    # innermost scope around it, so that scope is ``pq_topk``: the call
    # stays ``%pq_topk.<n>``, the name a device trace finds the kernel by.
    with jax.named_scope("head.codes_pad"):
        padded = _pad_codes(codes, tile)
        s = _pad_batch(s, bt)
    with jax.named_scope("head.kernel"), jax.named_scope("pq_topk"):
        idx = jnp.arange(padded.shape[0] // tile, dtype=jnp.int32)
        tv, ti = _k.pq_topk_fused_call(padded, s, k, tile_idx=idx,
                                       n_items=n, tile=tile, batch_tile=bt,
                                       interpret=interpret)
    with jax.named_scope("head.merge"):
        return _merge_slot_winners(tv, ti, k, bq)


def _pq_topk_tiles(codes: jax.Array, s: jax.Array, k: int,
                   tile_idx: jax.Array, *, tile: int, batch_tile: int,
                   use_kernel: bool, interpret: bool, live=None):
    """Non-jitted core of :func:`pq_topk_tiles` (shard_map bodies call this
    directly so the jit boundary stays at the outer dispatch).

    ``tile_idx`` may be 1D (one compacted list for the whole batch) or 2D
    ``(n_batch_tiles, n_slots)`` (the grouped route: each kernel batch
    tile scores its own slot row).

    ``live`` (N,) bool tombstone mask (mutable catalogues): dead items'
    scores are masked to -inf *inside* the tile top-k — post-hoc masking
    would be inexact, a dead item can crowd a live winner out of a tile's
    local candidate set — and dead winners' ids are remapped to the
    sentinel id ``n`` so they are indistinguishable from padding."""
    n, m = codes.shape
    bq = s.shape[0]
    tile = min(tile, _round_up(n, 128))
    if k > tile:
        raise ValueError(f"k={k} > tile={tile}")
    padded = _pad_codes(codes, tile, sentinel=True)
    live2 = None
    if live is not None:
        lv = live.astype(jnp.int8)
        pad = padded.shape[0] - n
        if pad:
            lv = jnp.pad(lv, (0, pad))      # padding + sentinel tile: dead
        live2 = lv.reshape(-1, 1, tile)
    bt = effective_batch_tile(bq, batch_tile)
    grouped = tile_idx.ndim == 2
    if grouped and tile_idx.shape[0] * bt < bq:
        raise ValueError(
            f"2D tile_idx has {tile_idx.shape[0]} batch-tile rows but the "
            f"batch pads to {-(-bq // bt)} tiles of {bt}")
    if use_kernel:
        tv, ti = _k.pq_topk_fused_call(padded, _pad_batch(s, bt), k,
                                       tile_idx=tile_idx, n_items=n,
                                       tile=tile, batch_tile=bt,
                                       live=live2, interpret=interpret)
        fv, fi = _merge_slot_winners(tv, ti, k, bq)
        if live is not None:
            fv, fi = _remap_dead(fv, fi, n)
        return fv, fi
    # XLA path: gather the surviving tiles' codes, score them with the
    # shared-accumulation-order oracle, top-k over the compacted axis and
    # map positions back to global ids.  tile_idx is ascending (plus
    # trailing sentinels), so position order == global id order and ties
    # resolve identically to the exhaustive oracle.  ``-1`` sentinel slots
    # (the in-graph cascade's compaction padding) are remapped to the
    # all-padding tile appended past the catalogue, whose global ids are
    # >= n and therefore mask to -inf below.
    tile_idx = jnp.where(tile_idx < 0, sentinel_tile(n, tile), tile_idx)
    codes3 = padded.reshape(-1, tile, m)
    if grouped:
        # Per-group gather + scoring: each batch tile's queries score only
        # that group's slot row — the XLA mirror of the kernel's 2D grid,
        # with the same per-row ascending order (hence identical ties).
        n_slots = tile_idx.shape[1]
        s3 = _pad_batch(s, bt).reshape(-1, bt, m, s.shape[-1])

        def group_fn(idx_row, s_g):
            sel = codes3[idx_row]                       # (S, tile, m)
            sc = _ref.pq_scores(sel.reshape(n_slots * tile, m), s_g)
            gid = (idx_row[:, None] * tile
                   + jnp.arange(tile, dtype=jnp.int32)[None, :]).reshape(-1)
            ok = gid < n
            if live2 is not None:
                ok = ok & (live2[idx_row].reshape(-1) != 0)
            sc = jnp.where(ok[None, :], sc, NEG_INF)
            fv, pos = topk_lib.tiled_topk(sc, k)
            return fv, jnp.take(gid, pos)

        fv, fi = jax.vmap(group_fn)(tile_idx, s3)       # (n_bt, bt, k)
        fv, fi = fv.reshape(-1, k)[:bq], fi.reshape(-1, k)[:bq]
        if live is not None:
            fv, fi = _remap_dead(fv, fi, n)
        return fv, fi
    n_slots = tile_idx.shape[0]
    sel = codes3[tile_idx]                              # (L, tile, m)
    scores = _ref.pq_scores(sel.reshape(n_slots * tile, m), s)
    gid = (tile_idx[:, None] * tile
           + jnp.arange(tile, dtype=jnp.int32)[None, :]).reshape(-1)
    ok = gid < n
    if live2 is not None:
        ok = ok & (live2[tile_idx].reshape(-1) != 0)
    scores = jnp.where(ok[None, :], scores, NEG_INF)
    fv, pos = topk_lib.tiled_topk(scores, k)
    fv, fi = fv, jnp.take(gid, pos)
    if live is not None:
        fv, fi = _remap_dead(fv, fi, n)
    return fv, fi


def _pq_topk_tiles_ladder(codes: jax.Array, s: jax.Array, k: int,
                          slot_lists, count: jax.Array, *, tile: int,
                          batch_tile: int, use_kernel: bool,
                          interpret: bool, live=None):
    """Non-jitted ladder core (shard_map bodies call this directly).

    ``slot_lists`` is a tuple of ``-1``-padded compacted tile buffers of
    strictly increasing static length, the last one full-length
    (exhaustive).  Lowers to a nested ``lax.cond`` chain: the first rung
    whose slot count holds ``count`` scores its buffer; every branch lives
    in the same traced computation, so the dispatch count never changes.
    For the grouped route the buffers are 2D ``(n_batch_tiles, budget)``
    rows and ``count`` is the per-group survivor-count vector — a rung is
    taken when it holds the LARGEST group (one shared ladder; lighter
    groups' spare slots are ``-1`` sentinels and cost ~nothing).
    -> (vals (B, k), ids (B, k), rung i32 — index of the rung taken).
    """
    count_max = jnp.max(count)

    def rung_fn(i):
        def run():
            v, ii = _pq_topk_tiles(codes, s, k, slot_lists[i], tile=tile,
                                   batch_tile=batch_tile,
                                   use_kernel=use_kernel,
                                   interpret=interpret, live=live)
            return v, ii, jnp.int32(i)
        if i == len(slot_lists) - 1:
            return run
        nxt = rung_fn(i + 1)
        budget = slot_lists[i].shape[-1]
        return lambda: jax.lax.cond(count_max <= budget, run, nxt)

    return rung_fn(0)()


def pq_topk_tiles_ladder(codes: jax.Array, s: jax.Array, k: int,
                         slot_lists, count: jax.Array, *, tile: int,
                         batch_tile: int = _k.DEFAULT_BATCH_TILE,
                         live: jax.Array | None = None,
                         use_kernel: bool | None = None,
                         interpret: bool | None = None):
    """Slot-budget-ladder scoring over compacted tile buffers (the
    cascade's scoring stage when a calibrated ladder is active).  See
    :func:`_pq_topk_tiles_ladder`; this wrapper only resolves the
    backend-dependent kernel/interpret defaults — jit the caller (the
    cascade is itself one traced computation)."""
    if use_kernel is None:
        use_kernel = compat.on_tpu()
    if interpret is None:
        interpret = not compat.on_tpu()
    return _pq_topk_tiles_ladder(
        codes, s, k, tuple(jnp.asarray(sl, jnp.int32) for sl in slot_lists),
        count, tile=tile, batch_tile=batch_tile, use_kernel=use_kernel,
        interpret=interpret, live=live)


@functools.partial(jax.jit, static_argnames=("k", "tile", "batch_tile",
                                             "use_kernel", "interpret"))
def pq_topk_tiles(codes: jax.Array, s: jax.Array, k: int,
                  tile_idx: jax.Array, *, tile: int = _k.DEFAULT_TILE,
                  batch_tile: int = _k.DEFAULT_BATCH_TILE,
                  live: jax.Array | None = None,
                  use_kernel: bool | None = None,
                  interpret: bool | None = None):
    """Fused scoring + top-k over a compacted tile list (the cascade's
    scoring stage — fed by host compaction in the legacy route, by the
    in-graph cumsum scatter in the single-dispatch route).

    codes (N, m) raw catalogue codes; tile_idx (n_slots,) int32 ascending
    tile indices, padded with either ``-1`` sentinel slots (in-graph
    compaction; ``@pl.when`` early-exit in the kernel) or legacy
    ``sentinel_tile(N, tile)`` entries.  Work is O(n_slots * tile * m)
    instead of O(N * m).  -> (vals (B,k), ids (B,k)), bit-identical to the
    exhaustive routes for surviving items.
    """
    if use_kernel is None:
        use_kernel = compat.on_tpu()
    if interpret is None:
        interpret = not compat.on_tpu()
    return _pq_topk_tiles(codes, s, k, tile_idx.astype(jnp.int32),
                          tile=tile, batch_tile=batch_tile,
                          use_kernel=use_kernel, interpret=interpret,
                          live=live)
