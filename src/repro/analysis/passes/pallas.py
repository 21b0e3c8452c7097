"""kernel-contract: static checks on every ``pallas_call`` in the trace.

Four contracts per kernel call, all readable from the traced
``grid_mapping`` without running (or even lowering) the kernel — so CPU
CI verifies them on the interpret-mode trace, closing two caveats that
previously lived in ROADMAP's "validate on real TPU" list:

* **static grid** — ``num_dynamic_grid_bounds == 0`` and every grid dim a
  Python int: a dynamic grid recompiles per shape and defeats the AOT
  variant memoisation.
* **VMEM budget** — Σ(block shape × dtype bytes) over all input+output
  block mappings, doubled for pipelining (Pallas double-buffers blocks so
  DMA overlaps compute), must fit the configurable budget.  TPU VMEM is
  ~16 MiB/core; the default budget is half that, leaving headroom for
  scratch and compiler-managed buffers (see
  ``/opt/skills/guides``' Pallas notes).
* **tiling / divisibility** — each block's last dim must be the full
  array dim or a multiple of 128 (the lane width); the second-minor dim
  must be the full array dim or a multiple of the dtype's min sublane
  tile — 8 for 4-byte types, 16 for 2-byte, **32 for int8/uint8 codes**.
  A squeezed (``None``) dim counts as 1, as the TPU lowering counts it,
  so a ``(TB, 1, K)`` or ``(TB, None, K)`` block on a ``(B, S, K)`` array
  fails here exactly as it fails the chip's compiler.  Rank-1 blocks must
  be the full array or a multiple of 128 x (4 / itemsize).  The TPU
  lowering itself asks only 8 sublanes of every dtype; the 16/32 rows
  for narrow dtypes are this pass's stricter rule, the native packed
  tile, below which Mosaic relayouts (pads) the block.
* **sentinel clamp** — the compacted-tile kernels drive the codes block
  index from a scalar-prefetched slot table padded with ``-1`` sentinels;
  the contract is that index maps clamp ``-1`` to block 0 (the kernel
  body then early-exits via ``@pl.when``, and block 0 is always resident
  so the clamped index costs no extra DMA).  The pass *evaluates* each
  index map twice — scalar tables filled with ``-1`` vs ``0`` — after
  discharging the scalar refs to values; the results must be equal and
  in-bounds at every sampled grid point.  An unclamped
  ``idx_ref[i]`` map returns block ``-1`` on the sentinel fill and fails.
"""
from __future__ import annotations

import itertools
from typing import Any, Dict, List, Optional, Tuple

from repro.analysis.core import (AnalysisPass, EntryContext, Finding,
                                 SEV_ERROR, iter_eqns)

LANE = 128
#: dtype itemsize (bytes) -> minimum sublane (second-minor) tile
SUBLANE_MIN = {1: 32, 2: 16, 4: 8, 8: 8}

DEFAULT_VMEM_BUDGET = 8 * 1024 * 1024
#: cap on exhaustively evaluated grid points per index map (past this,
#: sample the corners + a leading slice)
MAX_GRID_POINTS = 64


def _block_dims(bm) -> Tuple[int, ...]:
    """The block's size in every array dim; a squeezed dim is 1."""
    return tuple(d if isinstance(d, int) else getattr(d, "block_size", 1)
                 for d in bm.block_shape)


def _block_nbytes(bm) -> int:
    n = 1
    for d in _block_dims(bm):
        n *= d
    return n * bm.array_aval.dtype.itemsize


def _grid_points(grid) -> List[Tuple[int, ...]]:
    total = 1
    for g in grid:
        total *= int(g)
    pts = itertools.product(*(range(int(g)) for g in grid))
    if total <= MAX_GRID_POINTS:
        return list(pts)
    corners = list(itertools.product(*((0, int(g) - 1) for g in grid)))
    return list(dict.fromkeys(corners + list(itertools.islice(
        pts, MAX_GRID_POINTS - len(corners)))))


def _eval_index_map(imj, grid_pt, scalar_fill: int):
    """Evaluate a block's index-map jaxpr at one grid point with every
    scalar-prefetch table filled with ``scalar_fill``.  The scalar
    operands are SMEM refs inside the jaxpr; ``discharge_state`` converts
    the ref reads into pure ops so the jaxpr evaluates concretely."""
    import jax
    import jax.numpy as jnp
    from jax._src.state.discharge import discharge_state

    n_out = len(imj.jaxpr.outvars)
    dis_jaxpr, dis_consts = discharge_state(imj.jaxpr, imj.consts)
    args = []
    for invar in imj.jaxpr.invars:
        aval = invar.aval
        if getattr(aval, "shape", ()) == () and not hasattr(aval, "inner_aval"):
            args.append(None)          # grid index placeholder
        else:
            shape = getattr(getattr(aval, "inner_aval", aval), "shape",
                            aval.shape)
            dtype = getattr(getattr(aval, "inner_aval", aval), "dtype",
                            jnp.int32)
            args.append(jnp.full(shape, scalar_fill, dtype))
    it = iter(grid_pt)
    args = [jnp.int32(next(it)) if a is None else a for a in args]
    out = jax.core.eval_jaxpr(dis_jaxpr, dis_consts, *args)
    return tuple(int(o) for o in out[:n_out])   # discharge appends final
                                                # ref states; drop them


class PallasContractPass(AnalysisPass):
    name = "kernel-contract"
    description = ("per pallas_call: static grid, VMEM block budget, "
                   "tiling/divisibility (incl. int8 codes), and the -1 "
                   "sentinel index-map clamp")
    scope = "entrypoint"
    requires_trace = True

    def run(self, entrypoint: str, built: Any, ctx: Optional[EntryContext]
            ) -> Tuple[List[Finding], Dict[str, Any]]:
        findings: List[Finding] = []
        jaxpr = ctx.trace()
        budget = built.vmem_budget or DEFAULT_VMEM_BUDGET

        calls = [(eqn, path) for eqn, path in iter_eqns(jaxpr)
                 if eqn.primitive.name == "pallas_call"]
        info: Dict[str, Any] = {"n_pallas_calls": len(calls)}
        if built.expect_pallas and len(calls) < built.expect_pallas:
            findings.append(Finding(
                self.name, entrypoint, SEV_ERROR, "missing-kernel",
                f"expected >= {built.expect_pallas} pallas_call(s) in the "
                f"trace, found {len(calls)}: the route is not hitting the "
                f"kernel",
                details={"expected": built.expect_pallas,
                         "found": len(calls)}))

        max_vmem = 0
        for ci, (eqn, path) in enumerate(calls):
            gm = eqn.params["grid_mapping"]
            where = f"pallas_call#{ci}@{'/'.join(path) or '<top>'}"

            # -- static grid ------------------------------------------------
            dyn = getattr(gm, "num_dynamic_grid_bounds", 0)
            if dyn or not all(isinstance(g, int) for g in gm.grid):
                findings.append(Finding(
                    self.name, entrypoint, SEV_ERROR, "dynamic-grid",
                    f"{where}: grid {gm.grid!r} has "
                    f"{dyn} dynamic bound(s) — per-shape recompiles defeat "
                    f"AOT variant memoisation",
                    details={"grid": [repr(g) for g in gm.grid],
                             "num_dynamic_grid_bounds": int(dyn)}))
                continue   # block/sentinel math needs a concrete grid

            # -- VMEM budget (x2: Pallas double-buffers for pipelining) -----
            vmem = 2 * sum(_block_nbytes(bm) for bm in gm.block_mappings)
            max_vmem = max(max_vmem, vmem)
            if vmem > budget:
                findings.append(Finding(
                    self.name, entrypoint, SEV_ERROR, "vmem-budget",
                    f"{where}: estimated VMEM footprint {vmem} bytes "
                    f"(2x sum of block buffers) exceeds the "
                    f"{budget}-byte budget",
                    details={"vmem_bytes": vmem, "budget": budget,
                             "blocks": [list(_block_dims(bm))
                                        for bm in gm.block_mappings]}))

            # -- tiling / divisibility --------------------------------------
            for bi, bm in enumerate(gm.block_mappings):
                block = _block_dims(bm)
                arr = bm.array_aval.shape
                itemsize = bm.array_aval.dtype.itemsize
                if not block:
                    continue   # scalar operand: no layout
                sub_min = SUBLANE_MIN.get(itemsize, 8)
                last, arr_last = block[-1], arr[-1]
                bad = []
                if len(block) == 1:
                    lane = LANE * max(1, 4 // itemsize)
                    if last != arr_last and last % lane != 0:
                        bad.append(f"rank-1 block {last} (array "
                                   f"{arr_last}): not full and not a "
                                   f"multiple of {lane}")
                elif last != arr_last and last % LANE != 0:
                    bad.append(f"lane dim {last} (array {arr_last}): not "
                               f"full and not a multiple of {LANE}")
                sub, arr_sub = ((block[-2], arr[-2]) if len(block) > 1
                                else (arr_last, arr_last))
                if sub != arr_sub and sub % sub_min != 0:
                    bad.append(f"sublane dim {sub} (array {arr_sub}): not "
                               f"full and not a multiple of {sub_min} for "
                               f"itemsize {itemsize}")
                if bad:
                    findings.append(Finding(
                        self.name, entrypoint, SEV_ERROR, "tiling",
                        f"{where} block#{bi} "
                        f"{tuple(block)} on "
                        f"{bm.array_aval.dtype} array {tuple(arr)}: "
                        + "; ".join(bad),
                        details={"block": list(block),
                                 "array": list(arr),
                                 "dtype": str(bm.array_aval.dtype),
                                 "violations": bad}))

            # -- sentinel clamp ---------------------------------------------
            if getattr(gm, "num_index_operands", 0):
                pts = _grid_points(gm.grid)
                for bi, bm in enumerate(gm.block_mappings):
                    nblocks = [max(1, -(-a // b)) for a, b in
                               zip(bm.array_aval.shape, _block_dims(bm))]
                    for pt in pts:
                        try:
                            neg = _eval_index_map(bm.index_map_jaxpr, pt, -1)
                            zero = _eval_index_map(bm.index_map_jaxpr, pt, 0)
                        except Exception as e:  # noqa: BLE001
                            findings.append(Finding(
                                self.name, entrypoint, SEV_ERROR,
                                "sentinel-uncheckable",
                                f"{where} block#{bi}: index map could not "
                                f"be evaluated statically "
                                f"({type(e).__name__}: {e})",
                                details={"grid_point": list(pt)}))
                            break
                        oob = any(not 0 <= x < nb
                                  for x, nb in zip(neg, nblocks))
                        if neg != zero or oob:
                            findings.append(Finding(
                                self.name, entrypoint, SEV_ERROR,
                                "sentinel-clamp",
                                f"{where} block#{bi}: index map does not "
                                f"clamp -1 sentinel slots to block 0 — at "
                                f"grid {pt} a -1-filled slot table maps to "
                                f"block {neg} (0-filled table: {zero}"
                                f"{', out of bounds' if oob else ''})",
                                details={"grid_point": list(pt),
                                         "neg_table_block": list(neg),
                                         "zero_table_block": list(zero),
                                         "n_blocks": nblocks}))
                            break

        info["max_vmem_bytes"] = max_vmem
        info["vmem_budget"] = budget
        return findings, info
