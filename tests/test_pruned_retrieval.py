"""Cascaded pruned retrieval (``method="pqtopk_pruned"``) + the rebuilt
fused kernel: exactness against the ``score_pqtopk`` + ``tiled_topk``
oracle across the acceptance matrix (odd N, b in {64, 256}, int8/uint8/
int32 codes, B in {1, 8, 200}, item-sharded), batch-tiling parity, bound
tightness, and the satellite fixes (tiled_topk -inf padding, approx
route, per-request k in the serving engine)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.configs.base import PQConfig, min_code_dtype
from repro.core import pruning, retrieval_head, scoring, topk as topk_lib
from repro.kernels.pqtopk import ops as pq_ops, ref as pq_ref
from repro.serving.engine import Request, RetrievalEngine


def _oracle(codes, s, k):
    r = scoring.score_pqtopk(codes.astype(jnp.int32), s)
    return topk_lib.tiled_topk(r, k)


def _make_case(n, m, b, bq, *, code_dtype=jnp.int32, clustered=False,
               skewed=False, seed=0):
    rng = np.random.default_rng(seed)
    if clustered:
        centers = (np.arange(n) / n * b).astype(np.int64)
        codes_np = (centers[:, None] + rng.integers(-1, 2, (n, m))) % b
    else:
        codes_np = rng.integers(0, b, (n, m))
    codes = jnp.asarray(codes_np, code_dtype)
    g = rng.standard_normal((bq, m, b))
    if skewed:
        g = np.sign(g) * np.abs(g) ** 3
    s = jnp.asarray(g, jnp.float32)
    return codes, s


# ---------------------------------------------------------------------------
# cascade exactness: bit-identical values AND ids vs the oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bq", [1, 8, 200])
@pytest.mark.parametrize("n,b,dtype", [
    (999, 64, jnp.int8),       # odd N, int8 codes
    (1021, 256, jnp.uint8),    # prime N, uint8 codes (b=256 > int8 range)
    (2048, 64, jnp.int32),     # exact tiling, int32 fallback
    (3001, 256, jnp.int32),
])
def test_cascade_matches_oracle(n, b, dtype, bq):
    m = 4
    codes, s = _make_case(n, m, b, bq, code_dtype=dtype, seed=n + bq)
    k = 10
    v_ref, i_ref = _oracle(codes, s, k)
    v, i = pruning.cascade_topk(codes, s, k, tile=256)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))


def test_cascade_actually_prunes_and_stays_exact():
    """Clustered codes + skewed scores: the favourable regime — assert the
    survival fraction is < 1 AND the result is still bit-exact."""
    codes, s = _make_case(1 << 14, 8, 256, 2, clustered=True, skewed=True)
    k = 10
    v_ref, i_ref = _oracle(codes, s, k)
    v, i, stats = pruning.cascade_topk(codes, s, k, tile=512,
                                       return_stats=True)
    assert stats["survival_fraction"] < 1.0, stats
    assert stats["n_survived"] < stats["n_tiles"]
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))


def test_cascade_kernel_path_matches_xla_path():
    codes, s = _make_case(5000, 4, 64, 3, code_dtype=jnp.int8,
                          clustered=True, skewed=True)
    k = 7
    out = [pruning.cascade_topk(codes, s, k, tile=512, use_kernel=uk,
                                interpret=True) for uk in (False, True)]
    np.testing.assert_array_equal(np.asarray(out[0][0]),
                                  np.asarray(out[1][0]))
    np.testing.assert_array_equal(np.asarray(out[0][1]),
                                  np.asarray(out[1][1]))


def test_cascade_ties_broken_by_lowest_id():
    """All-identical codes -> every item ties; the cascade must preserve
    lax.top_k's lowest-id-first order through compaction and merge."""
    n, m, b = 700, 2, 8
    codes = jnp.zeros((n, m), jnp.int32)
    s = jax.random.normal(jax.random.PRNGKey(0), (2, m, b), jnp.float32)
    v_ref, i_ref = _oracle(codes, s, 5)
    v, i = pruning.cascade_topk(codes, s, 5, tile=128)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
    assert (np.asarray(i) == np.arange(5)[None, :]).all()


# ---------------------------------------------------------------------------
# retrieval head routes (host cascade, in-graph fallback, sharded)
# ---------------------------------------------------------------------------

def _pq_head(n, d=32, m=4, b=16, bq=3, seed=0, code_dtype="int32"):
    params = retrieval_head.init(jax.random.PRNGKey(seed), n, d,
                                 PQConfig(m=m, b=b, code_dtype=code_dtype))
    phi = jax.random.normal(jax.random.PRNGKey(seed + 1), (bq, d))
    return params, phi


@pytest.mark.parametrize("n,bq", [(1000, 1), (4097, 8)])
def test_top_items_pruned_matches_pqtopk(n, bq):
    params, phi = _pq_head(n, bq=bq)
    k = 9
    v_ref, i_ref = retrieval_head.top_items(params, phi, k, method="pqtopk")
    v, i, stats = retrieval_head.top_items_pruned(params, phi, k, tile=512,
                                                  return_stats=True)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
    assert stats["n_tiles"] == -(-n // 512)


def test_top_items_pruned_ingraph_jit():
    """method="pqtopk_pruned" through top_items is jit-compatible (masked
    in-graph cascade) and bit-exact."""
    params, phi = _pq_head(3000, bq=2)
    v_ref, i_ref = retrieval_head.top_items(params, phi, 6, method="pqtopk")
    fn = jax.jit(lambda p, x: retrieval_head.top_items(
        p, x, 6, method="pqtopk_pruned"))
    v, i = fn(params, phi)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))


def test_top_items_pruned_requires_pq():
    params = retrieval_head.init(jax.random.PRNGKey(0), 64, 16, pq=None)
    phi = jax.random.normal(jax.random.PRNGKey(1), (1, 16))
    with pytest.raises(ValueError, match="pqtopk_pruned"):
        retrieval_head.top_items(params, phi, 3, method="pqtopk_pruned")
    with pytest.raises(ValueError, match="PQ head"):
        retrieval_head.top_items_pruned(params, phi, 3)


@pytest.mark.sharded
@pytest.mark.parametrize("n", [128, 1013])   # odd N -> padding tail
def test_top_items_pruned_sharded_matches_plain(n):
    mesh = jax.make_mesh((1,), ("model",))
    params, phi = _pq_head(n, d=16, m=4, b=8, bq=2, code_dtype="uint8")
    v1, i1 = retrieval_head.top_items(params, phi, 7, method="pqtopk")
    v2, i2 = retrieval_head.top_items_sharded(params, phi, 7, mesh,
                                              method="pqtopk_pruned")
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    assert (np.asarray(i2) < n).all()


# ---------------------------------------------------------------------------
# rebuilt fused kernel: batch tiling + int8 codes, interpret parity atol=0
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.int8, jnp.uint8, jnp.int32])
def test_fused_kernel_batch_tiled_parity(dtype):
    """B=200 > batch_tile=64 engages the batch-tile grid axis; parity with
    the oracle must be exact (atol=0) for 8-bit and int32 codes."""
    n, m, b, bq, k = 2500, 4, 100, 200, 11
    codes, s = _make_case(n, m, b, bq, code_dtype=dtype, seed=5)
    v_ref, i_ref = pq_ref.pq_topk(codes.astype(jnp.int32), s, k)
    v, i = pq_ops.pq_topk(codes, s, k, tile=512, batch_tile=64,
                          interpret=True)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))


@pytest.mark.parametrize("k", [1, 7, 64, 128])
@pytest.mark.parametrize("layout", ["distinct", "ties", "neg_inf_tail"])
def test_tile_topk_matches_lax_top_k(k, layout):
    """The kernel's in-VMEM max-extract top-k returns exactly what
    ``lax.top_k`` returns — values, and columns in value-then-lowest-
    column order — including heavy ties and a ``-inf`` masked tail, where
    padding columns must come out in ascending order, never repeated."""
    from repro.kernels.pqtopk.kernel import _tile_topk
    rng = np.random.default_rng(k)
    scores = rng.normal(size=(8, 128)).astype(np.float32)
    if layout == "ties":
        scores = np.round(scores * 2) / 2           # few distinct values
    elif layout == "neg_inf_tail":
        scores[:, 5:] = -np.inf
    v, c = jax.jit(_tile_topk, static_argnums=1)(jnp.asarray(scores), k)
    v_ref, c_ref = jax.lax.top_k(jnp.asarray(scores), k)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c_ref))


def test_pq_topk_tiles_sentinel_padding():
    """Sentinel-padded slots emit -inf and never reach the top-k."""
    n, m, b, tile, k = 1000, 4, 16, 256, 5
    codes, s = _make_case(n, m, b, 2, seed=9)
    v_ref, i_ref = _oracle(codes, s, k)
    t = pq_ops.n_tiles(n, tile)
    idx = np.full(8, pq_ops.sentinel_tile(n, tile), np.int32)
    idx[:t] = np.arange(t)
    for uk in (False, True):
        v, i = pq_ops.pq_topk_tiles(codes, s, k, jnp.asarray(idx), tile=tile,
                                    use_kernel=uk, interpret=True)
        np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))
        np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))


# ---------------------------------------------------------------------------
# bound semantics
# ---------------------------------------------------------------------------

def test_tile_bounds_dominate_tile_scores():
    codes, s = _make_case(2000, 4, 32, 3, seed=2)
    tile = 256
    meta = pruning.build_tile_metadata(codes, 32, tile)
    bounds = np.asarray(pruning.tile_upper_bounds(meta.present, s))
    r = np.asarray(scoring.score_pqtopk(codes, s))
    for t in range(meta.n_tiles):
        seg = r[:, t * tile:(t + 1) * tile].max(axis=1)
        assert (bounds[:, t] >= seg).all()


def test_tile_bound_tight_for_single_item_tile():
    """tile=1: the bound IS the item's score, bit-for-bit (shared tree_sum
    accumulation order)."""
    codes, s = _make_case(64, 4, 16, 2, seed=3)
    meta = pruning.build_tile_metadata(codes, 16, 1)
    bounds = np.asarray(pruning.tile_upper_bounds(meta.present, s))
    r = np.asarray(scoring.score_pqtopk(codes, s))
    np.testing.assert_array_equal(bounds, r)


def test_theta_is_certified():
    """At least k items must score >= theta for every query."""
    codes, s = _make_case(5000, 4, 64, 4, seed=4)
    k, tile = 10, 512
    meta = pruning.build_tile_metadata(codes, 64, tile)
    bounds = pruning.tile_upper_bounds(meta.present, s)
    theta = np.asarray(pruning.theta_from_seed(codes, s, bounds, k,
                                               tile=tile, n_seed=2))
    r = np.asarray(scoring.score_pqtopk(codes, s))
    assert ((r >= theta[:, None]).sum(axis=1) >= k).all()


def test_metadata_cache_reuses_and_rebuilds():
    codes, _ = _make_case(1000, 2, 16, 1)
    m1 = pruning.get_tile_metadata(codes, 16, 256)
    m2 = pruning.get_tile_metadata(codes, 16, 256)
    assert m1 is m2
    assert pruning.get_tile_metadata(codes, 16, 128) is not m1


# ---------------------------------------------------------------------------
# satellite: tiled_topk pads odd N with -inf (no full-sort fallback)
# ---------------------------------------------------------------------------

def test_tiled_topk_odd_n_regression(monkeypatch):
    tile = 1024
    n = 3 * tile + 17
    scores = jax.random.normal(jax.random.PRNGKey(0), (2, n), jnp.float32)
    v_ref, i_ref = jax.lax.top_k(scores, 9)
    widths = []
    orig = jax.lax.top_k

    def spy(x, kk):
        widths.append(x.shape[-1])
        return orig(x, kk)

    monkeypatch.setattr(jax.lax, "top_k", spy)
    v, i = topk_lib.tiled_topk(scores, 9, tile=tile)
    monkeypatch.undo()
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
    # The perf cliff was a full lax.top_k sort over all N columns; the
    # padded path must never sort wider than one tile (+ the winner merge).
    assert max(widths) <= tile, widths


def test_tiled_topk_padding_never_wins():
    scores = jnp.full((1, 2 * 8192 + 1), -1e30, jnp.float32)
    v, i = topk_lib.tiled_topk(scores, 4)
    assert (np.asarray(i) < scores.shape[1]).all()
    assert np.isfinite(np.asarray(v)).all()


# ---------------------------------------------------------------------------
# satellite: approximate block-max route wired as pqtopk_approx
# ---------------------------------------------------------------------------

def test_pqtopk_approx_recall_vs_oracle():
    params, phi = _pq_head(50_000, d=32, m=4, b=64, bq=4, seed=7)
    k = 10
    v_ref, i_ref = retrieval_head.top_items(params, phi, k, method="pqtopk")
    v, i = retrieval_head.top_items(params, phi, k, method="pqtopk_approx")
    i, i_ref = np.asarray(i), np.asarray(i_ref)
    recall = np.mean([len(set(i[q]) & set(i_ref[q])) / k
                      for q in range(i.shape[0])])
    # Block-max with oversample=2 gives ~1 - k/(2*n_blocks) expected
    # recall (~0.75 here); assert a loose floor for seed stability.
    assert recall >= 0.5, recall
    # Returned values are genuine scores of the returned ids.
    r = np.asarray(retrieval_head.score_all(params, phi, "pqtopk"))
    np.testing.assert_array_equal(
        np.asarray(v), np.take_along_axis(r, i, axis=1))


def test_pqtopk_approx_in_methods_tuple():
    assert "pqtopk_approx" in retrieval_head.TOP_ITEMS_METHODS
    assert "pqtopk_pruned" in retrieval_head.TOP_ITEMS_METHODS


# ---------------------------------------------------------------------------
# satellite: per-request k in the serving engine
# ---------------------------------------------------------------------------

def _engine(method, k=5):
    from repro.models import seqrec as S
    cfg = get_reduced("sasrec-recjpq").model
    params = S.init_seqrec(jax.random.PRNGKey(0), cfg)
    eng = RetrievalEngine.for_seqrec(params, cfg, k=k, max_batch=8,
                                     method=method)
    return eng, cfg


def test_engine_mixed_k_batch():
    """Requests with different k in ONE batch: score at max(k), slice per
    request — the k=7 request must get 7 genuine winners, not a truncated
    or padded 5."""
    rng = np.random.default_rng(0)
    seqs = [rng.integers(1, 1000, 8) for _ in range(4)]
    ks = [3, 7, 5, 2]
    eng, _ = _engine("pqtopk", k=2)
    for i, (sq, kk) in enumerate(zip(seqs, ks)):
        eng.submit(Request(i, sq, k=kk))
    res = {r.request_id: r for r in eng.run_once()}
    assert len(res) == 4
    for i, kk in enumerate(ks):
        assert res[i].items.shape == (kk,)
        assert res[i].scores.shape == (kk,)
    # Every result is the exact prefix of a reference engine run at k=7.
    ref_eng, _ = _engine("pqtopk", k=7)
    for i, sq in enumerate(seqs):
        ref_eng.submit(Request(100 + i, sq, k=7))
    ref = {r.request_id - 100: r for r in ref_eng.drain()}
    for i, kk in enumerate(ks):
        np.testing.assert_array_equal(res[i].items, ref[i].items[:kk])
        np.testing.assert_array_equal(res[i].scores, ref[i].scores[:kk])


def test_engine_clamps_and_buckets_client_k():
    """Client-supplied Request.k is untrusted: oversized k must be clamped
    to max_k (not forwarded to serve_fn, where it would abort the whole
    batch), k<1 must not produce empty/negative slices, and distinct
    in-range values must collapse onto power-of-two buckets so adversarial
    or merely diverse traffic cannot drive unbounded jit recompiles."""
    calls = []

    def serve_fn(seqs, kk):
        calls.append(kk)
        ids = jnp.tile(jnp.arange(kk, dtype=jnp.int32)[None],
                       (seqs.shape[0], 1))
        return ids, jnp.zeros((seqs.shape[0], kk), jnp.float32)

    eng = RetrievalEngine(serve_fn, seq_len=4, k=2, max_k=16,
                          jit_serve=False)
    eng.submit(Request(0, np.asarray([1]), k=5000))
    eng.submit(Request(1, np.asarray([1]), k=0))
    res = {r.request_id: r for r in eng.run_once()}
    assert calls == [16]                    # clamped, batch not aborted
    assert res[0].items.shape == (16,)      # oversized k -> max_k winners
    assert res[1].items.shape == (1,)       # degenerate k -> 1 winner
    for i, kk in enumerate((5, 6, 7, 8)):   # one batch per distinct k
        eng.submit(Request(10 + i, np.asarray([1]), k=kk))
        eng.run_once()
    assert calls[1:] == [8, 8, 8, 8]        # one bucket, one compile
    # Without an explicit max_k the cap defaults to the engine's own k —
    # the only k a bare serve_fn is guaranteed to support (e.g. small
    # catalogues where 1024 winners don't exist).
    calls.clear()
    eng2 = RetrievalEngine(serve_fn, seq_len=4, k=3, jit_serve=False)
    eng2.submit(Request(0, np.asarray([1]), k=999))
    res2 = eng2.run_once()
    assert calls == [3] and res2[0].items.shape == (3,)


def test_engine_pruned_route_matches_pqtopk():
    rng = np.random.default_rng(1)
    seqs = [rng.integers(1, 1000, 8) for _ in range(4)]
    results = {}
    for method in ("pqtopk", "pqtopk_pruned"):
        eng, _ = _engine(method)
        assert eng.method == method
        for i, sq in enumerate(seqs):
            eng.submit(Request(i, sq, k=5))
        results[method] = {r.request_id: r for r in eng.drain()}
    for i in range(4):
        np.testing.assert_array_equal(results["pqtopk_pruned"][i].items,
                                      results["pqtopk"][i].items)
        np.testing.assert_array_equal(results["pqtopk_pruned"][i].scores,
                                      results["pqtopk"][i].scores)


# ---------------------------------------------------------------------------
# satellite: int8/uint8 code storage config validation
# ---------------------------------------------------------------------------

def test_pqconfig_code_dtype_validation():
    PQConfig(m=2, b=128, code_dtype="int8")        # fits
    PQConfig(m=2, b=256, code_dtype="uint8")       # fits
    with pytest.raises(ValueError, match="does not fit"):
        PQConfig(m=2, b=256, code_dtype="int8")
    with pytest.raises(ValueError, match="unsupported code_dtype"):
        PQConfig(m=2, b=16, code_dtype="float32")
    assert min_code_dtype(256) == "uint8"
    assert min_code_dtype(512) == "uint16"


def test_pq_head_stores_narrow_codes():
    params, _ = _pq_head(100, b=16, code_dtype="uint8")
    assert params["codes"].dtype == jnp.uint8
