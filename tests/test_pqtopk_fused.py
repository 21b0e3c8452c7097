"""End-to-end coverage for the fused PQTopK retrieval route
(``method="pqtopk_fused"``): kernel-vs-oracle bit-exactness, parity with the
unfused ``pqtopk`` + ``tiled_topk`` path through every layer (retrieval
head, item-sharded shard_map, serving engine)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.configs.base import PQConfig
from repro.core import retrieval_head, scoring
from repro.kernels.pqtopk import ops as pq_ops, ref as pq_ref
from repro.serving.engine import Request, RetrievalEngine


def _pq_head(n, d=32, m=4, b=16, bq=3, seed=0):
    params = retrieval_head.init(jax.random.PRNGKey(seed), n, d,
                                 PQConfig(m=m, b=b))
    phi = jax.random.normal(jax.random.PRNGKey(seed + 1), (bq, d))
    return params, phi


# ---------------------------------------------------------------------------
# kernel parity: interpret mode must be BIT-exact against the jnp oracle
# (shared tree_sum accumulation order; one-hot matmuls are exact in f32).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m,b,tile", [
    (999, 4, 16, 256),       # odd N -> padding tail inside the last tile
    (1021, 3, 100, 128),     # prime N, b neither 256 nor a power of two
    (4096, 8, 64, 2048),     # b != 256, exact tiling
    (300, 2, 256, 256),      # b == lane width, N < 2 tiles
])
def test_pq_scores_kernel_bitexact_vs_oracle(n, m, b, tile):
    codes = jax.random.randint(jax.random.PRNGKey(0), (n, m), 0, b,
                               dtype=jnp.int32)
    s = jax.random.normal(jax.random.PRNGKey(1), (2, m, b), jnp.float32)
    r_ref = np.asarray(pq_ref.pq_scores(codes, s))
    r_ker = np.asarray(pq_ops.pq_scores(codes, s, tile=tile, interpret=True))
    np.testing.assert_array_equal(r_ker, r_ref)
    # ... and both match Algorithm 1's gather form bit-for-bit.
    r_alg1 = np.asarray(scoring.score_pqtopk(codes, s))
    np.testing.assert_array_equal(r_alg1, r_ref)


def test_pq_scores_kernel_bitexact_small_magnitude():
    """The seed-suite regression: near-zero scores at rtol=1e-6, atol=0
    (1-ulp accumulation-order drift used to fail here)."""
    codes = jax.random.randint(jax.random.PRNGKey(0), (1024, 4), 0, 100
                               ).astype(jnp.int8)
    s = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 128), jnp.float32)
    r_ref = np.asarray(pq_ref.pq_scores(codes.astype(jnp.int32), s))
    r_ker = np.asarray(pq_ops.pq_scores(codes, s, tile=256))
    np.testing.assert_array_equal(r_ker, r_ref)


# ---------------------------------------------------------------------------
# retrieval head: fused route == unfused pqtopk + tiled_topk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1000, 4096, 100_000])
def test_top_items_fused_matches_pqtopk(n):
    params, phi = _pq_head(n)
    k = 10
    v_ref, i_ref = retrieval_head.top_items(params, phi, k, method="pqtopk")
    v_fus, i_fus = retrieval_head.top_items(params, phi, k,
                                            method="pqtopk_fused")
    np.testing.assert_array_equal(np.asarray(v_fus), np.asarray(v_ref))
    # Tie-breaking is index-consistent in both routes (lowest id first), so
    # ids agree exactly, not just score-wise.
    np.testing.assert_array_equal(np.asarray(i_fus), np.asarray(i_ref))


def test_top_items_fused_ties_broken_by_lowest_id():
    """All-identical codes => every item ties; both routes must pick ids
    0..k-1 in order (lax.top_k tie-break semantics)."""
    params, phi = _pq_head(512, m=2, b=8)
    params = dict(params, codes=jnp.zeros_like(params["codes"]))
    v_ref, i_ref = retrieval_head.top_items(params, phi, 5, method="pqtopk")
    v_fus, i_fus = retrieval_head.top_items(params, phi, 5,
                                            method="pqtopk_fused")
    np.testing.assert_array_equal(np.asarray(i_fus), np.asarray(i_ref))
    assert (np.asarray(i_fus) == np.arange(5)[None, :]).all()


def test_top_items_fused_requires_pq():
    params = retrieval_head.init(jax.random.PRNGKey(0), 64, 16, pq=None)
    phi = jax.random.normal(jax.random.PRNGKey(1), (1, 16))
    with pytest.raises(ValueError, match="pqtopk_fused"):
        retrieval_head.top_items(params, phi, 3, method="pqtopk_fused")


def test_score_candidates_fused_subset():
    params, phi = _pq_head(200)
    v_ids = jnp.asarray([0, 7, 63, 199])
    r_sub = retrieval_head.score_candidates(params, phi, v_ids,
                                            method="pqtopk_fused")
    r_all = retrieval_head.score_all(params, phi, "pqtopk")
    np.testing.assert_array_equal(np.asarray(r_sub),
                                  np.asarray(r_all[:, v_ids]))


# ---------------------------------------------------------------------------
# item-sharded: fused per-shard top-k + O(k * shards) merge
# ---------------------------------------------------------------------------

@pytest.mark.sharded
@pytest.mark.parametrize("n", [128, 101])   # 101: shard-padding rows masked
def test_top_items_sharded_fused_matches_plain(n):
    mesh = jax.make_mesh((1,), ("model",))
    params, phi = _pq_head(n, d=16, m=4, b=8, bq=2)
    v1, i1 = retrieval_head.top_items(params, phi, 7, method="pqtopk")
    v2, i2 = retrieval_head.top_items_sharded(params, phi, 7, mesh,
                                              method="pqtopk_fused")
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    assert (np.asarray(i2) < n).all()


# ---------------------------------------------------------------------------
# serving engine on the fused route
# ---------------------------------------------------------------------------

def _engine(method):
    from repro.models import seqrec as S
    cfg = get_reduced("sasrec-recjpq").model
    params = S.init_seqrec(jax.random.PRNGKey(0), cfg)
    eng = RetrievalEngine.for_seqrec(params, cfg, k=5, max_batch=8,
                                     method=method)
    return eng, cfg


def test_retrieval_engine_fused_matches_pqtopk():
    rng = np.random.default_rng(0)
    seqs = [rng.integers(1, 1000, 8) for _ in range(8)]
    results = {}
    for method in ("pqtopk", "pqtopk_fused"):
        engine, cfg = _engine(method)
        assert engine.method == method
        for i, s in enumerate(seqs):
            engine.submit(Request(i, s, k=5))
        results[method] = {r.request_id: r for r in engine.drain()}
    assert len(results["pqtopk_fused"]) == 8
    for i in range(8):
        np.testing.assert_array_equal(results["pqtopk_fused"][i].scores,
                                      results["pqtopk"][i].scores)
        np.testing.assert_array_equal(results["pqtopk_fused"][i].items,
                                      results["pqtopk"][i].items)


def test_engine_method_defaults_to_config():
    cfg = get_reduced("sasrec-recjpq").model
    from repro.models import seqrec as S
    params = S.init_seqrec(jax.random.PRNGKey(0), cfg)
    eng = RetrievalEngine.for_seqrec(params, cfg)
    assert eng.method == cfg.serve_method


def _levelwise_sum(parts):
    """Reference order: pair neighbours level by level, the odd one
    carried up."""
    parts = list(parts)
    while len(parts) > 1:
        nxt = [parts[i] + parts[i + 1] for i in range(0, len(parts) - 1, 2)]
        if len(parts) % 2:
            nxt.append(parts[-1])
        parts = nxt
    return parts[0]


@pytest.mark.parametrize("n", range(1, 18))
def test_tree_sum_lazy_keeps_the_levelwise_order(n):
    """The lazy reduction the kernel uses rounds exactly like the level-
    by-level pairing (values spread over many magnitudes, so any other
    association would change low bits)."""
    from repro.core.scoring import tree_sum, tree_sum_lazy
    rng = np.random.default_rng(n)
    parts = [np.float32(v) for v in
             rng.normal(size=n) * 10.0 ** rng.integers(-6, 7, n)]
    want = _levelwise_sum(parts)
    assert tree_sum(parts) == want
    assert tree_sum_lazy(lambda i: parts[i], n) == want


def test_bf16_split_rebuilds_f32_exactly():
    """hi + mid + lo of the kernel's bf16 split is the f32 input, bit for
    bit, across magnitudes and signs — what makes the one-hot MXU scores
    exact."""
    from repro.kernels.pqtopk.kernel import _bf16_split
    rng = np.random.default_rng(0)
    x = (rng.normal(size=(64, 512)) * 10.0 ** rng.integers(-20, 20, (64, 512))
         ).astype(np.float32)
    x[0, :4] = [0.0, -0.0, 1.0, -3.0]
    hi, mid, lo = _bf16_split(jnp.asarray(x))
    assert hi.dtype == mid.dtype == lo.dtype == jnp.bfloat16
    back = (hi.astype(jnp.float32) + mid.astype(jnp.float32)) \
        + lo.astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(back), x)


# ---------------------------------------------------------------------------
# the carried threshold: each row's running top-K keys cut a tile's
# max-extract passes; answers stay bit-identical to the pqtopk route
# ---------------------------------------------------------------------------

def _threshold_case(name):
    """-> (codes, s, k, tile, batch_tile, table, live, share): ``table``
    None for the exhaustive route, else a 1-D or 2-D tile table for
    ``pq_topk_tiles``; ``share`` the extraction share the exhaustive route
    must read, or None."""
    rng = np.random.default_rng(len(name))
    tile, n_tiles, m, b, bq, k = 128, 12, 2, 64, 3, 8
    n = tile * n_tiles
    codes = rng.integers(0, b, (n, m))
    s = table = live = share = None
    if name in ("rising", "falling"):
        # Split 0 alone scores: tile t's items take codes 4t..4t+3, so
        # every tile beats (rising) or loses to (falling) all before it.
        b = 4 * n_tiles
        codes[:, 0] = np.arange(n) // tile * 4 + rng.integers(0, 4, n)
        codes[:, 1] = 0
        s = np.zeros((bq, m, b))
        s[:, 0] = np.arange(b) * (1.0 if name == "rising" else -1.0)
        share = 1.0 if name == "rising" else 1.0 / n_tiles
    elif name == "ties":
        codes[:] = 0
        share = 1.0 / n_tiles
    elif name == "k_is_tile":
        k = tile
    elif name == "uint16_b512_k128":
        b, k, tile = 512, 128, 256
        codes = rng.integers(0, b, (n, m))
    elif name == "padded_last_tile":
        n = tile * (n_tiles - 1) + 3
        codes = codes[:n]
    elif name == "sentinels":
        table = np.full(n_tiles, -1, np.int32)
        table[:n_tiles // 2] = np.arange(0, n_tiles, 2)
    elif name == "grouped":
        bq = 16
        table = np.full((2, n_tiles), -1, np.int32)
        table[0, :5] = [0, 2, 3, 7, 11]
        table[1, :8] = [1, 2, 4, 5, 6, 8, 9, 10]
    elif name == "live":
        table = np.arange(n_tiles, dtype=np.int32)
        live = rng.random(n) < 0.3
    if s is None:
        s = rng.standard_normal((bq, m, b))
    dtype = jnp.uint16 if b > 256 else jnp.int8
    return (jnp.asarray(codes, dtype), jnp.asarray(s, jnp.float32), k, tile,
            8, table, live, share)


@pytest.mark.parametrize("name", [
    "rising", "falling", "ties", "k_is_tile", "uint16_b512_k128",
    "padded_last_tile", "sentinels", "grouped", "live"])
def test_carried_threshold_matches_pqtopk(name):
    """The fused kernel, whose tiles run only the passes each row's
    running threshold leaves, returns what the pqtopk route returns, bit
    for bit in values and ids, over many tiles: scores rising tile by
    tile (no pruning), falling (none after the first tile), all tied,
    k = tile, b=512 uint16 codes at k=128, a last tile mostly padding, a
    compacted table with -1 sentinels, a 2-D grouped table and a
    tombstone mask."""
    codes, s, k, tile, bt, table, live, share = _threshold_case(name)
    if table is None:
        got = pq_ops.pq_topk(codes, s, k, tile=tile, batch_tile=bt,
                             interpret=True)
        r = scoring.score_pqtopk(codes.astype(jnp.int32), s)
        want = jax.lax.top_k(r, k)
        got_share = float(pq_ops.extraction_share(
            codes, s, k, tile=tile, batch_tile=bt, interpret=True))
        if share is None:
            assert 0.0 < got_share <= 1.0
        else:
            assert got_share == pytest.approx(share, abs=1e-7)
    else:
        table = jnp.asarray(table)
        live = None if live is None else jnp.asarray(live)
        got, want = (pq_ops.pq_topk_tiles(codes, s, k, table, tile=tile,
                                          batch_tile=bt, live=live,
                                          use_kernel=uk, interpret=True)
                     for uk in (True, False))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


@pytest.mark.parametrize("grouped", [False, True], ids=["1d", "2d"])
def test_batch_past_the_running_key_bound_runs_in_pieces(grouped,
                                                         monkeypatch):
    """A batch with more rows than ``MAX_RUN_KEYS`` / K runs as several
    kernel calls, each with its own running keys (and, on the grouped
    route, its own rows of the table); the answers do not change."""
    from repro.kernels.pqtopk import kernel as pq_kernel
    monkeypatch.setattr(pq_kernel, "MAX_RUN_KEYS", 16 * 8)  # 16 rows at k=8
    rng = np.random.default_rng(3)
    n, tile, k, bq = 128 * 6, 128, 8, 40
    codes = jnp.asarray(rng.integers(0, 32, (n, 2)), jnp.int8)
    s = jnp.asarray(rng.standard_normal((bq, 2, 32)), jnp.float32)
    table = np.arange(6, dtype=np.int32)
    if grouped:                 # batch tile r scores tiles t % (r+2) == 0
        rows = [[t for t in range(6) if t % (r + 2) == 0] for r in range(5)]
        table = np.array([row + [-1] * (6 - len(row)) for row in rows],
                         np.int32)
    got, want = (pq_ops._pq_topk_tiles(codes, s, k, jnp.asarray(table),
                                       tile=tile, batch_tile=8,
                                       use_kernel=uk, interpret=True)
                 for uk in (True, False))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
