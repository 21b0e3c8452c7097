"""Compile rehearsals of the fused PQTopK kernel for a described TPU v5e.

The TPU compiler is installed on CPU hosts and compiles for a chip that is
described, not attached, so these tests catch what interpret mode cannot
(block layouts the TPU refuses, primitives Mosaic does not lower) at the
real widths of the configs, with no chip.  Nothing runs: they check that
the compile succeeds and that the compiled text holds the kernel.

This is the only test file that describes a topology.  Only one process
may load the TPU library at a time, so the description lives in a
module-scoped fixture of this file, never at import.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.pqtopk import kernel as pq_kernel

TILE = 2048
K = 16
#: (catalogue, items N, sub-ids b, code dtype): the Gowalla width of
#: configs/sasrec_recjpq.py and the Booking width of gbert4rec_recjpq.py.
WIDTHS = [("gowalla", 1_271_638, 512, jnp.uint16),
          ("booking", 34_742, 256, jnp.uint8)]
TABLES = ["identity", "sentinel", "grouped", "live"]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("batch_tile", [8, 128])
@pytest.mark.parametrize("table", TABLES)
@pytest.mark.parametrize("width", WIDTHS, ids=[w[0] for w in WIDTHS])
def test_fused_kernel_compiles_for_v5e(width, table, batch_tile, one_chip,
                                       no_persistent_cache):
    """``pq_topk_fused_call`` compiles for one v5e chip with the 1-D
    identity tile table (the exhaustive route), the 1-D table with ``-1``
    sentinels (the pruned route's compacted buffer), the 2-D per-group
    table (query grouping) and the tombstone ``live`` mask."""
    _, n, b, code_dtype = width
    m, bq = 8, 2 * batch_tile
    n_rows = -(-n // TILE) * TILE + TILE        # + the sentinel tile
    n_tiles = n_rows // TILE

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [sds((n_rows, m), code_dtype), sds((bq, m, b), jnp.float32)]
    if table == "identity":
        args.append(sds((n_tiles,), jnp.int32))
    elif table == "grouped":
        args.append(sds((bq // batch_tile, 64), jnp.int32))
    else:
        args.append(sds((64,), jnp.int32))
    if table == "live":
        args.append(sds((n_tiles, 1, TILE), jnp.int8))

    def fused(codes, s, tile_idx, live=None):
        return pq_kernel.pq_topk_fused_call(
            codes, s, K, tile_idx=tile_idx, n_items=n, tile=TILE,
            batch_tile=batch_tile, live=live)

    compiled = jax.jit(fused).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out_v, out_i = compiled.out_info
    n_slots = args[2].shape[-1]
    assert out_v.shape == out_i.shape == (n_slots, bq, K)


@pytest.mark.parametrize("bq", [256, pq_kernel.MAX_RUN_KEYS // 128],
                         ids=["candgen", "run_keys_bound"])
def test_fused_kernel_compiles_with_k128_running_keys(bq, one_chip,
                                                      no_persistent_cache):
    """At k=128 and the Gowalla width the (B, K) running-key scratch fits
    the chip's scoped VMEM beside the tile working set: at the candidate-
    generation batch (B=256) and at the largest batch one call takes
    (``MAX_RUN_KEYS`` keys)."""
    _, n, b, code_dtype = WIDTHS[0]
    n_rows = -(-n // TILE) * TILE

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fused(codes, s, tile_idx):
        return pq_kernel.pq_topk_fused_call(
            codes, s, 128, tile_idx=tile_idx, n_items=n, tile=TILE,
            batch_tile=128)

    compiled = jax.jit(fused).lower(
        sds((n_rows, 8), code_dtype), sds((bq, 8, b), jnp.float32),
        sds((n_rows // TILE,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1


@pytest.mark.parametrize("width", WIDTHS, ids=[w[0] for w in WIDTHS])
def test_kernel_call_keeps_its_name_inside_the_scopes(width, one_chip,
                                                      no_persistent_cache):
    """The exhaustive route's ``pq_topk`` for one v5e: the kernel's custom
    call is still the instruction ``%pq_topk.<n>`` (the name a device
    trace finds it by) inside the ``head.*`` scopes, which tag its ops."""
    import re

    from repro.kernels.pqtopk import ops
    _, n, b, code_dtype = width
    codes = jax.ShapeDtypeStruct((n, 8), code_dtype, sharding=one_chip)
    s = jax.ShapeDtypeStruct((16, 8, b), jnp.float32, sharding=one_chip)
    text = jax.jit(lambda c, s: ops.pq_topk(c, s, 10, interpret=False)
                   ).lower(codes, s).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert len(calls) == 1
    assert re.match(r"\s*%pq_topk(\.\d+)? = .*custom-call\(", calls[0])
    assert "/head.kernel/" in calls[0]
    for scope in ("head.codes_pad", "head.merge"):
        assert f"/{scope}/" in text
