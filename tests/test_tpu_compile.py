"""The main path's Pallas kernels compile for a TPU v5e chip.

Interpret mode (every other test) cannot see what Mosaic refuses: a lane
index that changes inside a loop, a slice not aligned to the tiling, too
much fast memory.  These tests lower each kernel for one chip of a
described ``v5e:2x2`` topology, at the widths ``chip_smoke.py`` serves (the
m = 10⁷ tenant graph: 8,646,926 vertices → 270,217 label words, 10⁷ edges
→ 312,500 relationship words), and check that the compiled program holds
the kernel (``tpu_custom_call``).  Nothing runs; no chip is needed.

The topology is described inside a fixture, never while the module is
imported: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

V_WORDS = 270_217  # ⌈8,646,926 / 32⌉ — vertex label plane of the smoke graph
E_WORDS = 312_500  # ⌈10⁷ / 32⌉ — edge relationship plane


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One-chip sharding; the persistent compile cache is off meanwhile, as
    a compile for a described chip is written to it but cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("w", [V_WORDS, E_WORDS])
@pytest.mark.parametrize("q", [1, 8, 64])
@pytest.mark.parametrize("k", [12, 50])
def test_packed_batched_compiles(one_chip, k, q, w):
    from repro.kernels.bitmap_query.kernel import bitmap_query_batched_packed_pallas

    fn = functools.partial(bitmap_query_batched_packed_pallas, interpret=False)
    text = _compiled_text(fn, one_chip, ((k, w), jnp.uint32), ((q, k), jnp.bool_))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("w", [V_WORDS, E_WORDS])
def test_packed_single_compiles(one_chip, w):
    from repro.kernels.bitmap_query.kernel import bitmap_query_packed_pallas

    fn = functools.partial(bitmap_query_packed_pallas, interpret=False)
    text = _compiled_text(fn, one_chip, ((12, w), jnp.uint32), ((12,), jnp.bool_))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batched", [False, True])
def test_byte_kernels_compile(one_chip, batched):
    from repro.kernels.bitmap_query import kernel

    n = 32 * V_WORDS
    if batched:
        fn = functools.partial(kernel.bitmap_query_batched_pallas, interpret=False)
        mask = ((8, 12), jnp.bool_)
    else:
        fn = functools.partial(kernel.bitmap_query_pallas, interpret=False)
        mask = ((12,), jnp.bool_)
    text = _compiled_text(fn, one_chip, ((12, n), jnp.int8), mask)
    assert "tpu_custom_call" in text


def test_embedding_bag_compiles(one_chip):
    """26 fields × 10⁵ rows, D = 128: each row is one DMA of whole lane
    tiles (D must be a multiple of 128 on the chip)."""
    from repro.kernels.embedding_bag.kernel import embedding_bag_pallas

    fn = functools.partial(embedding_bag_pallas, interpret=False)
    text = _compiled_text(fn, one_chip, ((26, 100_000, 128), jnp.float32),
                          ((2048, 26, 2), jnp.int32))
    assert "tpu_custom_call" in text


def test_embedding_bag_refuses_narrow_rows(one_chip):
    """DLRM's own width, D = 64, is refused with a clear error before
    Mosaic sees it: a 64-lane row is not a whole lane tile."""
    from repro.kernels.embedding_bag.kernel import embedding_bag_pallas

    fn = functools.partial(embedding_bag_pallas, interpret=False)
    with pytest.raises(ValueError, match="multiple of 128"):
        _compiled_text(fn, one_chip, ((26, 100_000, 64), jnp.float32),
                       ((2048, 26, 2), jnp.int32))
