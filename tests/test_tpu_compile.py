"""Compile the main-path Pallas kernels for a TPU v5e that is described,
not attached.

With libtpu installed (``jax[tpu]``; it is not part of jaxlib), Mosaic's
verdict on each kernel of the main read path — ``backend="pallas"``,
``codec="raw"``, static index and merge-on-read, plus the master's top-k
merge — is available without a chip.  These compiles run at real widths
(window 4096, Q=8, T=4, 64M flat postings) with ``interpret=False``; they
catch what interpret mode cannot:
Element offsets Mosaic cannot prove tile-aligned, i1 selects, flat
reshapes, block shapes off the (8, 128) tile.  Nothing runs, so they say
nothing about results or times.

The topology is described inside a module-scoped fixture: only one
process may load the TPU library at a time, so describing it at import
(or in a ``skipif``) would break multi-worker collection.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.index import BLOCK, flat_tile_pad
from repro.kernels.delta_merge import merge_delta_windows
from repro.kernels.posting_intersect import (
    intersect_batched_driver_streamed,
    intersect_batched_streamed,
)
from repro.kernels.topk_merge import merge_topk_rows

WINDOW = 4096
Q = 8
T = 4
POSTINGS = 64 * 1024 * 1024        # flat posting slots of the main index
N_TERMS = 100_000
CAP = 2 * BLOCK                    # the delta writer's default capacity
I32 = jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, I32, sharding=sharding)


def _compile(fn, args, **static):
    text = fn.lower(*args, **static).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _main_index(s):
    return [
        _spec(s, POSTINGS),                 # postings
        _spec(s, POSTINGS),                 # attrs
        _spec(s, N_TERMS),                  # offsets
        _spec(s, N_TERMS),                  # lengths
        _spec(s, POSTINGS // BLOCK),        # block_max
    ]


def _delta(s):
    flat = flat_tile_pad(N_TERMS * CAP)
    return [
        _spec(s, flat),                     # d_postings
        _spec(s, flat),                     # d_attrs
        _spec(s, N_TERMS),                  # d_offsets
        _spec(s, N_TERMS),                  # d_lengths
        _spec(s, N_TERMS * CAP // BLOCK),   # d_block_max
    ]


def test_driver_streamed_compiles(one_chip):
    s = one_chip
    postings, attrs, offsets, lengths, block_max = _main_index(s)
    args = [
        _spec(s, Q), _spec(s, Q),           # d_off, d_neff
        _spec(s, Q, T), _spec(s, Q, T),     # terms, active
        _spec(s, Q),                        # attr_filter
        postings, attrs, offsets, lengths, block_max,
    ]
    _compile(
        intersect_batched_driver_streamed, args,
        window=WINDOW, interpret=False,
    )


@pytest.mark.parametrize("with_delta", [False, True])
def test_streamed_compiles(one_chip, with_delta):
    s = one_chip
    postings, _, offsets, lengths, block_max = _main_index(s)
    args = [
        _spec(s, Q, WINDOW), _spec(s, Q, WINDOW), _spec(s, Q, WINDOW),
        _spec(s, Q, T), _spec(s, Q, T), _spec(s, Q),
        postings, offsets, lengths, block_max,
    ]
    if with_delta:
        d_post, _, d_off, d_len, d_bm = _delta(s)
        args += [d_post, d_off, d_len, d_bm, _spec(s, Q, WINDOW)]
    _compile(intersect_batched_streamed, args, interpret=False)


def test_merge_delta_windows_compiles(one_chip):
    s = one_chip
    postings, attrs, _, _, _ = _main_index(s)
    args = [
        postings, attrs, _spec(s, Q), _spec(s, Q),   # m_off, m_neff
        *_delta(s),
        _spec(s, Q),                                 # driver terms
    ]
    _compile(merge_delta_windows, args, window=WINDOW, interpret=False)


@pytest.mark.parametrize("k", [10, 1000])
@pytest.mark.parametrize("ns", [2, 4])
def test_merge_topk_rows_compiles(one_chip, k, ns):
    # a tournament round merges 2k candidates; the all-gather merge ns*k
    m = 2 * k if ns == 2 else ns * k
    _compile(merge_topk_rows, [_spec(one_chip, Q, m)], k=k, interpret=False)
