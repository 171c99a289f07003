"""Bitonic compare-exchange networks on ``(rows, 128)`` VMEM values.

Shared by the master's top-k merge (:mod:`repro.kernels.topk_merge`) and
the main+delta driver merge (:mod:`repro.kernels.delta_merge`).  Element
``e`` of an ``(R, 128)`` array is row ``e // 128``, lane ``e % 128``; its
partner at XOR distance ``d`` is fetched with two rotates — along lanes
when ``d < 128``, along sublanes otherwise — and a lane/sublane mask that
picks the rotate holding ``e ^ d``.  Mosaic lowers rotates and selects;
it cannot lower the flat ``reshape(blocks, 2, d)`` formulation these
networks used before.  ``R * 128`` must be a power of two.

The rotate direction is read off a rotated iota rather than assumed, so
the networks are correct whichever way the backend's rotate turns.  The
same rotate-and-mask step gives :func:`lane_prefix_sum`, which stands in
for ``cumsum`` (Mosaic has none) in the packed codec's decode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def flat_index(shape) -> jnp.ndarray:
    """Row-major element index of every slot of an ``(R, 128)`` array."""
    r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    c = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return r * LANES + c


def _partner(x: jnp.ndarray, d: int) -> jnp.ndarray:
    """``y[e] = x[e ^ d]`` for a power-of-two distance ``d``."""
    axis, step = (1, d) if d < LANES else (0, d // LANES)
    n = x.shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    want = idx ^ step
    a = pltpu.roll(x, step, axis)
    b = pltpu.roll(x, n - step, axis)
    from_a = pltpu.roll(idx, step, axis) == want
    return jnp.where(from_a, a, b)


def lane_prefix_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum along the 128 lanes of each row (log-step
    Hillis-Steele; Mosaic has no cumsum)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    d = 1
    while d < LANES:
        a = pltpu.roll(x, d, 1)
        b = pltpu.roll(x, LANES - d, 1)
        from_a = pltpu.roll(lane, d, 1) == lane - d
        prev = jnp.where(from_a, a, b)          # x[lane - d] where lane >= d
        x = x + jnp.where(lane >= d, prev, 0)
        d *= 2
    return x


def reverse(x: jnp.ndarray) -> jnp.ndarray:
    """Reverse the flat order of an ``(R, 128)`` array (``e -> n-1-e`` is
    XOR with ``n - 1``: one unconditional exchange per index bit)."""
    d = 1
    while d < x.size:
        x = _partner(x, d)
        d *= 2
    return x


def sort(x: jnp.ndarray) -> jnp.ndarray:
    """Ascending bitonic sort of all ``R * 128`` elements."""
    n = x.size
    log_n = n.bit_length() - 1
    assert 1 << log_n == n, "bitonic sort needs a power-of-two size"
    e = flat_index(x.shape)
    for k in range(1, log_n + 1):           # merge size 2^k
        desc = ((e >> k) & 1) == 1          # direction of e's 2^k block
        for j in range(k - 1, -1, -1):      # XOR distance 2^j
            d = 1 << j
            p = _partner(x, d)
            lower = (e & d) == 0
            take_min = (lower & ~desc) | (~lower & desc)
            x = jnp.where(take_min, jnp.minimum(x, p), jnp.maximum(x, p))
    return x


def merge(key, src, payloads=()):
    """Ascending merge of a bitonic ``(key, src)`` sequence: ties break by
    ``src`` (stream id), and ``payloads`` travel with their key."""
    n = key.size
    assert n & (n - 1) == 0, "bitonic merge needs a power-of-two size"
    e = flat_index(key.shape)
    d = n // 2
    while d >= 1:
        pk = _partner(key, d)
        ps = _partner(src, d)
        lower = (e & d) == 0
        gt = (key > pk) | ((key == pk) & (src > ps))
        lt = (key < pk) | ((key == pk) & (src < ps))
        swap = (lower & gt) | (~lower & lt)
        key = jnp.where(swap, pk, key)
        src = jnp.where(swap, ps, src)
        payloads = tuple(jnp.where(swap, _partner(p, d), p) for p in payloads)
        d //= 2
    return key, src, payloads
