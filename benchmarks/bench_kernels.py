"""Kernel microbenches (interpret-mode on CPU: correctness + op counts;
wall times are indicative only — the TPU path compiles the same kernels).
"""
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.index import INVALID_DOC
from repro.kernels import ops
from repro.kernels.posting_intersect import compute_skip_map


def _timed(fn, *args, reps=3, **kw):
    jax.block_until_ready(fn(*args, **kw))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args, **kw))
    return (time.perf_counter() - t0) / reps


def main():
    rng = np.random.default_rng(0)
    mode = "interpret" if ops.resolve_interpret() else "compiled"

    def sorted_list(n, valid, hi=10**6):
        v = np.sort(rng.choice(hi, size=valid, replace=False)).astype(np.int32)
        return jnp.asarray(np.concatenate([v, np.full(n - valid, INVALID_DOC, np.int32)]))

    a = sorted_list(4096, 4000)
    b = sorted_list(8192, 8000)
    attrs = jnp.asarray(rng.integers(0, 8, size=4096).astype(np.int32))
    dt = _timed(ops.intersect, a, attrs, b, -1, reps=2)
    print(f"kernels,intersect_4kx8k,{dt*1e6:.1f},us_per_call_{mode}")
    # skip-map itself (pure XLA, runs fast everywhere)
    dt = _timed(lambda: compute_skip_map(a, b), reps=5)
    print(f"kernels,skip_map_4kx8k,{dt*1e6:.1f},us_per_call")

    x = jnp.asarray(rng.integers(0, 1 << 30, size=4096).astype(np.int32))
    dt = _timed(ops.sort, x, reps=2)
    print(f"kernels,bitonic_sort_4k,{dt*1e6:.1f},us_per_call_{mode}")

    c = jnp.sort(jnp.asarray(
        rng.integers(0, 1 << 28, size=(16, 128)).astype(np.int32)), axis=1)
    dt = _timed(ops.topk_merge, c, 128, reps=2)
    print(f"kernels,topk_merge_16x128,{dt*1e6:.1f},us_per_call_{mode}")


if __name__ == "__main__":
    main()
