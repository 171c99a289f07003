"""Engine backend comparison: jnp reference join vs batched Pallas kernel.

Sweeps posting-window size and term count.  On CPU the Pallas path runs
under the interpreter, so its wall times measure semantics, not speed —
the jnp column is the meaningful CPU baseline and the kernel column becomes
meaningful on a TPU backend (where interpret=False compiles Mosaic).
The skipped-DMA fraction is reported alongside: that is the quantity the
paper's posting-skipping argument (§2, Fig 4) says the kernel should win by.
"""
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.engine import make_query_batch, query_topk
from repro.core.index import build_index
from repro.data.corpus import CorpusConfig, generate_corpus
from repro.kernels import ops


def _timed(fn, *args, reps=3, **kw):
    jax.block_until_ready(fn(*args, **kw))  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args, **kw))
    return (time.perf_counter() - t0) / reps


def main():
    corpus = generate_corpus(
        CorpusConfig(n_docs=20_000, vocab_size=2_000, mean_doc_len=60,
                     n_sites=50, seed=3)
    )
    idx, meta = build_index(corpus)
    rng = np.random.default_rng(0)

    mode = "interpret" if ops.resolve_interpret() else "compiled"
    for n_terms in (1, 2, 3):
        q = [
            (list(rng.integers(0, 64, size=n_terms)), None)
            for _ in range(8)
        ]
        qb = make_query_batch(q, t_max=4, meta=meta)
        for window in (1024, 2048, 4096):
            dt = _timed(query_topk, idx, qb, k=10, window=window,
                        backend="jnp", reps=2)
            print(f"backends,topk_t{n_terms}_w{window}_jnp,"
                  f"{dt/len(q)*1e6:.1f},per_query_us")
            dt = _timed(query_topk, idx, qb, k=10, window=window,
                        backend="pallas", reps=2)
            print(f"backends,topk_t{n_terms}_w{window}_pallas,"
                  f"{dt/len(q)*1e6:.1f},per_query_us_{mode}")

    # DMA-skip effectiveness over window size (dense-vs-dense lists).
    o = np.asarray(idx.offsets)
    post = np.asarray(idx.postings)
    for window in (1024, 2048, 4096):
        a = jnp.asarray(post[o[1]:o[1] + window])
        b = jnp.asarray(post[o[0]:o[0] + window])
        frac = float(ops.skip_fraction(a, b))
        print(f"backends,skip_fraction_w{window},{frac:.4f},tiles_skipped")


if __name__ == "__main__":
    main()
