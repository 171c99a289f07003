"""The benchmark's own corpus generator: a seeded synthetic web crawl, made
on the device by one jitted program, a chunk of documents a call.

Every document holds a Poisson(``mean_doc_len``) number of term draws, at
least one; terms are Zipf(``zipf_s``) over ``vocab_size`` ranks (term id 0
is the most frequent); a document keeps each term once.  Sites are
Zipf(``site_zipf_s``) over ``n_sites``.  DocIDs are rank order, as ODYS's
query-independent ranking assumes.  These are the statistics of the
program's own generator (``repro.data.corpus``), with both Zipf laws drawn
by the continuous inverse (:func:`draw_zipf`), so that a 3M-document crawl
takes seconds on the device.

Shapes are fixed by the configuration alone (each document has room for
``MAX_DRAWS`` draws, the tail masked), so every seed runs the same
compiled program; and every seed makes the same documents in another rank
order (:data:`STRUCTURE_SEED`), so every index has the same shapes.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class CorpusShape:
    n_docs: int
    vocab_size: int
    mean_doc_len: int
    zipf_s: float
    n_sites: int
    site_zipf_s: float

    @classmethod
    def from_config(cls, cfg: dict) -> "CorpusShape":
        return cls(**{f.name: cfg[f.name] for f in dataclasses.fields(cls)})


@dataclasses.dataclass
class HostCorpus:
    """The generated crawl on the host: per-document term sets (CSR)."""

    doc_offsets: np.ndarray   # int64[n_docs + 1]
    doc_terms: np.ndarray     # int32[nnz], ascending within each document
    doc_site: np.ndarray      # int32[n_docs]
    shape: CorpusShape


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a non-negative seed, including one past 32 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def draw_zipf(key, n: int, s: float, shape) -> jax.Array:
    """int32 ranks in ``[0, n)`` (0 the most frequent), Zipf(s) by the
    inverse of the continuous power law ``x**-s`` on ``[1, n + 1)``,
    floored: elementwise, where a table lookup per draw would take the
    device a minute for a 3M-document crawl."""
    u = jax.random.uniform(key, shape, dtype=jnp.float32)
    a = 1.0 - s
    top = (n + 1.0) ** a
    x = (1.0 + u * (top - 1.0)) ** (1.0 / a)
    return jnp.clip(jnp.floor(x).astype(jnp.int32) - 1, 0, n - 1)


#: Documents made by one call of the jitted generator; the crawl is made
#: chunk by chunk so that the generator's own buffers stay small.
CHUNK_DOCS = 1 << 18
#: Draws a document can hold: Poisson(64) passes 128 about once in 1e15.
MAX_DRAWS = 128


@functools.partial(jax.jit, static_argnames=("shape", "n"))
def _chunk(key, n_live, *, shape: CorpusShape, n: int):
    """``n`` documents (the first ``n_live`` real): each row holds the
    document's terms ascending with repeats and unused slots set to
    ``vocab_size``; their sites; the longest draw count (checked on the
    host)."""
    k_len, k_term, k_site = jax.random.split(key, 3)
    lens = jnp.maximum(1, jax.random.poisson(k_len, shape.mean_doc_len, (n,)))
    lens = jnp.where(jnp.arange(n) < n_live, lens, 0).astype(jnp.int32)
    draws = draw_zipf(k_term, shape.vocab_size, shape.zipf_s, (n, MAX_DRAWS))
    col = jnp.arange(MAX_DRAWS, dtype=jnp.int32)[None, :]
    row = lax.sort(jnp.where(col < lens[:, None], draws, shape.vocab_size), dimension=1)
    repeat = jnp.concatenate([jnp.zeros((n, 1), bool), row[:, 1:] == row[:, :-1]], axis=1)
    rows = jnp.where(repeat, shape.vocab_size, row)
    sites = draw_zipf(k_site, shape.n_sites, shape.site_zipf_s, (n,))
    return rows, sites, lens.max()


#: The documents' contents come from this key for every seed; the seed
#: decides their ranks (docIDs).  So every seed's index has the same list
#: lengths, and with them the same array shapes: the query programs that
#: one run compiles serve every later run from the persistent cache.
STRUCTURE_SEED = 0


def generate(shape: CorpusShape, seed: int) -> HostCorpus:
    """Make the crawl for ``seed`` on the default device; return it on the
    host.  The seed permutes which document holds which rank."""
    key = seed_key(STRUCTURE_SEED)
    n = min(shape.n_docs, CHUNK_DOCS)
    lives = [min(n, shape.n_docs - lo) for lo in range(0, shape.n_docs, n)]
    outs = [_chunk(jax.random.fold_in(key, c), live, shape=shape, n=n)
            for c, live in enumerate(lives)]
    counts, terms, sites = [], [], []
    for live, out in zip(lives, outs):
        rows, st, longest = jax.device_get(out)
        if int(longest) > MAX_DRAWS:
            raise RuntimeError(f"a document drew {int(longest)} terms, past {MAX_DRAWS}")
        keep = rows[:live] < shape.vocab_size
        counts.append(keep.sum(axis=1))
        terms.append(rows[:live][keep])
        sites.append(st[:live])
    counts = np.concatenate(counts)
    terms = np.concatenate(terms).astype(np.int32)
    starts = np.zeros(shape.n_docs + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    # new docID d holds old document perm[d]
    perm = np.random.default_rng(seed_sequence(seed)).permutation(shape.n_docs)
    lens = counts[perm]
    offsets = np.zeros(shape.n_docs + 1, np.int64)
    np.cumsum(lens, out=offsets[1:])
    src = np.repeat(starts[perm] - offsets[:-1], lens) + np.arange(offsets[-1])
    return HostCorpus(
        doc_offsets=offsets,
        doc_terms=terms[src],
        doc_site=np.concatenate(sites).astype(np.int32)[perm],
        shape=shape,
    )


def seed_sequence(seed: int) -> np.random.SeedSequence:
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.random.SeedSequence(seed)
