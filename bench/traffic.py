"""The one general traffic generator: reads a mix's parameters from its data
file (``bench/traffic/<name>.json``) and draws the queries, their arrivals
and the mutation stream from the seed.

A mix file holds:

- ``loop``: ``"open"`` (Poisson arrivals at ``rate_qps``, a fixed count a
  window, each query timed from its scheduled arrival) or ``"closed"`` (``outstanding`` queries in
  flight; each completion submits the next, timed from submit);
- ``mix``: ``{"<condition>/<k>": share}`` over the conditions ``single``
  (one keyword), ``multiple`` (a keyword AND of 2..``max_terms``) and
  ``limited`` (a keyword AND restricted to one site);
- ``zipf_s``, ``max_terms``: keyword popularity and the widest AND;
- ``mutations`` (open loop only, optional): a Poisson ingest stream at
  ``rate_per_s`` with shares ``p_insert``/``p_delete``/``p_update``, new
  documents of Poisson(``mean_doc_len``) Zipf(``zipf_s``) terms on
  Zipf(``site_zipf_s``) sites, and ``p_site_change`` of updates moving site;
  deletes and updates pick a live page Zipf(``target_zipf_s``) over docIDs
  (rank order, so the popular pages change most; 0 is uniform);
- ``arrivals`` (open loop, optional): ``"poisson"`` (the default: each
  window's count of arrivals uniform in it, a Poisson stream given its
  count) or ``"fixed_set"``: every seed gets the same set of gaps between
  queries (the quantiles of the exponential gap at the query rate), in an
  order drawn from the seed, and each mutation the same set of waits
  until the next query arrives (the quantiles of the exponential
  residual, as a Poisson stream independent of the queries would give),
  placed at a time drawn from the seed; so a tail over the mutations'
  waits reads the same work on every seed;
- ``assumed``: the reason for each value, read by no code.

Queries draw keywords as ODYS's workload generator does (Zipf over term
ranks, distinct within a query; sites uniform), by inverse CDF so that a
stream of tens of thousands takes milliseconds.  Deletes and updates
target documents that are live at that point of the stream.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator

import numpy as np

CONDITIONS = ("single", "multiple", "limited")


@dataclasses.dataclass(frozen=True)
class Query:
    terms: tuple[int, ...]
    site: int | None
    k: int
    cond: str


@dataclasses.dataclass(frozen=True)
class Mutation:
    op: str                       # insert | delete | update
    docid: int | None             # target (delete/update); the writer assigns inserts
    terms: tuple[int, ...] | None
    site: int | None              # insert: its site; update: new site or None


@dataclasses.dataclass(frozen=True)
class Event:
    t: float                      # seconds after the window opens
    query: Query | None = None
    mutation: Mutation | None = None


def _cdf(n: int, s: float) -> np.ndarray:
    p = np.arange(1, n + 1, dtype=np.float64) ** (-s)
    cdf = np.cumsum(p / p.sum())
    cdf[-1] = 1.0
    return cdf


def _zipf(rng, cdf: np.ndarray, size) -> np.ndarray:
    return np.minimum(
        np.searchsorted(cdf, rng.random(size), side="right"), cdf.shape[0] - 1
    )


class Traffic:
    """A mix's parameters plus the corpus dimensions it draws from."""

    def __init__(self, spec: dict, *, vocab_size: int, n_sites: int, n_docs: int):
        self.spec = spec
        self.loop = spec["loop"]
        if self.loop not in ("open", "closed"):
            raise ValueError(f"loop must be open or closed, got {self.loop!r}")
        kinds, shares = [], []
        for key, share in spec["mix"].items():
            cond, k = key.split("/")
            if cond not in CONDITIONS:
                raise ValueError(f"unknown condition {cond!r}")
            kinds.append((cond, int(k)))
            shares.append(float(share))
        self.kinds = kinds
        self.shares = np.asarray(shares) / np.sum(shares)
        self.max_terms = int(spec["max_terms"])
        self.vocab_size, self.n_sites, self.n_docs = vocab_size, n_sites, n_docs
        self.term_cdf = _cdf(vocab_size, float(spec["zipf_s"]))
        self.mut = spec.get("mutations")
        if self.mut is not None and self.loop != "open":
            raise ValueError("a mutation stream needs an open loop")
        if spec.get("arrivals", "poisson") not in ("poisson", "fixed_set"):
            raise ValueError(f"arrivals must be poisson or fixed_set, got {spec['arrivals']!r}")

    # -- queries -------------------------------------------------------

    def _terms(self, rng, n: int) -> tuple[int, ...]:
        out: list[int] = []
        while len(out) < n:
            t = int(_zipf(rng, self.term_cdf, 1)[0])
            if t not in out:
                out.append(t)
        return tuple(out)

    def query(self, rng) -> Query:
        cond, k = self.kinds[int(rng.choice(len(self.kinds), p=self.shares))]
        nt = 1 if cond == "single" else int(rng.integers(2, self.max_terms + 1))
        site = int(rng.integers(0, self.n_sites)) if cond == "limited" else None
        return Query(self._terms(rng, nt), site, k, cond)

    def queries(self, rng) -> Iterator[Query]:
        while True:
            yield self.query(rng)

    def warmup_queries(self, rng) -> list[Query]:
        """One query of each (condition, k) kind: every compiled shape."""
        out = []
        for cond, k in self.kinds:
            nt = 1 if cond == "single" else 2
            site = 0 if cond == "limited" else None
            out.append(Query(self._terms(rng, nt), site, k, cond))
        return out

    # -- the open loop's schedule --------------------------------------

    def schedule(self, seed: int, seconds: float, tail: float = 0.0) -> list[Event]:
        """Queries and mutations due in ``[0, seconds + tail)``, in time
        order.  Every seed gets the same number of each in ``[0, seconds)``
        and in the tail (the rate times the length): a Poisson stream given
        its count, so that seeds change the order and the draws, not the
        amount of work."""
        if self.loop != "open":
            raise ValueError("only an open loop has a schedule")
        q_rng, m_rng = (np.random.default_rng(s) for s in
                        np.random.SeedSequence(seed).spawn(2))
        rate = float(self.spec["rate_qps"])
        fixed = self.spec.get("arrivals", "poisson") == "fixed_set"
        q_times = (_fixed_arrivals(q_rng, rate, seconds, tail) if fixed
                   else _arrivals(q_rng, rate, seconds, tail))
        events = [Event(t, query=self.query(q_rng)) for t in q_times]
        if self.mut is not None:
            m_rate = float(self.mut["rate_per_s"])
            times = (_fixed_waits(m_rng, m_rate, rate, q_times, seconds, tail) if fixed
                     else _arrivals(m_rng, m_rate, seconds, tail))
            events += [Event(t, mutation=m)
                       for t, m in zip(times, self.mutations(m_rng, len(times)))]
        events.sort(key=lambda e: e.t)
        return events

    def mutations(self, rng, n: int) -> list[Mutation]:
        m = self.mut
        p = np.asarray([m["p_insert"], m["p_delete"], m["p_update"]], float)
        ops = rng.choice(3, size=n, p=p / p.sum())
        doc_cdf = _cdf(self.vocab_size, float(m["zipf_s"]))
        site_cdf = _cdf(self.n_sites, float(m["site_zipf_s"]))
        n_docs, dead = self.n_docs, set()
        out = []

        def doc_terms():
            n_t = max(1, int(rng.poisson(float(m["mean_doc_len"]))))
            return tuple(int(t) for t in np.unique(_zipf(rng, doc_cdf, n_t)))

        target_s = float(m["target_zipf_s"])

        def live_target():
            while True:
                d = _zipf_rank(rng, n_docs, target_s)
                if d not in dead:
                    return d

        for op in ops:
            if op == 0:
                out.append(Mutation("insert", None, doc_terms(),
                                    int(_zipf(rng, site_cdf, 1)[0])))
                n_docs += 1
            elif op == 1:
                d = live_target()
                dead.add(d)
                out.append(Mutation("delete", d, None, None))
            else:
                d = live_target()
                terms = doc_terms()
                site = (int(_zipf(rng, site_cdf, 1)[0])
                        if rng.random() < float(m["p_site_change"]) else None)
                out.append(Mutation("update", d, terms, site))
        return out


def _zipf_rank(rng, n: int, s: float) -> int:
    """A rank in ``[0, n)``, Zipf(s), by the inverse of the continuous power
    law ``x**-s`` on ``[1, n + 1)``, floored (no table of ``n`` entries)."""
    u = rng.random()
    x = (n + 1.0) ** u if s == 1.0 else (1.0 + u * ((n + 1.0) ** (1.0 - s) - 1.0)) ** (1.0 / (1.0 - s))
    return min(int(x) - 1, n - 1)


def _arrivals(rng, rate: float, seconds: float, tail: float) -> list[float]:
    """``round(rate * seconds)`` arrival times uniform in ``[0, seconds)``
    and ``round(rate * tail)`` in ``[seconds, seconds + tail)``, sorted."""
    head = rng.uniform(0.0, seconds, size=int(round(rate * seconds)))
    rest = seconds + rng.uniform(0.0, tail, size=int(round(rate * tail)))
    return [float(x) for x in np.sort(np.concatenate([head, rest]))]


def _exp_quantiles(rate: float, n: int) -> np.ndarray:
    """The ``n`` quantiles of an exponential of ``rate`` at ``(i + 0.5) / n``."""
    return -np.log1p(-(np.arange(n) + 0.5) / n) / rate


def _fixed_arrivals(rng, rate: float, seconds: float, tail: float) -> list[float]:
    """As ``_arrivals``, the same counts, but every seed the same set of
    gaps: in each span (the window, the tail) of ``n`` arrivals, ``n + 1``
    exponential quantiles in an order drawn from ``rng``, scaled to fill
    the span, the last gap running to its end."""
    out: list[float] = []
    for start, span in ((0.0, seconds), (seconds, tail)):
        n = int(round(rate * span))
        if n:
            g = rng.permutation(_exp_quantiles(rate, n + 1))
            out += [float(x) for x in start + np.cumsum(g * (span / g.sum()))[:-1]]
    return out


def _fixed_waits(rng, rate: float, q_rate: float, q_times: list[float],
                 seconds: float, tail: float) -> list[float]:
    """``round(rate * span)`` mutation times in each span (the window, the
    tail), sorted, each one's wait until the next query arrival taken from
    the span's exponential quantiles at the query rate, in an order drawn
    from ``rng``.  A mutation with wait ``r`` and a time drawn uniform in the
    span goes before the first query of the span at or after that time
    whose gap from the query before it (or from the span's start) is over
    ``r``; where there is none, before the last such query."""
    q = np.asarray(q_times)
    out: list[float] = []
    for start, span in ((0.0, seconds), (seconds, tail)):
        n = int(round(rate * span))
        if not n:
            continue
        a = q[(q >= start) & (q < start + span)]
        gaps = np.diff(a, prepend=start)
        waits = rng.permutation(_exp_quantiles(q_rate, n))
        anchors = rng.uniform(start, start + span, size=n)
        for r, u in zip(waits, anchors):
            fits = np.flatnonzero(gaps > r)
            if not fits.size:
                raise ValueError(f"no gap between queries holds a wait of {r:.3f} s: "
                                 "fixed_set needs fewer mutations than queries")
            after = fits[a[fits] - r >= u]
            out.append(float(a[after[0] if after.size else fits[-1]] - r))
    return sorted(out)
