"""The traffic generator: seeded, at the configured rate, and the closed
loop's in-flight count."""
import json
import types

import numpy as np
import pytest

from bench import harness, spec
from bench.traffic import Traffic


def traffic(name: str, **over) -> Traffic:
    s = json.loads((spec.BENCH / "traffic" / f"{name}.json").read_text())
    return Traffic(dict(s, **over), vocab_size=1000, n_sites=10, n_docs=5000)


def test_seed_reproduces_schedule():
    t = traffic("mix-open-ingest")
    a, b = t.schedule(2**33 + 7, 20.0, tail=2.0), t.schedule(2**33 + 7, 20.0, tail=2.0)
    assert a == b
    assert t.schedule(2**33 + 8, 20.0, tail=2.0) != a


@pytest.mark.parametrize("rate", [50.0, 400.0])
def test_open_loop_mean_rate(rate):
    t = traffic("mix-open", rate_qps=rate)
    seconds = 200.0
    for seed in (11, 2**40 + 1):
        events = t.schedule(seed, seconds, tail=5.0)
        inside = [e for e in events if e.t < seconds]
        # every seed offers the configured rate exactly; the tail its share
        assert len(inside) == round(rate * seconds)
        assert len(events) - len(inside) == round(rate * 5.0)
        assert all(0 <= e.t < seconds + 5.0 for e in events)
        assert [e.t for e in events] == sorted(e.t for e in events)
        # arrivals spread evenly: each quarter of the window holds ~1/4
        q = np.histogram([e.t for e in inside], bins=4, range=(0, seconds))[0]
        assert np.all(np.abs(q - rate * seconds / 4) < 5 * np.sqrt(rate * seconds / 4))


def test_mix_shares_and_query_shapes():
    t = traffic("mix-open", rate_qps=500.0)
    qs = [e.query for e in t.schedule(3, 40.0)]
    single = np.mean([q.cond == "single" for q in qs])
    assert abs(single - 0.45) < 0.03
    for q in qs:
        assert len(set(q.terms)) == len(q.terms)
        assert (len(q.terms) == 1) == (q.cond == "single")
        assert (q.site is not None) == (q.cond == "limited")


def test_mutations_target_live_documents():
    t = traffic("mix-open-ingest")
    muts = t.mutations(np.random.default_rng(5), 2000)
    dead, n_docs = set(), 5000
    for m in muts:
        if m.op == "insert":
            n_docs += 1
            continue
        assert 0 <= m.docid < n_docs and m.docid not in dead
        if m.op == "delete":
            dead.add(m.docid)
    shares = [np.mean([m.op == o for m in muts]) for o in ("insert", "delete", "update")]
    assert np.allclose(shares, [0.5, 0.2, 0.3], atol=0.04)
    # Zipf(1) over docIDs: the popular (low) pages change most, though
    # deletes use them up (a uniform draw's median would be ~2,750)
    targets = [m.docid for m in muts if m.op != "insert"]
    assert np.median(targets[:100]) < 500 and np.median(targets) < 1000


class _Ticket:
    def __init__(self, now):
        self.submit_time, self.finish_time = now, None
        self.done, self.result, self.span = False, None, None


class _FakeService:
    """Admits queries and serves the eight oldest a step, recording how
    many were in flight at each step."""

    def __init__(self):
        self.queue, self.seen = [], []
        self.scheduler = types.SimpleNamespace(step=self.step, drain=self.drain,
                                               pending=lambda: len(self.queue))

    def submit(self, terms, site, k):
        tk = _Ticket(0.0)
        self.queue.append(tk)
        return tk

    def step(self):
        self.seen.append(len(self.queue))
        out, self.queue = self.queue[:8], self.queue[8:]
        for tk in out:
            tk.done, tk.finish_time = True, 1e-3
            tk.result = types.SimpleNamespace(docids=[1, 2], n_hits=2)
        return out

    def drain(self):
        out = []
        while self.queue:
            out += self.step()
        return out


def test_closed_loop_keeps_its_outstanding_count():
    t = traffic("mix-closed")
    svc = _FakeService()
    res = harness._closed_loop(svc, t, seed=1, seconds=0.2, trace=False,
                               t_process=0.0)
    assert len(svc.seen) > 10
    # every step but the drain's found the full count in flight
    n_window = len(res["batches"]) - 1
    assert set(svc.seen[:n_window]) == {t.spec["outstanding"]}
    assert res["e2e"]["qps"] > 0
