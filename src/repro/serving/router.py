"""Serving-layer routing: set-health-aware batch routing + the ODYS-style
distributed top-k over a vocab-sharded LM head.

**Batch routing** (paper §3.1/§5.2): :class:`HealthAwareRouter` extends the
scheduler's least-loaded multi-set router with the set-granular failover of
:mod:`repro.core.faults` — a dead ODYS set receives no batches (queries are
stateless and the index replicated, so skipping a set is safe) and resumes
receiving them the moment it recovers.  Wire it into
:class:`~repro.serving.scheduler.MasterScheduler` via ``router=`` (the
:class:`~repro.serving.search.SearchService` ``set_health=`` knob does so).

**LM head top-k** (DESIGN.md §3.1): greedy/top-k decoding with the LM head
sharded over the ``model`` axis *is* the ODYS master/slave merge problem —
each shard owns a vocabulary slice ("document partition"), computes its
local top-k ("slave top-k"), and a log-depth tournament merges candidates
("master loser tree").  The naive alternative all-gathers the full (B, V)
logits (V up to 256k for gemma): the ODYS formulation moves k candidates
per shard instead — the collective-term optimization measured in §Perf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.faults import SetHealth
from repro.serving.scheduler import MultiSetRouter, SetState


class HealthAwareRouter(MultiSetRouter):
    """Multi-set router that honors :class:`~repro.core.faults.SetHealth`.

    Routing skips dead sets; :meth:`fail` / :meth:`recover` flip a set's
    health (or mutate the shared ``SetHealth`` directly — e.g. the fault
    simulator's own mask can be passed in).  With every set dead, routing
    raises ``RuntimeError`` exactly like
    :func:`repro.core.faults.route_queries`.
    """

    def __init__(self, n_sets: int, health: SetHealth | None = None):
        super().__init__(n_sets)
        self.health = health if health is not None else SetHealth.all_alive(n_sets)
        if self.health.n_sets != n_sets:
            # an undersized mask would IndexError (or silently misroute)
            # only at route time — fail at construction instead
            raise ValueError(
                f"health mask covers {self.health.n_sets} sets, "
                f"router has {n_sets}"
            )
        self.health.subscribe(self._on_health_change)
        # base __init__ bound the process registry before self.health
        # existed — rebind now so the health instruments come up too
        self.bind_registry(self._registry)

    def bind_registry(self, reg) -> None:
        super().bind_registry(reg)
        self._registry = reg
        self._c_transitions = {
            to: reg.counter(
                "odys_set_health_transitions_total",
                help="set liveness transitions observed by the router",
                to=to,
            )
            for to in ("alive", "dead")
        }
        health = getattr(self, "health", None)
        self._g_alive = {
            s.sid: reg.gauge(
                "odys_set_alive",
                help="1 while the set is routable, 0 while dead",
                set=str(s.sid),
            )
            for s in self.sets
        }
        if health is not None:
            for s in self.sets:
                self._g_alive[s.sid].set(float(bool(health.alive[s.sid])))

    def _on_health_change(self, set_id: int, alive: bool) -> None:
        self._c_transitions["alive" if alive else "dead"].inc()
        g = self._g_alive.get(set_id)
        if g is not None:
            g.set(1.0 if alive else 0.0)

    def _candidates(self) -> list[SetState]:
        alive = [s for s in self.sets if bool(self.health.alive[s.sid])]
        if not alive:
            raise RuntimeError("no ODYS set alive")
        return alive

    def fail(self, set_id: int) -> None:
        self.health.fail(set_id)

    def recover(self, set_id: int) -> None:
        self.health.recover(set_id)


def _merge_scored(av, ai, bv, bi, k: int):
    """Merge two descending (B,k) scored candidate sets -> best k."""
    v = jnp.concatenate([av, bv], axis=-1)
    i = jnp.concatenate([ai, bi], axis=-1)
    topv, sel = lax.top_k(v, k)
    topi = jnp.take_along_axis(i, sel, axis=-1)
    return topv, topi


def tournament_topk_scored(values, indices, axis: str, n: int, k: int):
    """Butterfly merge of per-shard (B,k) candidates over mesh axis."""
    assert n & (n - 1) == 0
    d = 1
    while d < n:
        perm = [(i, i ^ d) for i in range(n)]
        ov = lax.ppermute(values, axis, perm)
        oi = lax.ppermute(indices, axis, perm)
        values, indices = _merge_scored(values, indices, ov, oi, k)
        d *= 2
    return values, indices


@functools.partial(
    jax.jit, static_argnames=("mesh", "k", "axis", "strategy", "batch_axes")
)
def distributed_vocab_topk(
    logits: jnp.ndarray,       # (B, V), sharded (or shardable) over axis
    *,
    mesh: Mesh,
    k: int = 1,
    axis: str = "model",
    strategy: str = "tournament",   # tournament | allgather
    batch_axes=None,                # e.g. ("data",) when B is sharded too
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Global top-k (values, token_ids) of vocab-sharded logits."""
    n = mesh.shape[axis]

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=P(batch_axes, axis),
        out_specs=(P(batch_axes, None), P(batch_axes, None)),
        check_vma=False,
    )
    def run(local):                       # (B, V/n)
        shard = lax.axis_index(axis)
        v_local = local.shape[-1]
        lv, li = lax.top_k(local, k)      # local top-k ("slave" side)
        gi = li + shard * v_local         # local -> global token ids
        if strategy == "tournament":
            return tournament_topk_scored(lv, gi, axis, n, k)
        allv = lax.all_gather(lv, axis, axis=-1, tiled=True)   # (B, n*k)
        alli = lax.all_gather(gi, axis, axis=-1, tiled=True)
        topv, sel = lax.top_k(allv, k)
        return topv, jnp.take_along_axis(alli, sel, axis=-1)

    return run(logits)


def greedy_token(logits, *, mesh: Mesh | None = None, axis="model"):
    """argmax next token; distributed when a mesh is active."""
    if mesh is None or axis not in mesh.axis_names:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    _, idx = distributed_vocab_topk(logits, mesh=mesh, k=1, axis=axis)
    return idx[..., 0].astype(jnp.int32)
