"""ODYS master/slave parallel query processing, on a JAX mesh.

Paper architecture (§3.1): masters broadcast each query to all
shared-nothing slaves; each slave runs the query over its document
partition and returns its local top-k; the master merges ns sorted streams
into the global top-k.

Mesh mapping (DESIGN.md §2):

- slaves            -> shards along the ``data`` mesh axis (one document
                       partition per device), index arrays sharded on their
                       leading axis;
- query broadcast   -> queries replicated over ``data`` (in_specs=P(None));
- master merge      -> a collective over ``data``.  Two strategies:

  * ``allgather``  — the paper-faithful centralized master: every shard's
    k candidates are all-gathered (ns·k ids per device) and reduced with a
    single top-k.  Models the master as a point of convergence.
  * ``tournament`` — beyond-paper: a butterfly of log2(ns) ppermute rounds;
    at each round partners exchange k candidates and keep the best k.
    Bytes on the busiest link drop from ns·k to k·log2(ns) — the
    loser-tree's O(k log ns) compare count, achieved in *communication*.

  Both merge strategies honor the same ``backend`` flag as the slave join:
  under ``backend="pallas"`` the per-round best-k reduction runs the
  bitonic top-k merge kernel (kernels/topk_merge.py) instead of jnp.sort.

- online updates (repro.indexing) -> an optional ShardedDelta rides next
  to the index with the same P(axis) sharding; each slave then answers
  with merge-on-read over its main partition + delta, so mutations are
  visible to live traffic without rebuilding or resharding the main index.
  Inside shard_map each slave builds its PostingSource (static or merged;
  see repro.core.engine) from the local index + delta slice, so the
  streaming kernels run per-shard unchanged — the distributed layer only
  moves pytrees, never posting windows.  Since the read path became
  fully streamed, that is a structural invariant of the whole engine:
  below this layer the only per-query buffers that exist at all are the
  kernel *outputs* (driver window + mask, k candidates); every posting
  read inside a slave is a tile-granular scan of that slave's resident
  flat arrays, which is what makes per-shard service time track the
  paper's sequential-scan slave cost model (Formula (7)) rather than a
  gather-bound memory system.

- ODYS sets (§3.1 fault tolerance) -> the ``pod`` axis: each pod is an
  independent replica engine; the query stream is sharded across pods and
  no collective crosses them on the query path (see
  :func:`replicated_query_topk`).

Named scopes: each slave's local top-k (its join and the local->global
docID map) runs under ``jax.named_scope(SLAVE_SCOPE)``, the master merge
(the ppermute/all-gather exchange, its top-k merges) and the ``psum`` of
``n_hits`` under ``jax.named_scope(MERGE_SCOPE)``, so a profiler shows the
set's work by name (the ops' ``tf_op`` metadata).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.engine import QueryBatch, query_topk
from repro.core.index import (
    InvertedIndex,
    ShardedIndex,
    local_to_global_docids,
)
from repro.indexing.delta import DeltaIndex, ShardedDelta, local_delta


SLAVE_SCOPE = "odys.slave_topk"
MERGE_SCOPE = "odys.set_merge"


class SearchResult(NamedTuple):
    docids: jnp.ndarray  # int32[Q, k] global docIDs, ascending (= rank order)
    n_hits: jnp.ndarray  # int32[Q]    total matches across all shards


def _local_index(stacked: ShardedIndex) -> InvertedIndex:
    """Inside shard_map each device sees a leading shard dim of 1."""
    return InvertedIndex(*(x[0] for x in stacked))


def place_index(index, mesh: Mesh):
    """Lay a stacked index (or delta) over ``mesh``'s ``data`` axis, shard
    ``s`` on the axis's ``s``-th device.  From host leaves each device
    receives only its own shard's rows."""
    return jax.device_put(index, NamedSharding(mesh, P("data")))


def _slave_topk(idx: ShardedIndex, qb: QueryBatch, dlt, *, axis: str,
                ns: int, **engine) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One slave's answer inside ``shard_map``: its local top-k as global
    docIDs, and its hit count."""
    with jax.named_scope(SLAVE_SCOPE):
        shard = lax.axis_index(axis)
        ldelta = None if dlt is None else local_delta(dlt)
        docs, hits = query_topk(_local_index(idx), qb, delta=ldelta, **engine)
        return local_to_global_docids(docs, shard, ns), hits


def _set_merge(gdocs: jnp.ndarray, hits: jnp.ndarray, *, axis: str, ns: int,
               merge: str, backend: str,
               interpret: bool | None) -> SearchResult:
    """The master merge of the slaves' answers, on every device."""
    with jax.named_scope(MERGE_SCOPE):
        if merge == "tournament":
            merged = tournament_merge(
                gdocs, axis, ns, backend=backend, interpret=interpret
            )
        elif merge == "allgather":
            merged = allgather_merge(
                gdocs, axis, backend=backend, interpret=interpret
            )
        else:
            raise ValueError(merge)
        return SearchResult(merged, lax.psum(hits, axis))


def _row_topk(cands: jnp.ndarray, k: int, backend: str,
              interpret: bool | None) -> jnp.ndarray:
    """Per-query best-k of concatenated candidates, ascending.

    ``backend="pallas"`` runs the bitonic top-k merge kernel
    (:func:`repro.kernels.topk_merge.merge_topk_rows`) — the same flag the
    slave join honors, closing the ROADMAP item on the master merge.
    """
    if backend == "pallas":
        from repro.kernels import ops

        shape = cands.shape
        rows = cands.reshape(-1, shape[-1])
        out = ops.topk_merge_rows(rows, k, interpret=interpret)
        return out.reshape(*shape[:-1], k)
    return jnp.sort(cands, axis=-1)[..., :k]


def _merge_pair(a: jnp.ndarray, b: jnp.ndarray, *, backend: str = "jnp",
                interpret: bool | None = None) -> jnp.ndarray:
    """Merge two ascending (Q, k) candidate sets -> best-k ascending."""
    k = a.shape[-1]
    return _row_topk(
        jnp.concatenate([a, b], axis=-1), k, backend, interpret
    )


def tournament_merge(cands: jnp.ndarray, axis: str, ns: int, *,
                     backend: str = "jnp",
                     interpret: bool | None = None) -> jnp.ndarray:
    """Butterfly top-k merge over mesh axis ``axis`` (ns must be a pow2)."""
    assert ns & (ns - 1) == 0, "tournament merge needs power-of-two shards"
    d = 1
    while d < ns:
        perm = [(i, i ^ d) for i in range(ns)]
        other = lax.ppermute(cands, axis, perm)
        cands = _merge_pair(cands, other, backend=backend, interpret=interpret)
        d *= 2
    return cands


def allgather_merge(cands: jnp.ndarray, axis: str, *, backend: str = "jnp",
                    interpret: bool | None = None) -> jnp.ndarray:
    """Paper-faithful centralized merge: gather all, one top-k."""
    k = cands.shape[-1]
    allc = lax.all_gather(cands, axis, axis=0)          # (ns, Q, k)
    allc = jnp.moveaxis(allc, 0, -2).reshape(*cands.shape[:-1], -1)
    return _row_topk(allc, k, backend, interpret)


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "ns", "k", "window", "attr_strategy", "merge", "axis",
        "backend", "interpret",
    ),
)
def distributed_query_topk(
    index: ShardedIndex,
    batch: QueryBatch,
    delta: ShardedDelta | None = None,
    *,
    mesh: Mesh,
    ns: int,
    k: int = 10,
    window: int = 4096,
    attr_strategy: str = "embed",
    merge: str = "tournament",
    axis: str = "data",
    backend: str = "jnp",
    interpret: bool | None = None,
) -> SearchResult:
    """Broadcast the batch to all shards, local top-k, merge to global top-k.

    ``delta`` attaches the per-shard online-update deltas
    (:class:`~repro.indexing.delta.ShardedDelta`, sharded over the same
    mesh axis as the index): every slave runs merge-on-read over its main
    partition + delta, so live traffic sees inserts/updates/deletes at the
    next batch without an index rebuild.

    ``backend``/``interpret`` select the execution engine on BOTH sides of
    the paper's architecture (see :func:`repro.core.engine.query_topk`):
    ``backend="pallas"`` runs the block-skipping join kernel on every
    slave, inside ``shard_map``, and the bitonic top-k merge kernel in the
    master merge.
    """

    index_spec = jax.tree.map(lambda _: P(axis), index)
    batch_spec = jax.tree.map(lambda _: P(), batch)
    delta_spec = jax.tree.map(lambda _: P(axis), delta)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(index_spec, batch_spec, delta_spec),
        out_specs=SearchResult(P(), P()),
        check_vma=False,
    )
    def run(idx: ShardedIndex, qb: QueryBatch, dlt) -> SearchResult:
        gdocs, hits = _slave_topk(
            idx, qb, dlt, axis=axis, ns=ns, k=k, window=window,
            attr_strategy=attr_strategy, backend=backend, interpret=interpret,
        )
        return _set_merge(gdocs, hits, axis=axis, ns=ns, merge=merge,
                          backend=backend, interpret=interpret)

    return run(index, batch, delta)


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "ns", "k", "window", "attr_strategy", "axis",
        "backend", "interpret",
    ),
)
def slave_topk_unmerged(
    index: ShardedIndex,
    batch: QueryBatch,
    delta: ShardedDelta | None = None,
    *,
    mesh: Mesh,
    ns: int,
    k: int = 10,
    window: int = 4096,
    attr_strategy: str = "embed",
    axis: str = "data",
    backend: str = "jnp",
    interpret: bool | None = None,
) -> SearchResult:
    """Slave phase only: per-shard local top-k with NO master merge.

    Returns stacked per-shard candidates — ``docids`` int32[ns, Q, k]
    (already globalized) and ``n_hits`` int32[ns, Q].  This is the
    calibration probe (:mod:`repro.core.calibrate`): timing it against
    :func:`distributed_query_topk` on the same batch isolates the master's
    merge + dispatch cost (Formula (4)'s ``ST_master``) from the slave
    service time, which is what lets the hybrid perf model be fitted from
    the live engine instead of the paper's Table 3.
    """
    index_spec = jax.tree.map(lambda _: P(axis), index)
    batch_spec = jax.tree.map(lambda _: P(), batch)
    delta_spec = jax.tree.map(lambda _: P(axis), delta)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(index_spec, batch_spec, delta_spec),
        out_specs=SearchResult(P(axis), P(axis)),
        check_vma=False,
    )
    def run(idx: ShardedIndex, qb: QueryBatch, dlt) -> SearchResult:
        gdocs, hits = _slave_topk(
            idx, qb, dlt, axis=axis, ns=ns, k=k, window=window,
            attr_strategy=attr_strategy, backend=backend, interpret=interpret,
        )
        return SearchResult(gdocs[None], hits[None])

    return run(index, batch, delta)


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "ns", "k", "window", "attr_strategy", "merge", "axis",
        "pod_axis", "backend", "interpret",
    ),
)
def replicated_query_topk(
    index: ShardedIndex,
    batch: QueryBatch,
    delta: ShardedDelta | None = None,
    *,
    mesh: Mesh,
    ns: int,
    k: int = 10,
    window: int = 4096,
    attr_strategy: str = "embed",
    merge: str = "tournament",
    axis: str = "data",
    pod_axis: str = "pod",
    backend: str = "jnp",
    interpret: bool | None = None,
) -> SearchResult:
    """Multi-pod serving: each pod is an independent ODYS set (replica).

    The index — and the online-update ``delta``, when attached — is
    replicated across pods (sharded over ``data`` inside each pod); the
    *query stream* is sharded over pods.  No collective crosses the pod
    axis on the query path — the paper's ODYS-set isolation, which is also
    what makes set-granular failover trivial (core/faults.py).
    """
    index_spec = jax.tree.map(lambda _: P(None, axis), _stack_for_pods(index))
    batch_spec = jax.tree.map(lambda _: P(pod_axis), batch)
    pod_delta = None if delta is None else ShardedDelta(
        *(x[None] for x in delta)
    )
    delta_spec = jax.tree.map(lambda _: P(None, axis), pod_delta)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(index_spec, batch_spec, delta_spec),
        out_specs=SearchResult(P(pod_axis), P(pod_axis)),
        check_vma=False,
    )
    def run(idx, qb: QueryBatch, dlt) -> SearchResult:
        gdocs, hits = _slave_topk(
            ShardedIndex(*(x[0] for x in idx)), qb,
            None if dlt is None else ShardedDelta(*(x[0] for x in dlt)),
            axis=axis, ns=ns, k=k, window=window,
            attr_strategy=attr_strategy, backend=backend, interpret=interpret,
        )
        return _set_merge(gdocs, hits, axis=axis, ns=ns, merge=merge,
                          backend=backend, interpret=interpret)

    return run(_stack_for_pods(index), batch, pod_delta)


def _stack_for_pods(index: ShardedIndex) -> ShardedIndex:
    """Add a size-1 pod axis (replicated) in front of the shard axis."""
    return ShardedIndex(*(x[None] for x in index))


def set_mesh_slices(
    n_sets: int, ns: int, devices=None
) -> "list[Mesh]":
    """Carve ``n_sets`` disjoint ``(1, ns)`` ``("pod", "data")`` meshes out
    of the device pool — one independent ODYS set per slice.

    This is the paper's §5.2 scale-out as *device topology* rather than
    time-sharing: each set serves its batches on its own device subset
    (through :func:`replicated_query_topk` with the slice as the mesh), so
    adding a set adds real concurrent capacity, and a set-granular fault
    (core/faults.py) quarantines exactly one slice.  Slices are contiguous
    runs of ``devices`` (default: ``jax.devices()``); a pool smaller than
    ``n_sets * ns`` raises rather than silently overlapping sets.
    """
    if n_sets < 1 or ns < 1:
        raise ValueError(f"need n_sets >= 1 and ns >= 1, got {n_sets}x{ns}")
    devs = list(jax.devices()) if devices is None else list(devices)
    need = n_sets * ns
    if len(devs) < need:
        raise ValueError(
            f"{n_sets} sets x {ns} shards need {need} devices, "
            f"have {len(devs)} (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} for host runs)"
        )
    return [
        jax.make_mesh(
            (1, ns), ("pod", "data"), devices=devs[i * ns:(i + 1) * ns]
        )
        for i in range(n_sets)
    ]


# ---------------------------------------------------------------------------
# Reference oracle for the distributed path
# ---------------------------------------------------------------------------

def sequential_reference(
    shard_indexes: list[InvertedIndex],
    batch: QueryBatch,
    *,
    ns: int,
    k: int,
    window: int,
    attr_strategy: str = "embed",
    deltas: list[DeltaIndex] | None = None,
    backend: str = "jnp",
    interpret: bool | None = None,
    codec: str = "raw",
) -> SearchResult:
    """Run each shard sequentially on one device and merge on host —
    the oracle for :func:`distributed_query_topk`.  ``deltas`` supplies
    the per-shard online-update deltas (``DeltaWriter.shard_deltas()``)."""
    all_cands, all_hits = [], []
    for s, idx in enumerate(shard_indexes):
        docs, hits = query_topk(
            idx, batch,
            delta=None if deltas is None else deltas[s],
            k=k, window=window, attr_strategy=attr_strategy,
            backend=backend, interpret=interpret, codec=codec,
        )
        all_cands.append(local_to_global_docids(docs, jnp.int32(s), ns))
        all_hits.append(hits)
    cands = jnp.concatenate(all_cands, axis=-1)  # (Q, ns*k)
    merged = jnp.sort(cands, axis=-1)[..., :k]
    return SearchResult(merged, sum(all_hits))
