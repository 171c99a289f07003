"""Search serving front-end: a thin façade over the unified master pipeline.

The ODYS master's admission path (paper §3.1/§4.1) lives in
:class:`repro.serving.scheduler.MasterScheduler`; this module binds it to
the distributed query engine.  A submitted ``(terms, site)`` query is
admitted to a ``(t_max, k)`` bucket, checked against the version-stamped
LRU result cache, micro-batched (partial batches padded with inert
queries so device shapes never change), routed across the replicated
sets, executed with :func:`repro.core.parallel.distributed_query_topk`,
and merged — one pipeline whether the caller uses the synchronous
:meth:`SearchService.search` or the async-style
:meth:`~SearchService.submit` / :meth:`~SearchService.drain` pair.

The execution backend (pure-jnp reference vs the batched block-skipping
Pallas kernel) is a constructor knob, so the same service object serves
CPU CI (``backend="pallas", interpret=True``) and TPU production
(``backend="pallas"``) without touching the query path.

**Online updates** (repro.indexing): constructing the service with
``updatable=True`` (or passing an existing :class:`DeltaWriter`) attaches
the transactional write path.  :meth:`SearchService.insert` /
:meth:`~SearchService.delete` / :meth:`~SearchService.update` mutate the
delta; the next dispatched batch snapshots it and every slave answers
with merge-on-read, so live traffic sees each mutation at the following
batch — the paper's "no batch rebuild" freshness story.  Every mutation
bumps the writer version, which lazily invalidates cached results
(:class:`~repro.serving.scheduler.ResultCache`), so the cache never
serves across a mutation.  :meth:`SearchService.compact` (or
``auto_compact``) folds a filled delta back into a fresh main index
between batches, optionally handing the writer a larger
``doc_headroom``/``term_capacity`` generation — the main index recompiles
at a compaction boundary anyway, so the delta may change shape there too.
"""
from __future__ import annotations

import dataclasses
import time
from collections import defaultdict

import numpy as np

import jax

from repro.core.engine import make_query_batch
from repro.core.index import INVALID_DOC, IndexMeta, ShardedIndex
from repro.core.parallel import (
    SearchResult,
    distributed_query_topk,
    place_index,
    replicated_query_topk,
)
from repro.data.corpus import Corpus
from repro.indexing.compaction import compact as _compact
from repro.indexing.delta import DeltaWriter
from repro.obs.registry import MetricsRegistry, get_registry
from repro.obs.trace import PhaseClock, annotation
from repro.serving.scheduler import MasterScheduler, QueryTicket


@dataclasses.dataclass
class SearchHit:
    """One query's merged result: global docIDs in rank order."""

    docids: list[int]
    n_hits: int


class SearchService:
    """Serve search queries over a sharded index on a device mesh.

    Engine parameters mirror :func:`distributed_query_topk`; ``backend``
    selects the execution engine for the slave join *and* the master merge
    (see :func:`repro.core.engine.query_topk`).

    Scheduler parameters (the unified master pipeline):

    - ``batch_size`` — queries per dispatched micro-batch;
    - ``t_max_buckets`` — padded-width buckets for dynamic batch formation
      (default: the single bucket ``(t_max,)``, i.e. the legacy behavior);
    - ``cache_size`` — LRU result-cache capacity (0 disables);
    - ``n_sets`` — replicated sets for the multi-set router (§5.2);
    - ``max_wait`` — batch-formation deadline used by the open-loop replay;
    - ``adaptive_wait``/``capacity_qps`` — adaptive formation deadline:
      ``max_wait`` becomes a ceiling that shrinks as the arrival rate
      approaches the (fitted or self-measured) capacity, and drops to zero
      when a partial bucket cannot fill in time anyway (see
      :class:`~repro.serving.scheduler.MasterScheduler`);
    - ``set_health`` — a :class:`~repro.core.faults.SetHealth` mask: dead
      sets are skipped by the router and re-admitted on recovery
      (:class:`~repro.serving.router.HealthAwareRouter`);
    - ``set_meshes`` — disjoint per-set device slices (build them with
      :func:`repro.core.parallel.set_mesh_slices`): when given, a batch
      routed to ``set_id`` executes on that set's own ``(1, ns)``
      ``("pod", "data")`` mesh through
      :func:`~repro.core.parallel.replicated_query_topk` instead of
      time-sharing the service ``mesh`` — the paper's §5.2 scale-out as
      real concurrent device capacity.  The index is pre-placed on every
      slice (and re-placed at each compaction); delta snapshots are placed
      lazily per (set, writer version).  ``set_health`` composes: a dead
      set quarantines exactly its slice.

    Online updates: pass ``updatable=True`` together with the ``corpus``
    the index was built from (a :class:`DeltaWriter` is created), or pass
    a ready ``writer``.  ``auto_compact`` (a fill fraction in (0, 1], or
    None to disable) folds the delta into a fresh main index whenever a
    mutation pushes the *posting* fill past the threshold; when the
    *document* fill crosses it instead, the compaction hands the writer a
    doubled ``doc_headroom`` generation (headroom is otherwise
    lifetime-fixed — growing it is only possible at a compaction boundary,
    where the main index recompiles anyway).
    """

    def __init__(
        self,
        index: ShardedIndex,
        meta: IndexMeta,
        mesh: jax.sharding.Mesh,
        *,
        ns: int,
        k: int = 10,
        window: int = 4096,
        t_max: int = 4,
        strategy: str = "embed",
        merge: str = "tournament",
        backend: str = "jnp",
        interpret: bool | None = None,
        corpus: Corpus | None = None,
        updatable: bool = False,
        writer: DeltaWriter | None = None,
        term_capacity: int = 256,
        doc_headroom: int = 1024,
        auto_compact: float | None = None,
        batch_size: int = 8,
        t_max_buckets: tuple[int, ...] | None = None,
        cache_size: int = 1024,
        n_sets: int = 1,
        max_wait: float = 0.0,
        adaptive_wait: bool = False,
        capacity_qps: float | None = None,
        set_health: "SetHealth | None" = None,
        set_meshes: "list[jax.sharding.Mesh] | None" = None,
        registry: MetricsRegistry | None = None,
        span_sink=None,
    ):
        self.index = index
        self.meta = meta
        self.mesh = mesh
        self.registry = registry if registry is not None else get_registry()
        self._set_index: list[ShardedIndex] | None = None
        self._observe_build()
        self._place_index()
        self.ns = ns
        self.k = k
        self.window = window
        self.t_max = t_max
        self.strategy = strategy
        self.merge = merge
        self.backend = backend
        self.interpret = interpret
        self.auto_compact = auto_compact
        if writer is None and updatable:
            if corpus is None:
                raise ValueError("updatable=True needs the base corpus")
            writer = DeltaWriter(
                corpus, meta, ns,
                term_capacity=term_capacity, doc_headroom=doc_headroom,
            )
        if writer is not None:
            # A mismatched writer would stripe delta docIDs with the wrong
            # d % ns map (silently wrong results) — fail loudly instead.
            if writer.ns != ns:
                raise ValueError(
                    f"writer.ns={writer.ns} != service ns={ns}"
                )
            if writer.n_terms != meta.n_terms:
                raise ValueError(
                    f"writer n_terms={writer.n_terms} != index {meta.n_terms}"
                )
        self.writer = writer
        buckets = t_max_buckets if t_max_buckets is not None else (t_max,)
        if max(buckets) > t_max:
            raise ValueError(f"t_max_buckets {buckets} exceed t_max={t_max}")
        self.set_meshes = list(set_meshes) if set_meshes is not None else None
        # delta placements per set slice; key None = the service mesh
        self._set_delta: dict[int | None, tuple[object, object]] = {}
        if self.set_meshes is not None:
            if len(self.set_meshes) != n_sets:
                raise ValueError(
                    f"{len(self.set_meshes)} set_meshes for n_sets={n_sets}"
                )
            for m in self.set_meshes:
                shape = dict(zip(m.axis_names, m.devices.shape))
                if shape.get("data") != ns or shape.get("pod") != 1:
                    raise ValueError(
                        f"set mesh must be (pod=1, data={ns}), got {shape}"
                    )
            self._place_set_indexes()
        router = None
        if set_health is not None:
            from repro.serving.router import HealthAwareRouter

            router = HealthAwareRouter(n_sets, set_health)
        self._m_mutation = self.registry.histogram(
            "odys_mutation_apply_seconds",
            help="one insert/delete/update call on the writer, compaction "
                 "included (wall domain; timed while tracing)")
        self.scheduler = MasterScheduler(
            self._execute,
            batch_size=batch_size,
            t_max_buckets=buckets,
            default_k=k,
            cache_size=cache_size,
            n_sets=n_sets,
            max_wait=max_wait,
            adaptive_wait=adaptive_wait,
            capacity_qps=capacity_qps,
            router=router,
            version_fn=self._snapshot_version,
            width_fn=self._query_width,
            registry=self.registry,
            span_sink=span_sink,
        )

    # ------------------------------------------------------------------
    # write path
    # ------------------------------------------------------------------

    def _require_writer(self) -> DeltaWriter:
        if self.writer is None:
            raise RuntimeError("service is read-only (no DeltaWriter attached)")
        return self.writer

    def insert(self, docs) -> list[int]:
        """Insert ``(terms, site)`` documents; returns global docIDs."""
        return self._mutate(lambda w: w.insert_docs(docs))

    def delete(self, docids) -> None:
        self._mutate(lambda w: w.delete_docs(docids))

    def update(self, updates) -> None:
        """Apply ``(docid, new_terms, new_site_or_None)`` updates."""
        self._mutate(lambda w: w.update_docs(updates))

    def _mutate(self, apply):
        """``apply`` the writer, then compact if due.  Traced, the call is
        an ``odys.mutation_apply`` annotation carrying the writer version
        it produced, and its time feeds ``odys_mutation_apply_seconds``."""
        writer = self._require_writer()
        if not self.scheduler.trace:
            out = apply(writer)
            self._maybe_compact()
            return out
        with annotation("mutation_apply") as ann:
            t0 = time.perf_counter()
            out = apply(writer)
            self._maybe_compact()
            self._m_mutation.observe(time.perf_counter() - t0)
            ann.set_metadata(version=self.writer.version)
        return out

    def compact(
        self,
        *,
        verify: bool = False,
        term_capacity: int | None = None,
        doc_headroom: int | None = None,
    ) -> None:
        """Fold the delta into a fresh main index and swap it in.

        ``term_capacity``/``doc_headroom`` hand the writer a re-sized delta
        generation at the boundary (see :meth:`DeltaWriter.rebase`)."""
        writer = self._require_writer()
        self.index, self.meta = _compact(
            writer, verify=verify,
            term_capacity=term_capacity, doc_headroom=doc_headroom,
        )
        self._observe_build()
        self._place_index()
        if self.set_meshes is not None:
            # the main index changed identity: every slice re-places it
            # (the per-set delta cache is cleared there too — the rebase
            # bumped the writer epoch, so no stale snapshot survives)
            self._place_set_indexes()

    def _maybe_compact(self) -> None:
        w = self.writer
        if self.auto_compact is None or w is None:
            return
        grow = w.doc_fill() >= self.auto_compact
        if grow or w.needs_compaction(self.auto_compact):
            self.compact(doc_headroom=2 * w.doc_headroom if grow else None)

    # ------------------------------------------------------------------
    # read path
    # ------------------------------------------------------------------

    def _snapshot_version(self) -> int:
        """Cache-invalidation stamp: the writer's monotone version (every
        mutation and every compaction bumps it); 0 for read-only service."""
        return 0 if self.writer is None else self.writer.version

    def _query_width(self, terms, site) -> int:
        """Effective padded width — the ``site_term`` strategy rewrites the
        site restriction into an extra join term."""
        extra = 1 if (site is not None and self.strategy == "site_term") else 0
        return len(terms) + extra

    def _observe_build(self) -> None:
        """Each shard's host build time of the index at hand, when its
        metadata carries them (``build_sharded_index``)."""
        hist = self.registry.histogram(
            "odys_index_shard_build_seconds",
            help="host build of one shard of the main index (stripe, invert, "
                 "lay out), shards built concurrently")
        for seconds in self.meta.shard_build_s:
            hist.observe(seconds)

    def _place_index(self) -> None:
        """Lay the main index out over the service mesh's ``data`` axis,
        one shard per device, once — not copied from the default device
        at every dispatch.  From the build's host arrays each device
        receives only its own shard.  Traced: ``odys.index_place``."""
        with annotation("index_place"):
            self.index = place_index(self.index, self.mesh)
        self._export_device_bytes()

    def _place_set_indexes(self) -> None:
        """(Re)place the main index on every set's mesh slice.

        Each slice holds its own copy, sharded over its ``data`` axis —
        the replication that makes sets independent failure/capacity
        domains (§3.1/§5.2).  Also drops the per-set delta placements:
        callers re-place lazily at the next dispatch."""
        with annotation("index_place"):
            self._set_index = [place_index(self.index, m) for m in self.set_meshes]
        self._set_delta.clear()
        self._export_device_bytes()

    def _export_device_bytes(self) -> None:
        """``odys_index_device_bytes{device}``: the main index's bytes
        resident on each device, over every placement of it."""
        per_device: dict[int, int] = defaultdict(int)
        for placed in [self.index, *(self._set_index or [])]:
            for leaf in jax.tree.leaves(placed):
                for shard in leaf.addressable_shards:
                    per_device[shard.device.id] += shard.data.nbytes
        for device, n in sorted(per_device.items()):
            self.registry.gauge(
                "odys_index_device_bytes", device=str(device),
                help="main index bytes placed on the device",
            ).set(n)

    def _delta_snapshot(
        self, set_id: int | None = None, clock: PhaseClock | None = None
    ):
        """Current delta snapshot placed on ``set_id``'s slice (None: the
        service mesh), cached per (placement, writer version) — a new
        publish on any shard re-places.  A publish is the batch's
        ``delta_publish`` phase: the writer's host gather of what changed
        since its placed snapshot (``delta_rebuild``), then
        ``writer.device_delta`` — the patch's copy to the device and its
        scatter into a copy of that snapshot, or a full placement — and
        the ``device_put`` onto the mesh, which on a one-device mesh keeps
        the writer's buffers (``delta_place``: no sync is added)."""
        if self.writer is None:
            return None
        # read before the snapshot: a mutation racing the publish can only
        # make the cache key older than the content, never newer
        ver = self.writer.version
        cached = self._set_delta.get(set_id)
        if cached is not None and cached[0] == ver:
            return cached[1]
        if clock is not None:
            clock.open("delta_publish")
            clock.open("delta_rebuild")
        self.writer.host_publish()      # cached for device_delta below
        if clock is not None:
            clock.close("delta_rebuild")
            clock.open("delta_place")
        snap = self.writer.device_delta()
        mesh = self.mesh if set_id is None else self.set_meshes[set_id]
        placed = place_index(snap, mesh)
        if clock is not None:
            clock.close("delta_publish")
        self._set_delta[set_id] = (ver, placed)
        return placed

    def _run_engine(
        self,
        queries,
        *,
        t_max: int,
        k: int,
        set_id: int | None = None,
        clock: PhaseClock | None = None,
    ) -> SearchResult:
        """One batch end-to-end on the mesh at the given padded shapes.

        With ``set_meshes`` configured and a ``set_id``, the batch runs on
        that set's disjoint slice via :func:`replicated_query_topk`;
        otherwise on the shared service mesh.  ``clock`` (tracing) times
        the batch build, the delta publish and the launch."""
        if clock is not None:
            clock.open("batch_build")
        batch = make_query_batch(
            queries, t_max=t_max, meta=self.meta, strategy=self.strategy
        )
        if clock is not None:
            clock.close("batch_build")
        if set_id is not None and self.set_meshes is not None:
            run, index, mesh = (
                replicated_query_topk, self._set_index[set_id],
                self.set_meshes[set_id],
            )
        else:
            run, index, mesh, set_id = (
                distributed_query_topk, self.index, self.mesh, None
            )
        delta = self._delta_snapshot(set_id, clock)
        if clock is not None:
            clock.open("launch")
        res = run(
            index,
            batch,
            delta,
            mesh=mesh,
            ns=self.ns,
            k=k,
            window=self.window,
            attr_strategy=self.strategy,
            merge=self.merge,
            backend=self.backend,
            interpret=self.interpret,
        )
        if clock is not None:
            clock.close("launch")
        return res

    def _execute(self, queries, t_max: int, k: int, set_id: int) -> list[SearchHit]:
        """Scheduler executor: run one formed micro-batch.

        ``set_id`` identifies the replicated set the router picked.  With
        ``set_meshes`` configured the batch executes on that set's own
        disjoint device slice (the paper's multi-set deployment shape);
        otherwise the in-process deployment time-shares one mesh across
        sets.

        When the scheduler traces, the batch's service is decomposed on
        its :attr:`~MasterScheduler.batch_clock`, at the batch boundary
        only — host build and dispatch of the jitted program
        (``slave_dispatch`` and its children), the ``np.asarray`` device
        sync that was already on this path (``master_merge``: the fused
        slave top-k + master merge completes under it), and the host-side
        result extraction (``finalize``).  No host syncs are added inside
        the device program."""
        clock = self.scheduler.batch_clock
        if clock is not None:
            clock.open("slave_dispatch")
        res = self._run_engine(
            queries, t_max=t_max, k=k, set_id=set_id, clock=clock
        )
        if clock is not None:
            clock.close("slave_dispatch")
            clock.open("master_merge")
        docs = np.asarray(res.docids)
        hits = np.asarray(res.n_hits)
        if clock is not None:
            clock.close("master_merge")
            clock.open("finalize")
        out = [
            SearchHit(
                docids=[int(d) for d in row if d != INVALID_DOC],
                n_hits=int(h),
            )
            for row, h in zip(docs, hits)
        ]
        if clock is not None:
            clock.close("finalize")
        return out

    def submit(
        self, terms, site: int | None = None, *, k: int | None = None
    ) -> QueryTicket:
        """Admit one query into the pipeline (async-style entry point).

        Returns the ticket — already completed on a cache hit; otherwise
        its ``result`` lands on a later :meth:`drain`/``step``."""
        return self.scheduler.submit(terms, site, k=k)

    def drain(self) -> list[QueryTicket]:
        """Dispatch micro-batches until the admission queue is empty."""
        return self.scheduler.drain()

    def search_batch(
        self, queries: list[tuple[list[int], int | None]]
    ) -> SearchResult:
        """Run one pre-formed batch end-to-end; returns device arrays.

        Bypasses admission/caching — this is the raw engine path the
        scheduler itself dispatches through.  With a writer attached the
        batch runs merge-on-read against the current delta snapshot
        (per-batch snapshot isolation)."""
        return self._run_engine(queries, t_max=self.t_max, k=self.k)

    def search(
        self, queries: list[tuple[list[int], int | None]]
    ) -> list[SearchHit]:
        """Host-friendly entry point, through the full pipeline: every
        query is admitted, cache-checked, micro-batched and routed; returns
        the merged hits in submission order."""
        tickets = [self.scheduler.submit(terms, site) for terms, site in queries]
        self.scheduler.drain()
        assert all(t.done for t in tickets)
        return [t.result for t in tickets]

    # ------------------------------------------------------------------
    # reporting
    # ------------------------------------------------------------------

    def stats(self) -> dict:
        """Scheduler/cache/router counters (see MasterScheduler.stats)."""
        return self.scheduler.stats()
