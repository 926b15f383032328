"""NeighborLoader: seeds -> sampler(graph store) -> features(feature store)
-> jit-ready mini-batch — the paper's three-component loading loop (C6).

The loader is oblivious to the storage backends (swap InMemory for
Partitioned, Cached, Mmap or Resilient without touching this file — the
paper's plug-and-play claim) and emits **static-shape** batches so the
jit'd step never recompiles. Batches are *jit-ready*: the producer path
sorts the sampled COO by destination and pre-fills the ``EdgeIndex``
CSR/CSC caches host-side — plus, when Pallas dispatch is on, a
static-layout blocked-ELL packing whose bucket shapes derive from the
sampler's budgets, so per-batch edge indices passed as jit arguments take
the Pallas SpMM path with a single compilation across batches. ``Batch``
is a registered pytree for exactly this reason. Supports externally-seeded
iteration (training tables with per-seed timestamps + attached labels, the
RDL workflow of §3.1) via ``transform``.

Out-of-core overlap: batch production decomposes into three stages —
**sample** (graph-store walk, sequential so the sampler's seeded RNG draws
in batch order), **gather** (feature-store fetch, the dominant latency
against partitioned/remote/disk backends) and **pack** (host CSR/CSC/ELL
packing + device put). With ``pipeline_depth > 1`` the producer keeps that
many batches in flight on a small worker pool with *ordered reassembly*:
batch ``i``'s gather hides behind the sampling and packing of batches
``i+1..i+depth``, while consumers still see batches in exactly the
sequential order (bit-identical in the fault-free case — the equivalence
tests pin this down). ``partition_order=True`` additionally groups shuffled
seeds by their home partition (discovered through the store chain's routing
table) so each batch's gather touches fewer remote partitions.

Fault tolerance: when the feature store is a
``repro.data.resilience.ResilientFeatureStore`` the gathers fan out per
partition on its thread pool (retries + deadlines + circuit breakers behind
the scenes) and each batch carries an ``extras['degraded']`` row mask for
features served from the stale cache; ``on_batch_error="raise"|"retry"|
"skip"`` decides what a batch-level store failure does — identically in the
sequential and pipelined producers (a failed pipelined chain re-runs the
remaining policy attempts in order at reassembly) — with every
retry/skip/degraded row counted in the loader's ``health`` dict. See the
ROADMAP "Store-backed loading pipeline" subsection.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import trace
from repro.core.edge_index import EdgeIndex
from repro.data.feature_store import FeatureStore
from repro.data.graph_store import DEFAULT_ETYPE, GraphStore
from repro.data.resilience import StoreError, find_routed
from repro.data.sampler import NeighborSampler, SamplerOutput
from repro.kernels import use_pallas
from repro.kernels.spmm.ops import ell_layout_from_bounds


@dataclasses.dataclass
class Batch:
    """A sampled subgraph with fetched features (all jnp, static shapes)."""
    x: jnp.ndarray                    # (N_slots, F) zero rows for padding
    edge_index: EdgeIndex             # local slots; pads are (0, 0) self-loops
    n_id: jnp.ndarray                 # (N_slots,) global node ids (-1 pad)
    e_id: jnp.ndarray                 # (E_slots,) global edge ids (-1 pad)
    seed_slots: jnp.ndarray           # (B,)
    num_sampled_nodes: List[int]
    num_sampled_edges: List[int]
    y: Optional[jnp.ndarray] = None
    edge_mask: Optional[jnp.ndarray] = None
    extras: dict = dataclasses.field(default_factory=dict)

    @property
    def num_nodes(self) -> int:
        return int(self.x.shape[0])

    @property
    def seed_mask(self) -> jnp.ndarray:
        """(B,) True for real seeds, False for -1 shard-padding seeds.

        Per-shard batches only (inside a shard_map body, or shards=1): a
        stacked multi-shard batch must be sliced to one shard first.
        """
        return self.n_id[self.seed_slots] >= 0

    def seed_output(self, out: jnp.ndarray) -> jnp.ndarray:
        return out[self.seed_slots]


def _batch_flatten(b: Batch):
    children = (b.x, b.edge_index, b.n_id, b.e_id, b.seed_slots, b.y,
                b.edge_mask, b.extras)
    aux = (tuple(b.num_sampled_nodes), tuple(b.num_sampled_edges))
    return children, aux


def _batch_unflatten(aux, children):
    x, ei, n_id, e_id, seed_slots, y, edge_mask, extras = children
    nn, ne = aux
    return Batch(x=x, edge_index=ei, n_id=n_id, e_id=e_id,
                 seed_slots=seed_slots, num_sampled_nodes=list(nn),
                 num_sampled_edges=list(ne), y=y, edge_mask=edge_mask,
                 extras=extras)


# Batch flows through jit boundaries whole (the per-hop counts are static
# aux data); identical budgets -> identical treedef -> no recompiles.
jax.tree_util.register_pytree_node(Batch, _batch_flatten, _batch_unflatten)


def split_seed_shards(seeds: np.ndarray,
                      seed_time: Optional[np.ndarray],
                      shards: int):
    """Split one global seed batch into ``shards`` equal-size parts.

    Pure numpy (producer-thread stage). When the batch doesn't divide, the
    tail pads with -1 seeds (seed time 0) up to ``ceil(B/shards)`` per shard
    — the masked-seed convention the sampler keeps out of its dedup table
    and ``Batch.seed_mask`` exposes to the loss. Returns a list of
    ``(seeds, seed_time)`` pairs, one per shard.
    """
    shards = int(shards)
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    seeds = np.asarray(seeds, np.int64)
    per = -(-len(seeds) // shards)
    pad = per * shards - len(seeds)
    if pad:
        seeds = np.concatenate([seeds, np.full(pad, -1, seeds.dtype)])
        if seed_time is not None:
            seed_time = np.concatenate(
                [seed_time, np.zeros(pad, seed_time.dtype)])
    return [(seeds[i * per:(i + 1) * per],
             None if seed_time is None
             else seed_time[i * per:(i + 1) * per])
            for i in range(shards)]


def stack_batches(batches: List[Batch]) -> Batch:
    """Stack per-shard batches leaf-wise into one leading-``D``-axis pytree.

    The stacked batch is what the mesh trainer shards over the ``data``
    axis: every leaf gains a leading shard dimension, the static aux data
    (per-hop counts) is shared. Requires identical treedefs — i.e. equal
    per-shard seed counts, which ``split_seed_shards`` guarantees.
    """
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *batches)


_SKIP = object()  # sentinel: a batch dropped by on_batch_error="skip"


def _real_edges(sample) -> int:
    """Sampled edges that are real (edge id >= 0, not budget padding) in a
    ``_stage_sample`` result, over its shards and edge types."""
    if "parts" in sample:
        return sum(_real_edges(p) for p in sample["parts"])
    edge = sample["out"].edge
    leaves = edge.values() if isinstance(edge, dict) else [edge]
    return sum(int(np.count_nonzero(e >= 0)) for e in leaves)


_BATCH_ERROR_MODES = ("raise", "retry", "skip")


class _PrefetchLoader:
    """Seed-batching + pipelined/prefetch production shared by both loaders.

    Subclasses set ``input_nodes``, ``input_time``, ``batch_size``,
    ``shuffle``, ``drop_last``, ``prefetch``, ``pipeline_depth``,
    ``partition_order`` and ``rng`` in ``__init__`` and implement the three
    production stages:

      * ``_stage_sample(seeds, seed_time)`` — graph-store sampling + any
        shared shape/layout decisions. Always called sequentially in batch
        order (the sampler's seeded RNG must draw deterministically), pure
        numpy.
      * ``_stage_gather(sample)`` — feature-store fetch for the sampled
        nodes. The dominant latency against partitioned/remote/disk
        stores; safe to run concurrently across batches, pure numpy.
      * ``_stage_pack(sample, gather)`` — host ELL/CSR packing + device
        put, assembling the final batch.

    ``_make_batch`` composes the three, so the sequential path and the
    policy retry loop re-run one chain. Iteration (the producer thread,
    the stage pipeline with ordered reassembly, exception propagation
    through the queue, and reaping of abandoned producers/workers) lives
    here once — the homogeneous and heterogeneous loaders differ only in
    what a batch *is*.

    Store failures (``repro.data.resilience.StoreError``) are policy, not
    fate: ``on_batch_error`` picks what a failed batch chain does —
    ``"raise"`` propagates immediately, ``"retry"`` re-samples/re-fetches
    the same seeds up to ``batch_retries`` times then raises, ``"skip"``
    retries then drops the batch and keeps the epoch going. Every decision
    lands in the ``health`` counter dict ({batches, batch_retries,
    skipped_batches, degraded_rows}); degraded rows are read off the
    batch's ``extras['degraded']`` mask (filled by the resilient feature
    store). Non-store exceptions always propagate — a bug is not a fault.
    The pipelined producer applies the *same* policy with the same
    counters: a chain that failed in flight consumed attempt 0, and the
    remaining attempts re-run sequentially at its reassembly slot.

    With the program's tracer on (``repro.trace.enable()``) each stage runs
    under a span (``loader.sample``/``gather``/``pack``, tagged with the
    batch's index), the producer's wait on a full queue under
    ``loader.queue_put`` and the consumer's under ``loader.wait``; the
    counters ``loader.sampled_edges`` and ``loader.put_bytes`` add each
    batch's real sampled edges and array bytes.
    """

    input_nodes: np.ndarray
    input_time: Optional[np.ndarray]
    batch_size: int
    shuffle: bool
    drop_last: bool
    prefetch: int
    pipeline_depth: int = 1
    partition_order: bool = False
    rng: np.random.Generator
    on_batch_error: str = "raise"
    batch_retries: int = 2

    # ---- the three production stages (subclass contract) ----
    def _stage_sample(self, seeds: np.ndarray,
                      seed_time: Optional[np.ndarray]):
        raise NotImplementedError

    def _stage_gather(self, sample):
        raise NotImplementedError

    def _stage_pack(self, sample, gather):
        raise NotImplementedError

    def _make_batch(self, seeds: np.ndarray,
                    seed_time: Optional[np.ndarray], index: int):
        sample = self._sample(seeds, seed_time, index)
        return self._pack(sample, self._gather(sample, index), index)

    # ---- the stages under the program's spans (``repro.trace``); ``index``
    # is the batch's place in the epoch's seed order ----
    def _sample(self, seeds, seed_time, index: int):
        with trace.span("loader.sample", batch=index):
            sample = self._stage_sample(seeds, seed_time)
        if trace.enabled():
            trace.count("loader.sampled_edges", _real_edges(sample))
        return sample

    def _gather(self, sample, index: int):
        with trace.span("loader.gather", batch=index):
            return self._stage_gather(sample)

    def _pack(self, sample, gather, index: int):
        with trace.span("loader.pack", batch=index):
            batch = self._stage_pack(sample, gather)
        if trace.enabled():
            trace.count("loader.put_bytes", sum(
                int(a.nbytes) for a in jax.tree_util.tree_leaves(batch)))
        return batch

    def _init_policy(self, on_batch_error: str, batch_retries: int):
        if on_batch_error not in _BATCH_ERROR_MODES:
            raise ValueError(f"on_batch_error must be one of "
                             f"{_BATCH_ERROR_MODES}, got {on_batch_error!r}")
        self.on_batch_error = on_batch_error
        self.batch_retries = int(batch_retries)
        self.health = {"batches": 0, "batch_retries": 0,
                       "skipped_batches": 0, "degraded_rows": 0}

    def _init_pipeline(self, pipeline_depth: int, partition_order: bool):
        self.pipeline_depth = int(pipeline_depth)
        if self.pipeline_depth < 0:
            raise ValueError(
                f"pipeline_depth must be >= 0, got {pipeline_depth}")
        self.partition_order = bool(partition_order)

    @staticmethod
    def _degraded_count(batch) -> int:
        extras = getattr(batch, "extras", None)
        if not extras or "degraded" not in extras:
            return 0
        d = extras["degraded"]
        leaves = d.values() if isinstance(d, dict) else [d]
        return int(sum(int(np.asarray(m).sum()) for m in leaves))

    def _count_success(self, batch) -> None:
        self.health["batches"] += 1
        self.health["degraded_rows"] += self._degraded_count(batch)

    def _make_batch_guarded(self, seeds, seed_time, index, abort=None):
        """Apply ``on_batch_error`` around the full batch chain.

        Returns the batch, or ``_SKIP`` when the policy drops it. ``abort``
        (the producer's abandonment flag) bounds how long a retry loop can
        hold the producer thread after the consumer is gone.
        """
        if not hasattr(self, "health"):
            self._init_policy(self.on_batch_error, self.batch_retries)
        try:
            batch = self._make_batch(seeds, seed_time, index)
        except StoreError as exc:
            return self._finish_policy(seeds, seed_time, index, exc, abort)
        self._count_success(batch)
        return batch

    def _finish_policy(self, seeds, seed_time, index, first_exc, abort):
        """Policy attempts 1..N after attempt 0 raised ``first_exc``.

        Shared by the sequential path and the pipelined reassembly (where
        attempt 0 ran — and failed — in flight on the worker pool). Health
        accounting is identical either way.
        """
        attempts = (1 if self.on_batch_error == "raise"
                    else 1 + self.batch_retries)
        last = first_exc
        if attempts > 1:
            self.health["batch_retries"] += 1
        for attempt in range(1, attempts):
            if abort is not None and abort():
                break
            try:
                batch = self._make_batch(seeds, seed_time, index)
            except StoreError as exc:
                last = exc
                if attempt + 1 < attempts:
                    self.health["batch_retries"] += 1
                continue
            self._count_success(batch)
            return batch
        if self.on_batch_error == "skip":
            self.health["skipped_batches"] += 1
            return _SKIP
        raise last

    # ---- seed batching ----
    def _seed_route(self) -> Optional[np.ndarray]:
        """Home partition of every *input node*, via the feature-store
        chain's routing table (None when the chain doesn't route)."""
        routed = find_routed(getattr(self, "fs", None))
        if routed is None:
            return None
        route = getattr(routed, "_route", {}).get(self._seed_feature_key())
        if route is None:
            return None
        return np.asarray(route)[self.input_nodes]

    def _seed_feature_key(self):
        """(group, attr) of the seed features (hetero overrides group)."""
        return ("node", "x")

    def _seed_batches(self):
        order = np.arange(len(self.input_nodes))
        if self.shuffle:
            self.rng.shuffle(order)
        if self.partition_order:
            # group (shuffled) seeds by home partition: each batch's gather
            # then touches one — or few — partitions, cutting the remote-row
            # fraction. A stable sort keeps the shuffled order within each
            # partition, so epochs stay randomised *inside* locality groups.
            part = self._seed_route()
            if part is not None:
                order = order[np.argsort(part[order], kind="stable")]
        bs = self.batch_size
        for i in range(0, len(order) - (bs - 1 if self.drop_last else 0), bs):
            idx = order[i:i + bs]
            if len(idx) < bs and self.drop_last:
                break
            yield (self.input_nodes[idx],
                   None if self.input_time is None else self.input_time[idx])

    # ---- batch production (sequential or stage-pipelined) ----
    def _produce(self, abort=None):
        """Yield ``(index, batch)``: policy-guarded batches in seed-batch
        order, each with its place in that order."""
        if not hasattr(self, "health"):
            self._init_policy(self.on_batch_error, self.batch_retries)
        if self.pipeline_depth > 1:
            yield from self._produce_pipelined(abort)
            return
        for index, (seeds, t) in enumerate(self._seed_batches()):
            if abort is not None and abort():
                return
            batch = self._make_batch_guarded(seeds, t, index, abort=abort)
            if batch is not _SKIP:
                yield index, batch

    def _produce_pipelined(self, abort=None):
        """Stage-pipelined production with ordered reassembly.

        Sampling stays sequential on this thread (deterministic RNG draw
        order); each sampled batch's *gather* is submitted to a bounded
        worker pool, up to ``pipeline_depth`` gathers in flight. Gather is
        the stage that blocks on the store (remote/disk I/O releases the
        GIL), so batch ``i``'s fetch latency hides behind the sampling and
        packing of its successors; packing stays on this thread at
        reassembly time — host packing is CPU-bound and would only fight
        the coordinator for the GIL on a worker, and coordinator packing
        keeps device puts single-threaded and the in-memory fast path
        overhead-free. Batches are yielded strictly in submission order,
        so consumers see exactly the sequential sequence. A chain that
        raises a ``StoreError`` re-enters the policy loop at its
        reassembly slot (the in-flight run was attempt 0); non-store
        errors propagate from the head slot in order. The pool is torn
        down (and every worker joined) when the generator closes, however
        early — abandonment cannot leak stage workers.
        """
        depth = self.pipeline_depth
        pool = ThreadPoolExecutor(max_workers=depth,
                                  thread_name_prefix="loader-stage")
        # (index, seeds, t, sample, Future | StoreError)
        inflight: deque = deque()
        seed_iter = enumerate(self._seed_batches())
        exhausted = False
        try:
            while True:
                while not exhausted and len(inflight) < depth:
                    try:
                        index, (seeds, t) = next(seed_iter)
                    except StopIteration:
                        exhausted = True
                        break
                    try:
                        sample = self._sample(seeds, t, index)
                    except StoreError as exc:  # sampling itself can fetch
                        inflight.append((index, seeds, t, None, exc))
                    else:
                        inflight.append((index, seeds, t, sample, pool.submit(
                            self._gather, sample, index)))
                if not inflight:
                    return
                index, seeds, t, sample, head = inflight.popleft()
                try:
                    if isinstance(head, StoreError):
                        raise head
                    batch = self._pack(sample, head.result(), index)
                except StoreError as exc:
                    batch = self._finish_policy(seeds, t, index, exc, abort)
                else:
                    self._count_success(batch)
                if batch is not _SKIP:
                    yield index, batch
                if abort is not None and abort():
                    return
        finally:
            pool.shutdown(wait=True, cancel_futures=True)

    def __iter__(self):
        if self.prefetch <= 0:
            # inline production on the consumer thread; with
            # pipeline_depth > 1 gathers still overlap on the worker pool
            gen = self._produce()
            try:
                for _, batch in gen:
                    yield batch
            finally:
                gen.close()  # deterministic worker-pool teardown
            return
        # bounded host prefetch: a producer thread runs the (sequential or
        # pipelined) generator and feeds the consumer through a queue
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        stop = object()
        abandoned = threading.Event()

        def producer():
            # A raised exception must reach the consumer: swallowing it here
            # would never enqueue the sentinel and deadlock `q.get()`.
            gen = self._produce(abort=abandoned.is_set)
            try:
                for index, batch in gen:
                    if abandoned.is_set():
                        return
                    with trace.span("loader.queue_put", batch=index):
                        q.put(batch)
            except BaseException as exc:  # noqa: BLE001 - re-raised below
                q.put(exc)
                return
            finally:
                gen.close()  # reap stage workers even on abandonment
            q.put(stop)

        th = threading.Thread(target=producer, daemon=True,
                              name="loader-producer")
        th.start()
        try:
            while True:
                with trace.span("loader.wait"):
                    item = q.get()
                if item is stop:
                    break
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            # Reap the producer even when the consumer abandons the iterator
            # early (GeneratorExit): drain the bounded queue so a blocked
            # q.put unblocks, then join.
            abandoned.set()
            while th.is_alive():
                try:
                    q.get_nowait()
                except queue.Empty:
                    pass
                th.join(timeout=0.01)

    def __len__(self):
        n = len(self.input_nodes)
        return n // self.batch_size if self.drop_last else -(-n // self.batch_size)


class NeighborLoader(_PrefetchLoader):
    def __init__(self, feature_store: FeatureStore, graph_store: GraphStore,
                 *, num_neighbors: Sequence[int], batch_size: int,
                 input_nodes: Optional[np.ndarray] = None,
                 input_time: Optional[np.ndarray] = None,
                 labels_attr: Optional[str] = "y",
                 edge_type=DEFAULT_ETYPE, disjoint: bool = False,
                 temporal_strategy: str = "uniform",
                 transform: Optional[Callable[[Batch], Batch]] = None,
                 shuffle: bool = False, drop_last: bool = True,
                 prefetch: int = 0, pipeline_depth: int = 1,
                 partition_order: bool = False,
                 prefill_ell: Optional[bool] = None,
                 on_batch_error: str = "raise", batch_retries: int = 2,
                 shards: int = 1, seed: int = 0):
        self.fs = feature_store
        self.shards = int(shards)
        if self.shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        self._init_policy(on_batch_error, batch_retries)
        self._init_pipeline(pipeline_depth, partition_order)
        self.sampler = NeighborSampler(
            graph_store, num_neighbors, edge_type=edge_type,
            disjoint=disjoint, temporal_strategy=temporal_strategy, seed=seed)
        if input_nodes is None:
            n = feature_store.get_tensor_size(group="node", attr="x")[0]
            input_nodes = np.arange(n)
        self.input_nodes = np.asarray(input_nodes)
        self.input_time = None if input_time is None else np.asarray(
            input_time)
        self.batch_size = batch_size
        self.labels_attr = labels_attr
        self.transform = transform
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.prefetch = prefetch
        # Static-layout ELL packing plan: depends only on the sampler's
        # budgets and the seed count, shared by every batch of that size
        # (a drop_last=False tail batch gets its own, smaller layout).
        self.prefill_ell = prefill_ell
        self._ell_layouts: dict = {}
        self.rng = np.random.default_rng(seed)

    def _ell_layout_for(self, num_seeds: int):
        if num_seeds not in self._ell_layouts:
            self._ell_layouts[num_seeds] = ell_layout_from_bounds(
                self.sampler.slot_degree_bounds(num_seeds))
        return self._ell_layouts[num_seeds]

    # ---- stages ----
    # With shards > 1 each stage runs its single-shard body once per shard
    # (sampling stays in shard order for deterministic RNG draws) and
    # ``_stage_pack`` stacks the per-shard batches leaf-wise; health
    # counters keep counting *global* batches either way.
    def _stage_sample(self, seeds: np.ndarray,
                      seed_time: Optional[np.ndarray]):
        """Sequential: sampler RNG draws + the (cached) shared ELL layout
        decision both happen in batch order on one thread."""
        if self.shards == 1:
            return self._sample_one(seeds, seed_time)
        return {"parts": [self._sample_one(s, t) for s, t in
                          split_seed_shards(seeds, seed_time, self.shards)]}

    def _stage_gather(self, sample):
        """Feature (+ label) fetch — the latency this pipeline hides."""
        if "parts" not in sample:
            return self._gather_one(sample)
        return {"parts": [self._gather_one(p) for p in sample["parts"]]}

    def _stage_pack(self, sample, gather) -> Batch:
        """Host ELL/CSR packing + device put -> the jit-ready batch."""
        if "parts" not in sample:
            return self._pack_one(sample, gather)
        return stack_batches([
            self._pack_one(s, g)
            for s, g in zip(sample["parts"], gather["parts"])])

    def _sample_one(self, seeds: np.ndarray,
                    seed_time: Optional[np.ndarray]):
        out: SamplerOutput = self.sampler.sample(seeds, seed_time)
        fill_ell = (use_pallas() if self.prefill_ell is None
                    else self.prefill_ell)
        layout = self._ell_layout_for(len(seeds)) if fill_ell else None
        return {"seeds": seeds, "out": out, "layout": layout,
                "fill_ell": fill_ell}

    def _gather_one(self, sample):
        out: SamplerOutput = sample["out"]
        fetch = getattr(self.fs, "get_padded_resilient", None)
        degraded = None
        if fetch is not None:  # resilient store: degraded-row mask surfaced
            x, degraded = fetch(out.node, group="node", attr="x")
        else:
            x = self.fs.get_padded(out.node, group="node", attr="x")
        y = None
        if self.labels_attr is not None:
            seeds = np.asarray(sample["seeds"])
            # -1 shard-padding seeds must not wrap to the last row: gather
            # through a safe index, then zero the padded label rows.
            safe = np.where(seeds >= 0, seeds, 0)
            try:
                y = self.fs.get_tensor(
                    group="node", attr=self.labels_attr, index=safe)
            except KeyError:
                y = None
            if y is not None and (seeds < 0).any():
                y = np.asarray(y)
                mask = (seeds >= 0).reshape(
                    (-1,) + (1,) * (y.ndim - 1))
                y = np.where(mask, y, np.zeros((), y.dtype))
        return {"x": x, "y": y, "degraded": degraded}

    def _pack_one(self, sample, gather) -> Batch:
        out: SamplerOutput = sample["out"]
        n_slots = len(out.node)
        ei = EdgeIndex.from_coo_prefilled(
            out.row, out.col, n_slots, n_slots,
            ell_layout=sample["layout"] if sample["fill_ell"] else None)
        batch = Batch(
            x=jnp.asarray(gather["x"]), edge_index=ei,
            n_id=jnp.asarray(out.node), e_id=jnp.asarray(out.edge),
            seed_slots=jnp.asarray(out.seed_slots.astype(np.int32)),
            num_sampled_nodes=out.num_sampled_nodes,
            num_sampled_edges=out.num_sampled_edges,
            y=None if gather["y"] is None else jnp.asarray(gather["y"]),
            edge_mask=jnp.asarray((out.edge >= 0)))
        if gather["degraded"] is not None:
            batch.extras["degraded"] = jnp.asarray(gather["degraded"])
        if self.transform is not None:
            batch = self.transform(batch)
        return batch
