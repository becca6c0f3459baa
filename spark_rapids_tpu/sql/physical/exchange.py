"""Shuffle & broadcast exchanges (reference ``GpuShuffleExchangeExecBase``,
``GpuBroadcastExchangeExec``, SURVEY §2.8/§3.4).

Local-mode data plane: rows are routed by a partitioner id column and
compacted per target with static-shape gathers (the contiguousSplit analog).
Multi-chip data plane (parallel/shuffle.py) swaps this loop for an ICI
all-to-all under shard_map; the exec contract (materialize once, serve
per-partition) is identical, mirroring the reference's shuffle-manager SPI.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, List, Optional

import numpy as np

from ...columnar.batch import CONCAT_STATS, ColumnarBatch, concat_declined
from ...observability import tracer as _trace
from ...parallel.partitioning import (HashPartitioning, Partitioning,
                                      RangePartitioning, RoundRobinPartitioning,
                                      SinglePartitioning)
from ..expressions.core import EvalContext
from .base import TPU, PhysicalPlan, TaskContext
from .kernel_cache import exprs_key


def empty_batch_for(attrs) -> ColumnarBatch:
    """Zero-row batch with the schema of an exec's output attributes."""
    from ... import types as T
    return ColumnarBatch.empty(T.StructType(tuple(
        T.StructField(a.name, a.dtype, True) for a in attrs)))


def _recolumned(batch: ColumnarBatch, columns) -> ColumnarBatch:
    """``batch`` over other columns of the same rows, its host-known row
    count kept."""
    return ColumnarBatch(batch.names, tuple(columns), batch.num_rows
                         ).with_known_rows(batch.num_rows_int)


def _empty_like(batch: ColumnarBatch, chip) -> ColumnarBatch:
    """A batch of ``batch``'s shape with no rows, on ``chip``: the shard
    of a chip whose maps produced nothing."""
    import jax
    from ...columnar.encoded import DictEncodedColumn
    from ...parallel import placement

    def zeros(tree):
        return jax.tree_util.tree_map(
            lambda x: np.zeros(x.shape, x.dtype), tree)

    # a dictionary is no row data: the chip gets a copy of it as it is
    cols = tuple(
        DictEncodedColumn(c.dtype, zeros(c.codes), c.dictionary,
                          zeros(c.validity))
        if isinstance(c, DictEncodedColumn) else zeros(c)
        for c in batch.columns)
    return ColumnarBatch(batch.names, placement.put(cols, chip),
                         placement.put(np.int32(0), chip)).with_known_rows(0)


#: observability (tests assert on these): the AQE skew-split reader, and
#: how the local plane launched its work — per map output one ``map``
#: program (rows ordered by target) and one ``shrink`` program (the pieces
#: cut out at their buckets), one ``concat`` program per merge of pieces;
#: ``eager_fallbacks`` counts the maps and merges that ran per launch or
#: per array instead (their spans carry ``declined=<why>``)
STATS = {"skew_splits": 0, "skew_chunks": 0, "map_programs": 0,
         "shrink_programs": 0, "concat_programs": 0, "eager_fallbacks": 0}


@contextmanager
def _counting_concats():
    """Credit the ``ColumnarBatch.concat`` calls made inside to STATS."""
    programs, eager = CONCAT_STATS["programs"], CONCAT_STATS["eager"]
    try:
        yield
    finally:
        STATS["concat_programs"] += CONCAT_STATS["programs"] - programs
        STATS["eager_fallbacks"] += CONCAT_STATS["eager"] - eager


class ShuffleExchangeExec(PhysicalPlan):
    def __init__(self, partitioning: Partitioning, child: PhysicalPlan,
                 backend=TPU, coalescible: bool = True,
                 skew_splittable: bool = False):
        super().__init__(child)
        self.backend = backend
        self.partitioning = partitioning.bind(child.output)
        #: skew splitting only pays off for consumers that STREAM their
        #: per-partition batches (shuffled-hash-join probe sides); an
        #: aggregate/sort/window would just concat the chunks back at
        #: device-copy cost, so the join planner opts the probe exchange
        #: in explicitly (same pattern as coalescible/map_side_filter)
        self._skew_splittable = skew_splittable
        #: AQE partition coalescing is only sound when no sibling exchange
        #: must stay aligned with this one — the two exchanges feeding a
        #: co-partitioned join decide INDEPENDENTLY, so one coalescing
        #: while the other keeps hashing would silently mis-join; the join
        #: planner passes coalescible=False for both sides
        self._coalescible = coalescible
        self._materialized: Optional[List[List[ColumnarBatch]]] = None
        #: serializes one-shot materialization: under the parallel
        #: partition scheduler (and prefetch producer threads) several
        #: reduce partitions race into the first execute — a double
        #: materialize would run the whole map side twice and double-write
        #: shuffle blocks
        self._mat_lock = threading.Lock()
        #: map-side runtime filter (bloom-filter join pushdown): applied to
        #: each map partition's merged output BEFORE the split/write, so
        #: dropped rows never ride the shuffle.  Installed by the join
        #: after its build side materializes (ops/bloom.py; reference
        #: GpuBloomFilterMightContain pushed below the exchange).
        self.map_side_filter = None

    @property
    def output(self):
        return self.children[0].output

    def num_partitions(self):
        return self.partitioning.num_partitions

    # --- device kernels ---------------------------------------------------
    def _split_all(self, batch: ColumnarBatch, pids):
        """One map output to all of its pieces in one pass: the rows are
        ordered by target, each target's in their original order (dead
        rows last), so every array is gathered ONCE; piece ``t`` is rows
        ``[sum(counts[:t]), sum(counts[:t + 1]))`` of the result.  With it
        the int32 row count of every piece, for the host's one read."""
        from ...ops.join import partition_indices
        xp, nt = self.xp, self.num_partitions()
        live = batch.row_mask()     # of the input and, ordered, of the output
        perm, counts = partition_indices(
            xp, xp.where(live, pids, nt), nt + 1)
        cols = tuple(c.gather(perm, live) for c in batch.columns)
        return (ColumnarBatch(batch.names, cols, batch.num_rows),
                counts[:nt])

    def _map_all(self, batch: ColumnarBatch, map_id):
        """Partition ids and :meth:`_split_all` in one trace, so the
        partitioner's hash (a Pallas call on the chip) is traced once per
        key and input shape, not once per map."""
        ctx = EvalContext(batch, xp=self.xp)
        return self._split_all(
            batch, self.partitioning.partition_ids(ctx, batch, map_id))

    # --- map side: one output to its pieces -------------------------------
    def _map_declined(self) -> str:
        """Why a map output cannot go through the map program, or ""."""
        if isinstance(self.partitioning, RangePartitioning):
            # the bounds are data of this materialization, read by the
            # partitioner from its own state and looped over on the host
            return "range"
        return "" if self.backend == TPU else "numpy"

    def _order_map_output(self, merged: ColumnarBatch, cpid: int):
        """One map output ordered by target, with the pieces' row counts
        (still on the device): the map program's launch, no read."""
        nt = self.num_partitions()
        declined = self._map_declined()
        why = {"declined": declined} if declined else {}
        STATS["eager_fallbacks" if declined else "map_programs"] += 1
        part = self.partitioning
        with _trace.span("shuffle", "exchange.partition_ids", map=cpid,
                         **why):
            if declined == "range":
                pids = part.partition_ids(
                    EvalContext(merged, xp=self.xp), merged, cpid)
                return self._jit(
                    self._split_all, key=("split", nt))(merged, pids)
            return self._jit(self._map_all, key=(
                "map", type(part).__name__,
                exprs_key(part.exprs)
                if isinstance(part, HashPartitioning) else (), nt))(
                    merged, np.int32(cpid))

    def _split_map_output(self, merged: ColumnarBatch, cpid: int
                          ) -> List[Optional[ColumnarBatch]]:
        """The pieces of one map output, by target, each at its row
        count's bucket and carrying its host-known count; ``None`` for a
        target that got no row.  Two launches: the map program, then the
        program that cuts the pieces (off the TPU backend the same
        functions run un-jitted)."""
        ordered, counts = self._order_map_output(merged, cpid)
        declined = self._map_declined()
        why = {"declined": declined} if declined else {}
        with _trace.span("shuffle", "exchange.split", map=cpid,
                         partitions=self.num_partitions(), **why):
            return self._cut_pieces(ordered, counts)

    def _cut_pieces(self, ordered: ColumnarBatch, counts
                    ) -> List[Optional[ColumnarBatch]]:
        """The pieces out of a target-ordered map output, in one program
        keyed by the capacities it cuts to (``shrunk()``'s rule: the row
        count's bucket, powers of two, so a dataset makes few keys)."""
        xp = self.xp
        host_counts = np.asarray(counts).tolist()    # the map's one read
        caps = tuple(ordered.shrunk_capacity(n) if n else 0
                     for n in host_counts)
        todo = [t for t, c in enumerate(caps) if c]
        out: List[Optional[ColumnarBatch]] = [None] * len(caps)
        if not todo:
            return out

        def cut(batch, ns):
            starts = xp.cumsum(ns, dtype=xp.int32) - ns
            return tuple(batch.window(starts[t], ns[t], caps[t], xp)
                         for t in todo)

        pieces = self._jit(cut, key=("shrink", ordered.capacity, caps))(
            ordered, counts)
        if self.backend == TPU:
            STATS["shrink_programs"] += 1
        for t, p in zip(todo, pieces):
            out[t] = p.with_known_rows(host_counts[t])
        return out

    # --- materialization --------------------------------------------------
    def _ensure_materialized(self, tctx: TaskContext):
        if self._materialized is not None:
            return
        with self._mat_lock:
            if self._materialized is not None:
                return
            with _trace.span("shuffle", "exchange.materialize",
                             partitions=self.num_partitions()):
                self._materialize(tctx)
            # materialized partitions are RETAINED by this exec and may be
            # re-served (shared-subtree parents, AQE readers): pin them so
            # a downstream fused stage never donates their buffers
            from ...memory import retention as _ret
            for part in self._materialized or []:
                for b in part:
                    _ret.pin_batch(b)

    def _materialize(self, tctx: TaskContext):
        """Map side: split each child batch by target and hand the pieces to
        the shuffle manager (serializer + SORT/MULTITHREADED/ICI data
        plane); reduce side then fetches + host-concats per partition
        (SURVEY §3.4 write/read paths).

        Where the map outputs lie on several chips (several executors on
        this host: ``parallel/placement.py``) the whole exchange is ONE
        compiled all_to_all program instead (parallel/mesh.py) — the
        planned-query analog of the reference's UCX device-direct path."""
        from ...shuffle import get_shuffle_manager
        child = self.children[0]
        nt = self.num_partitions()
        mgr = get_shuffle_manager(tctx.conf)
        shuffle_id = mgr.new_shuffle_id()

        # run the child plan exactly ONCE; every downstream consumer
        # (range-bounds sampling, mesh plane, local plane) shares the
        # collected map outputs
        num_maps = child.num_partitions()
        map_out: List[Optional[ColumnarBatch]] = []
        from ...serving import lifecycle as _lc
        for cpid in range(num_maps):
            # lifecycle poll site `exchange`: the map side is the one
            # place a query re-runs its whole subtree serially — a
            # cancel/deadline must drain between map tasks, not after
            # all of them
            _lc.check_cancel("exchange")
            ctctx = TaskContext(cpid, tctx.conf, parent=tctx)
            with ctctx.as_current():
                got = list(child.execute(cpid, ctctx))
            with _counting_concats():
                map_out.append(ColumnarBatch.concat(got) if len(got) > 1
                               else (got[0] if got else None))

        if self.map_side_filter is not None:
            map_out = [self.map_side_filter(b) if b is not None else None
                       for b in map_out]

        # AQE partition coalescing: a tiny total map output routes whole
        # to reduce partition 0 — equal keys stay co-located (trivially)
        # and a range order is trivially preserved, while the downstream
        # plan stops paying nt-1 empty split/launch/sync rounds
        # (GpuCustomShuffleReaderExec coalesced-partitions analog)
        from ...config import ADAPTIVE_COALESCE_ROWS, ADAPTIVE_ENABLED
        from ...shuffle import get_shuffle_manager as _gsm
        _topo = _gsm(tctx.conf).topology
        coalesce = (nt > 1 and self._coalescible
                    # multi-slice: the coalesce decision is DATA-dependent
                    # (local map row count), so two slices could partition
                    # the same shuffle differently and split a key across
                    # reduce partitions — same hazard as co-partitioned
                    # sibling exchanges (coalescible=False); never coalesce
                    and (_topo is None or not _topo.multi_slice)
                    and bool(tctx.conf.get(ADAPTIVE_ENABLED))
                    and sum(b.num_rows_int for b in map_out
                            if b is not None)
                    <= int(tctx.conf.get(ADAPTIVE_COALESCE_ROWS)))

        if isinstance(self.partitioning, RangePartitioning):
            if coalesce:    # every row goes to partition 0: nothing sampled
                tctx.inc_metric("rangeBoundSamples", 0)
            else:
                with _trace.span("sort", "range_bounds", partitions=nt):
                    self._compute_range_bounds(map_out, tctx)

        topo = mgr.topology
        multi = topo is not None and topo.multi_slice

        # The layout decides the plane, no switch does: where this host is
        # several executors (``spark.executor.instances``, one chip each)
        # the map outputs lie on several chips.  An exchange to ONE
        # partition gathers by its nature: they come to chip 0 and are
        # merged there, with no block written or read.  Any other exchange
        # is one all_to_all program over the chips and leaves reduce
        # partition t on chip t % n; if the plane declines, that raises —
        # the wire plane below would bring every map output to one chip
        # and serialize it (``meshFallbacks``; never more than one in a
        # collect that ends).  Several slices never get here
        # (``placement.chips`` is then one chip): the mesh plane would
        # assemble all nt partitions from this slice's maps alone and
        # publish nothing for the peer slices to pull.
        from ...parallel import placement
        spread = (placement.chips(tctx.conf) if self.backend == TPU
                  else ())
        if len(spread) > 1 and (nt == 1 or coalesce):
            live = placement.gather([b for b in map_out if b is not None],
                                    spread[0])
            with _counting_concats():
                merged = ([ColumnarBatch.concat(live)] if len(live) > 1
                          else live)
            self._materialized = [merged] + [[] for _ in range(nt - 1)]
            return
        if len(spread) > 1:
            from ...parallel.mesh import MeshShuffleUnsupported
            try:
                self._mesh_materialize(map_out, nt, tctx)
            except MeshShuffleUnsupported as e:
                from ...parallel.mesh import STATS as MESH_STATS
                MESH_STATS["fallbacks"] += 1
                tctx.inc_metric("meshFallbacks")
                raise RuntimeError(
                    f"{self.simple_string()}: the mesh plane declined an "
                    f"exchange between {len(spread)} executors on their "
                    f"own chips ({e}); there is no other plane between "
                    f"chips (spark.executor.instances="
                    f"{len(spread)})") from e
            tctx.inc_metric("meshExchanges")
            self._maybe_skew_split(tctx)
            return

        # multi-slice: namespace map ids per slice so the peer slices'
        # blocks never collide with ours (symmetric deployments: every
        # slice runs the same plan, so num_maps agrees — docs/distributed)
        map_base = topo.slice_id * num_maps if multi else 0

        #: one piece of every map: what the reduce side will merge
        samples: List[ColumnarBatch] = []

        def _write_map(cpid: int, merged: ColumnarBatch) -> None:
            if nt == 1 or coalesce:
                pieces: List[Optional[ColumnarBatch]] = [merged]
            else:
                pieces = self._split_map_output(merged, cpid)
            live = next((p for p in pieces if p is not None), None)
            if live is not None:
                samples.append(live)
            with _trace.span("shuffle", "exchange.write", map=cpid):
                mgr.write_map_output(shuffle_id, map_base + cpid, pieces)

        for cpid, merged in enumerate(map_out):
            if merged is None:
                continue
            _write_map(cpid, merged)
        declined = concat_declined(samples) if len(samples) > 1 else ""
        read_why = {"declined": declined} if declined else {}

        # lost-block recompute lineage: the collected map outputs + the
        # bound partitioner (range bounds already fixed above) make the
        # re-split deterministic, so a recomputed block is bit-identical
        # to the lost one.  Only THIS slice's maps are recomputable; a
        # peer slice's lost block keeps the FetchFailed contract.
        def _recompute_map(map_id: int) -> None:
            local = map_id - map_base
            if not (0 <= local < num_maps):
                from ...shuffle import ShuffleFetchFailed
                raise ShuffleFetchFailed(
                    f"map {map_id} belongs to a peer slice; no local "
                    f"lineage to recompute it")
            merged = map_out[local]
            if merged is not None:
                _write_map(local, merged)
        mgr.register_recompute(shuffle_id, _recompute_map)

        total_maps = num_maps * (topo.num_slices if multi else 1)
        out: List[List[ColumnarBatch]] = []
        try:
            for t in range(nt):
                if multi and not topo.is_local(t, nt):
                    # two-tier plane: this slice assembles ONLY the reduce
                    # partitions it owns; peer slices pull their own blocks
                    # (published above) over the DCN transport
                    out.append([])
                    continue
                with _trace.span("shuffle", "exchange.read", partition=t,
                                 **read_why), _counting_concats():
                    got = mgr.read_reduce_partition(shuffle_id, total_maps,
                                                    t)
                out.append([got] if got is not None else [])
        except BaseException:
            # an aborted materialization (query cancel/deadline, fetch
            # failure) must not leave the lineage closure — which pins
            # every map output batch — registered in the process-wide
            # manager forever (found by tools/leak_sentinel.py)
            mgr.unregister_recompute(shuffle_id)
            mgr.cleanup(shuffle_id)
            raise
        if not multi:
            mgr.cleanup(shuffle_id)
        else:
            # peers may still be fetching this shuffle's blocks — defer
            # reclamation to the TTL sweep instead of leaking forever.
            # The recompute lineage is only reachable from OUR read loop
            # (a peer's failed fetch fails in the peer's manager), so it
            # must not pin the map outputs across the TTL window.
            mgr.unregister_recompute(shuffle_id)
            mgr.defer_cleanup(shuffle_id)
        self._materialized = out
        self._maybe_skew_split(tctx)

    def _maybe_skew_split(self, tctx: TaskContext) -> None:
        """AQE skew handling at the reader (reference
        ``GpuCustomShuffleReaderExec.scala:87-91`` skewed-partition
        specs): a materialized reduce partition whose row count exceeds
        skewedPartitionFactor x the median non-empty partition (and the
        absolute row threshold) is re-sliced into contiguous
        median-sized chunks.  Downstream shuffled hash joins stream
        probe batches, so each chunk joins against the full build
        partition — one hot key no longer sends the join through the
        OOM-retry path.  Chunks stay inside their partition, so key
        co-location (and range order: slices are contiguous) is
        untouched, which also keeps it safe for co-partitioned sibling
        exchanges, unlike coalescing."""
        from ...config import (ADAPTIVE_ENABLED, SKEW_JOIN_ENABLED,
                               SKEW_JOIN_FACTOR, SKEW_JOIN_ROWS)
        if not (self._skew_splittable
                and bool(tctx.conf.get(ADAPTIVE_ENABLED))
                and bool(tctx.conf.get(SKEW_JOIN_ENABLED))):
            return
        sizes = [sum(b.num_rows_int for b in p)
                 for p in self._materialized]
        nonzero = sorted(s for s in sizes if s > 0)
        if len(nonzero) < 2:
            return
        median = nonzero[len(nonzero) // 2]
        factor = int(tctx.conf.get(SKEW_JOIN_FACTOR))
        thresh = int(tctx.conf.get(SKEW_JOIN_ROWS))
        target = max(median, thresh // factor, 1)
        for t, part in enumerate(self._materialized):
            if sizes[t] <= thresh or sizes[t] <= factor * median:
                continue
            chunks: List[ColumnarBatch] = []
            for b in part:
                n = b.num_rows_int
                k = -(-n // target)
                if k <= 1:
                    chunks.append(b)
                    continue
                step = -(-n // k)
                for off in range(0, n, step):
                    chunks.append(b.sliced(off, min(step, n - off)))
            if len(chunks) > len(part):
                STATS["skew_splits"] += 1
                STATS["skew_chunks"] += len(chunks) - len(part)
                tctx.inc_metric("skewSplitPartitions")
                self._materialized[t] = chunks

    def _empty_batch(self) -> ColumnarBatch:
        return empty_batch_for(self.output)

    def _mesh_pids(self, batch: ColumnarBatch, shard: int, n_dev: int):
        """Target CHIP of every row of one shard (target ``t`` lives on
        chip ``t % n_dev``), on the shard's chip: one cached program, or
        the partitioner's own eager pass where it reads its bounds."""
        part = self.partitioning

        def chip_ids(b, map_id):
            ids = part.partition_ids(EvalContext(b, xp=self.xp), b, map_id)
            return (ids % n_dev).astype(self.xp.int32)

        if self._map_declined():
            return chip_ids(batch, shard)
        return self._jit(chip_ids, key=(
            "meshpids", type(part).__name__,
            exprs_key(part.exprs) if isinstance(part, HashPartitioning)
            else (), self.num_partitions(), n_dev))(batch, np.int32(shard))

    def _mesh_materialize(self, map_out: List[Optional[ColumnarBatch]],
                          nt: int, tctx: TaskContext) -> None:
        """The exchange between executors on their own chips: one compiled
        all_to_all program (``parallel/mesh.py``).  Raises
        ``MeshShuffleUnsupported`` where the batch layout cannot ride it.

        Rows route over ICI to their OWNER chip (target % n_dev), taken
        from the chip their map ran on; where a chip owns several targets
        (``nt`` above the chip count) its received batch is split there,
        with the local plane's map and shrink programs.  Reduce partition
        ``t`` is handed on living on chip ``t % n_dev``.  Spans, inside
        ``srt:shuffle:mesh_exchange``: ``.map`` (what is launched per
        chip before the collective: merges, partition ids), ``.collective``
        (the program's launch), ``.counts`` (the exchange's one read) and
        ``.shrink`` (each chip's shard cut to its row count's bucket)."""
        from ...columnar.batch import _codes_column, _dict_column
        from ...columnar.encoded import (DictEncodedColumn, RLEColumn,
                                         same_dictionary)
        from ...parallel import placement
        from ...parallel.mesh import (MeshShuffleUnsupported, align_batches,
                                      device_mesh, mesh_shuffle_batches)
        chips = placement.chips(tctx.conf)
        n_dev = len(chips)
        # content-determined partitionings only where a chip owns several
        # targets: the second-stage split recomputes partition ids on the
        # RECEIVED batch, which round-robin (source-position-dependent)
        # cannot survive
        if nt > n_dev and not isinstance(
                self.partitioning, (HashPartitioning, RangePartitioning)):
            raise MeshShuffleUnsupported(
                f"{type(self.partitioning).__name__} into {nt} partitions "
                f"on {n_dev} chips")
        mesh = device_mesh(devices=chips)

        # shard d = the map outputs that lie on chip d (m % n_dev where a
        # map output says nothing of where it lies)
        shard_batches: List[List[ColumnarBatch]] = [[] for _ in range(n_dev)]
        for cpid, b in enumerate(map_out):
            if b is not None and b.num_rows_int > 0:
                at = placement.chip_of(b)
                shard_batches[chips.index(at) if at in chips
                              else cpid % n_dev].append(b)
        if not any(shard_batches):
            self._materialized = [[] for _ in range(nt)]
            return
        with _trace.span("shuffle", "mesh_exchange", partitions=nt,
                         devices=n_dev):
            with _trace.span("shuffle", "mesh_exchange.map"), \
                    _counting_concats():
                merged = [ColumnarBatch.concat(bs) if len(bs) > 1
                          else (bs[0] if bs else None)
                          for bs in shard_batches]
                template = next(b for b in merged if b is not None)
                merged = [b if b is not None
                          else _empty_like(template, chips[d])
                          for d, b in enumerate(merged)]
                # a dictionary is not row data: where every shard's column
                # is encoded over one dictionary the codes ride the
                # exchange and each chip keeps its own copy; any other
                # encoding is decoded
                keep = [same_dictionary([b.columns[ci] for b in merged])
                        for ci in range(template.num_cols)]
                aligned = align_batches([_recolumned(b, (
                    c if keep[ci] or not isinstance(
                        c, (DictEncodedColumn, RLEColumn))
                    else c.materialized()
                    for ci, c in enumerate(b.columns))) for b in merged])
                pids = [self._mesh_pids(b, d, n_dev)
                        for d, b in enumerate(aligned)]
                plain = [_recolumned(b, map(_codes_column, b.columns))
                         for b in aligned]
            out, record = mesh_shuffle_batches(mesh, plain, pids, n_dev)
            tctx.inc_metric("meshExchangeBytes", record["exchanged_bytes"])
            tctx.inc_metric("meshCrossChipBytes", record["sent_bytes"])
            tctx.inc_metric("meshFallbacks", 0)  # beside meshExchanges

            # what chip d received, at its row count's bucket, over chip
            # d's own dictionaries again
            received: List[Optional[ColumnarBatch]] = []
            with _trace.span("shuffle", "mesh_exchange.shrink"):
                for d, b in enumerate(out):
                    n = b.num_rows_int
                    if n == 0:
                        received.append(None)
                        continue
                    b = self._cut_pieces(
                        b, np.asarray([n], dtype=np.int32))[0]
                    received.append(_recolumned(b, map(
                        _dict_column, aligned[d].columns, b.columns)))
        if nt <= n_dev:
            mat = [[b] if b is not None else [] for b in received[:nt]]
        else:
            # second stage: chip d owns targets {d, d+n_dev, ...} — split
            # what it received by the full partition id, there: every
            # chip's map program is launched before the first count read
            mat = [[] for _ in range(nt)]
            staged = [(d, self._order_map_output(b, d))
                      for d, b in enumerate(received) if b is not None]
            for d, (ordered, counts) in staged:
                with _trace.span("shuffle", "exchange.split", map=d,
                                 partitions=nt):
                    pieces = self._cut_pieces(ordered, counts)
                for t, piece in enumerate(pieces):
                    if piece is not None:
                        mat[t].append(piece)
        record["batches_handed_on_live_on"] = sorted({
            placement.label(placement.chip_of(b))
            for part in mat for b in part})
        self._materialized = mat

    def _compute_range_bounds(self, map_out: List[Optional[ColumnarBatch]],
                              tctx: TaskContext):
        """Sample the collected map outputs, sort the sample by the orders,
        take quantile rows as bounds (reference
        GpuRangePartitioner.createRangeBounds)."""
        from .sortlimit import SortExec
        part: RangePartitioning = self.partitioning  # type: ignore
        samples = []
        for batch in map_out:
            if batch is None:
                continue
            n = batch.num_rows_int
            if n > 4096:  # cheap deterministic sample
                batch = batch.sliced(0, 4096)
            tctx.inc_metric("rangeBoundSamples", min(n, 4096))
            samples.append(batch)
        if not samples:
            part.set_bounds(self._empty_batch())
            return
        from ...parallel import placement
        samples = placement.gather(samples)     # a sample gathers by nature
        merged = ColumnarBatch.concat(samples) if len(samples) > 1 else samples[0]
        sorter = SortExec(part.orders, self.children[0], self.backend)
        merged = sorter._fn(merged)
        # evaluate sort keys over the sorted batch, pick boundary rows
        ctx = EvalContext(merged, xp=self.xp)
        key_cols = tuple(o.child.eval(ctx) for o in sorter._bound)
        names = tuple(f"_k{i}" for i in range(len(key_cols)))
        keys_batch = ColumnarBatch(names, key_cols, merged.num_rows)
        n = merged.num_rows_int
        nparts = part.num_partitions
        idxs = [min(n - 1, max(0, (i + 1) * n // nparts))
                for i in range(nparts - 1)] if n else []
        rows = [keys_batch.sliced(i, 1) for i in idxs]
        bounds = ColumnarBatch.concat(rows) if len(rows) > 1 else (
            rows[0] if rows else keys_batch.sliced(0, 0))
        if self.backend == TPU and len(placement.chips(tctx.conf)) > 1:
            # every chip's partitioner reads the bounds: through the host
            # they are committed to no chip and follow their reader
            import jax
            from .basic import _to_backend_batch
            bounds = _to_backend_batch(
                jax.device_get(bounds), TPU
            ).with_known_rows(bounds.num_rows_int)
        part.set_bounds(bounds)

    def execute(self, pid, tctx):
        self._ensure_materialized(tctx)
        yield from self._materialized[pid]

    def simple_string(self):
        return f"{self.node_name()} {self.partitioning.simple_string()}"


class BroadcastExchangeExec(PhysicalPlan):
    """Materialize the (small) child once as a single concatenated batch,
    served to every consumer partition (reference serializes to host and
    re-uploads per task; locally the device batch is just shared).

    Build-cache contract: consumers attach derived build-side artifacts to
    the batch itself (``_join_build_sides`` — the hash-join fast path's
    sorted key tuples, keyed by bound build-key signature), so every probe
    partition and every probe batch of every join over this broadcast
    shares ONE build-side preparation, exactly like the reference builds
    its broadcast hash table once (``GpuHashJoin.scala:298``).  The dict
    lives on the batch, not the exec, so it dies with the batch."""

    def __init__(self, child: PhysicalPlan, backend=TPU):
        super().__init__(child)
        self.backend = backend
        self._cached: Optional[ColumnarBatch] = None
        #: where partitions are spread over several chips: the copy each
        #: chip's probe partitions join against (a broadcast crosses
        #: chips by its nature), with build-side artifacts of its own
        self._replicas: Dict[object, ColumnarBatch] = {}
        #: parallel consumer partitions race into the first
        #: broadcast_batch; the build must run exactly once
        self._mat_lock = threading.Lock()

    @property
    def output(self):
        return self.children[0].output

    def num_partitions(self):
        return 1

    def broadcast_batch(self, tctx: TaskContext) -> ColumnarBatch:
        from ...parallel import placement
        chip = (placement.home_chip(tctx.partition_id, tctx.conf)
                if self.backend == TPU else None)
        if chip is None:
            if self._cached is not None:
                return self._cached
            with self._mat_lock:
                return self._broadcast_batch_locked(tctx)
        with self._mat_lock:
            got = self._replicas.get(chip)
            if got is None:
                whole = self._broadcast_batch_locked(tctx)
                got = placement.move(whole, chip, terminal=True)
                if got is not whole:
                    got._join_build_sides = {}
                    from ...memory import retention as _ret
                    _ret.pin_batch(got)
                self._replicas[chip] = got
            return got

    def _broadcast_batch_locked(self, tctx: TaskContext) -> ColumnarBatch:
        if self._cached is None:
            # cross-query broadcast sharing (docs/serving.md): key the
            # child subtree by content and serve a process-cached batch —
            # the same dimension table broadcast by N queries/sessions
            # uploads and build-prepares once.  The shared batch stays
            # pinned by the cache, so donation safety is unchanged.
            from ...config import SERVING_BROADCAST_SHARE
            share_key = None
            if bool(tctx.conf.get(SERVING_BROADCAST_SHARE)):
                from ...serving import broadcast_cache as _bc
                share_key = _bc.content_key(self.children[0], tctx.conf)
                if share_key is not None:
                    got = _bc.lookup(share_key)
                    if got is not None:
                        # this exec takes its OWN pin (below) so a cache
                        # eviction can never unpin a batch a live plan
                        # still serves; the artifact dict already exists
                        # from the original build
                        self._cached = got
                        from ...memory import retention as _ret
                        _ret.pin_batch(self._cached)
                        return self._cached
            with _trace.span("broadcast", "build"):
                batches = []
                with _trace.span("broadcast", "build.collect"):
                    for cpid in range(self.children[0].num_partitions()):
                        ctctx = TaskContext(cpid, tctx.conf, parent=tctx)
                        with ctctx.as_current():
                            batches.extend(
                                self.children[0].execute(cpid, ctctx))
                # one batch on the device for every probe to share: the
                # pieces brought to one chip and packed without padding
                with _trace.span("broadcast", "build.upload"):
                    from ...parallel import placement
                    batches = placement.gather(batches)
                    if not batches:
                        self._cached = empty_batch_for(self.output)
                    else:
                        self._cached = (ColumnarBatch.concat(batches)
                                        if len(batches) > 1 else batches[0])
            from ...memory.spill import batch_device_bytes
            tctx.inc_metric_late("broadcastBuildRows", self._cached.num_rows)
            tctx.inc_metric("broadcastBuildBytes",
                            batch_device_bytes(self._cached))
            if share_key is not None:
                from ...serving import broadcast_cache as _bc
                _bc.store(share_key, self._cached,
                          int(self.children[0].estimate_bytes() or 0))
            # seed the artifact cache eagerly: a concat result could be a
            # pass-through of a child batch that already carries artifacts
            # from an unrelated join over different keys — the per-key
            # signatures keep those distinct, but the dict must exist on
            # THIS object for all consumers to share one instance
            if getattr(self._cached, "_join_build_sides", None) is None:
                self._cached._join_build_sides = {}
            # the broadcast batch is shared by every probe partition for
            # the plan's lifetime: pin it against whole-stage donation
            from ...memory import retention as _ret
            _ret.pin_batch(self._cached)
        return self._cached

    def execute(self, pid, tctx):
        yield self.broadcast_batch(tctx)
