"""Typed configuration registry — the TPU equivalent of the reference's
``RapidsConf.scala`` (Spark-style ``ConfEntry`` builder with docs, defaults,
``internal()``/``startupOnly()``/``commonlyUsed()`` attributes; reference
``RapidsConf.scala:120+``, 197 ``spark.rapids.*`` keys).

Keys keep the ``spark.rapids.*`` naming so a user of the reference finds the
same knobs; TPU-specific keys live under ``spark.rapids.tpu.*``.
``RapidsConf.help()`` -> :func:`help_text` emits the markdown config docs the
same way the reference's docgen does (``RapidsConf.scala:2057-2103``).
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

__all__ = ["ConfEntry", "RapidsConf", "register", "ENTRIES", "help_text"]


@dataclass
class ConfEntry:
    key: str
    doc: str
    default: Any
    type_: type
    internal: bool = False
    startup_only: bool = False
    commonly_used: bool = False
    checker: Optional[Callable[[Any], bool]] = None

    def convert(self, raw: Any) -> Any:
        if raw is None:
            return self.default
        if self.type_ is bool:
            if isinstance(raw, bool):
                return raw
            return str(raw).strip().lower() in ("true", "1", "yes")
        if self.type_ is int:
            return int(raw)
        if self.type_ is float:
            return float(raw)
        if self.type_ is list:
            if isinstance(raw, (list, tuple)):
                return list(raw)
            return [s.strip() for s in str(raw).split(",") if s.strip()]
        return str(raw)


ENTRIES: Dict[str, ConfEntry] = {}


def register(key: str, doc: str, default: Any, type_: Optional[type] = None,
             internal: bool = False, startup_only: bool = False,
             commonly_used: bool = False) -> ConfEntry:
    e = ConfEntry(key, doc, default,
                  type_ or (type(default) if default is not None else str),
                  internal, startup_only, commonly_used)
    ENTRIES[key] = e
    return e


# --- SQL behavior (names follow reference RapidsConf.scala) -----------------
SQL_ENABLED = register(
    "spark.rapids.sql.enabled",
    "Enable or disable TPU acceleration of SQL operations.", True,
    commonly_used=True)
SQL_MODE = register(
    "spark.rapids.sql.mode",
    "executeOnGPU runs supported ops on the accelerator; explainOnly plans and "
    "reports what would run without touching the device.", "executeongpu")
EXPLAIN = register(
    "spark.rapids.sql.explain",
    "NONE | NOT_ON_GPU | ALL: log why operators are or are not placed on the "
    "accelerator.", "NOT_ON_GPU", commonly_used=True)
BATCH_SIZE_ROWS = register(
    "spark.rapids.sql.batchSizeRows",
    "Target row count cap per columnar batch (shape-bucketing granularity).",
    1 << 20)
SORT_OOC_TARGET_ROWS = register(
    "spark.rapids.sql.sort.outOfCore.targetRows",
    "Row budget per device-resident chunk in the out-of-core sort "
    "(reference GpuOutOfCoreSortIterator, GpuSortExec.scala:242): inputs "
    "larger than this are sorted as spillable runs and k-way merged in "
    "chunks of at most this many rows.", 1 << 22)
WINDOW_BATCH_TARGET_ROWS = register(
    "spark.rapids.sql.window.batchTargetRows",
    "Window inputs larger than this many rows are processed in "
    "key-complete chunks (every chunk holds whole partitions, cut at "
    "partition-key boundaries) instead of one concatenated batch — the "
    "reference's key-batched windows (GpuKeyBatchingIterator.scala). "
    "Bounded by the largest single partition.", 1 << 22)
JOIN_OUTPUT_CHUNK_ROWS = register(
    "spark.rapids.sql.join.outputChunkRows",
    "Join outputs larger than this many rows are gathered in chunks of "
    "this size instead of one worst-case buffer (reference "
    "JoinGatherer.scala:730 lazy chunked gather).", 1 << 22)
JOIN_BUILD_CACHE_ENABLED = register(
    "spark.rapids.sql.join.buildSideCache.enabled",
    "Cache the sorted build-side join keys on the build batch so a "
    "broadcast/shuffled hash join sorts its build side once and every "
    "probe batch only binary-searches it (the sort-based analog of the "
    "reference building its hash table once per build side, "
    "GpuHashJoin.scala:298).  Off falls back to the union-rank path, "
    "which re-sorts probe+build per probe batch.", True)
JOIN_SPECULATIVE_SIZING = register(
    "spark.rapids.sql.join.speculativeSizing.enabled",
    "Dispatch each probe batch's join gather at an output capacity "
    "predicted from the previous batch's selectivity BEFORE the blocking "
    "count readback, so the one sizing fetch overlaps the gather instead "
    "of serializing it; an overflow of the predicted bucket re-gathers "
    "at the exact size.", True)
JOIN_INITIAL_SELECTIVITY = register(
    "spark.rapids.sql.join.speculativeSizing.initialSelectivity",
    "First-batch output-rows-per-probe-row estimate used by speculative "
    "join output sizing before any realized selectivity is observed.",
    1.0)
CONCURRENT_TASKS = register(
    "spark.rapids.sql.concurrentGpuTasks",
    "Number of tasks that may hold the device semaphore concurrently "
    "(reference GpuSemaphore, RapidsConf.scala:535).", 1, commonly_used=True)
FUSION_ENABLED = register(
    "spark.rapids.tpu.sql.fusion.enabled",
    "Fuse filter/project chains (and their terminal hash aggregate) into "
    "one compiled XLA program per pipeline stage — whole-stage codegen, "
    "the TPU analog of the reference's tiered projection + kernel reuse "
    "(basicPhysicalOperators.scala:500, SURVEY §3.3).", True)
WHOLE_STAGE_ENABLED = register(
    "spark.rapids.tpu.sql.wholeStage.enabled",
    "Deepened whole-stage formation (docs/whole_stage.md): hash "
    "aggregates (partial/complete) and hash-join probe phases become "
    "stage TERMINALS — the upstream filter/project chain compiles into "
    "the terminal's own program under one stage-signature kernel-cache "
    "key, the fused filter mask feeds the aggregate/probe directly, and "
    "intermediates never materialize.  Off keeps only >=2-op map-chain "
    "fusion (requires spark.rapids.tpu.sql.fusion.enabled).", True)
WHOLE_STAGE_DONATION = register(
    "spark.rapids.tpu.sql.wholeStage.donation.enabled",
    "Donate a fused map-stage's input buffers to its compiled program "
    "(XLA donate_argnums) so the stage output reuses the input's HBM. "
    "Guarded by the batch retention registry (memory/retention.py): "
    "donation is declined whenever the batch is pinned by the scan "
    "upload cache, a broadcast, a materialized shuffle partition, the "
    "spill tier, a prefetch queue, or a transfer stager — or when its "
    "provenance is unknown or it carries shared-dictionary encoded "
    "columns.  Buffers are only physically reclaimed on real device "
    "backends (XLA:CPU ignores donation); the safety decision runs "
    "everywhere.", True)
WHOLE_STAGE_SORT_WINDOW = register(
    "spark.rapids.tpu.sql.wholeStage.sortWindowTerminal.enabled",
    "Sort/window stage terminals (docs/whole_stage.md): a SortExec or "
    "WindowExec absorbs the upstream filter/project chain into its own "
    "compiled program, and a WindowExec additionally absorbs the "
    "planner-inserted partition sort — partition sort + segmented frame "
    "evaluation ride ONE stage program instead of one dispatch per op. "
    "Requires spark.rapids.tpu.sql.wholeStage.enabled.", True)
JOIN_FUSED_PROBE = register(
    "spark.rapids.tpu.sql.join.fusedProbe.enabled",
    "Single-program probe pipeline: each probe batch runs multi-key "
    "search + run-end expansion + pair generation + the gather of ALL "
    "output columns on both sides as ONE compiled program that also "
    "returns the sizing scalars — at most two device launches per probe "
    "batch (the optional second handles a speculative-bucket overflow "
    "re-gather), with the one batched sizing readback unchanged.  Off "
    "keeps the separate probe-search and gather programs.", True)
DISPATCH_COALESCE_ENABLED = register(
    "spark.rapids.tpu.sql.dispatch.coalesce.enabled",
    "Dispatch coalescer for the many-small-partitions regime "
    "(docs/whole_stage.md): consecutive small same-shape batches entering "
    "a fused map stage are stacked on a leading axis and the stage "
    "program is vmapped over the stack INSIDE one compiled program — N "
    "batches, one device launch.  Only batches whose padded capacity "
    "bucket and column layout match coalesce (the padding buckets are "
    "the existing capacity quantization); tracer stage spans carry "
    "coalesced_n and deviceDispatches counts real launches.", True)
DISPATCH_COALESCE_MAX_BATCHES = register(
    "spark.rapids.tpu.sql.dispatch.coalesce.maxBatches",
    "Upper bound on the number of batches stacked into one coalesced "
    "stage launch.", 8)
DISPATCH_COALESCE_MAX_ROWS = register(
    "spark.rapids.tpu.sql.dispatch.coalesce.maxRows",
    "Only batches whose padded capacity is at or below this many rows "
    "are eligible for dispatch coalescing — large batches already "
    "amortize their launch overhead.", 1 << 16)
ANSI_ENABLED = register(
    "spark.sql.ansi.enabled",
    "ANSI mode: overflow/invalid-cast raise instead of null/wrap.", False)
CASE_SENSITIVE = register(
    "spark.sql.caseSensitive", "Case sensitive column resolution.", False)
SESSION_TIMEZONE = register(
    "spark.sql.session.timeZone", "Session timezone (UTC only on device, "
    "mirroring the reference's UTC-only timezone check).", "UTC")
SHUFFLE_PARTITIONS = register(
    "spark.sql.shuffle.partitions", "Default shuffle partition count.", 8)
EXECUTOR_INSTANCES = register(
    "spark.executor.instances",
    "Spark's own key: how many executors the application runs.  This "
    "process is one host; with n > 1 on a host that shows at least n "
    "chips it is n executors, one chip each: partition t of a relation "
    "and reduce partition t of an exchange live on chip t % n, a task "
    "runs on its partition's chip, and an exchange between them is one "
    "all_to_all program over the chips' interconnect "
    "(parallel/placement.py, docs/distributed.md).  1 (default) or a "
    "host with fewer chips: one executor on chip 0.", 1)
AUTO_BROADCAST_THRESHOLD = register(
    "spark.rapids.sql.autoBroadcastJoinThreshold",
    "Maximum build-side size in bytes for which an equi-join uses a "
    "broadcast hash join instead of a shuffled hash join.",
    10 * 1024 * 1024, commonly_used=True)

# --- memory / runtime -------------------------------------------------------
ALLOC_FRACTION = register(
    "spark.rapids.memory.gpu.allocFraction",
    "Fraction of device HBM the buffer pool may use.", 0.85)
RESERVE_BYTES = register(
    "spark.rapids.memory.gpu.reserve",
    "Device memory reserved for XLA scratch/system.", 640 << 20)
OOM_SYNC_MODE = register(
    "spark.rapids.memory.oom.syncMode",
    "When the per-kernel OOM guard forces device synchronization: "
    "'always' blocks after every kernel (every execution-time OOM lands "
    "inside the guard, at one host-device round trip per kernel), 'never' "
    "lets dispatch stay asynchronous (OOM surfaces at the next "
    "materialization point), 'auto' syncs only under memory pressure — "
    "accounted pool usage above oom.syncWatermark, armed test OOM "
    "injection, or a recently observed device OOM.", "auto")
D2H_PREPACK = register(
    "spark.rapids.tpu.d2h.prepack",
    "Device-side pre-pack for host fetches (shuffle frames, spill, result "
    "collection): integer bit-width narrowing, lossless float64->float32 "
    "and bool bit-packing shrink bytes before they cross the host link "
    "(reference: nvcomp device codecs, NvcompLZ4CompressionCodec.scala). "
    "'auto' enables it on a non-CPU backend, 'true' "
    "forces it everywhere (CPU-mesh measurement), 'false' disables.",
    "auto")
D2H_PREPACK_MIN_BYTES = register(
    "spark.rapids.tpu.d2h.prepack.minBytes",
    "Minimum narrowable payload (bytes) before the pre-pack probe round "
    "trip pays for itself; smaller batches ride the plain packed fetch.",
    1 << 20)
D2H_PACK_F64 = register(
    "spark.rapids.tpu.d2h.packFloat64",
    "Include float64 columns in the packed single-transfer D2H fetch. "
    "On TPU, f64 is an emulated double-float; the packed encoding is "
    "bit-faithful to every value the device can itself COMPUTE, but an "
    "uploaded-and-untouched f64 below ~1e-29 whose low component falls "
    "in the f32-denormal range loses those low bits (device arithmetic "
    "flushes them identically).  Set false to fetch f64 columns with "
    "full storage fidelity at one extra transfer round trip each.", True)
# --- encoded columnar execution (docs/encoded_columns.md) ------------------
ENCODED_ENABLED = register(
    "spark.rapids.tpu.sql.encoded.enabled",
    "Keep dictionary/RLE-encoded columns encoded THROUGH the engine "
    "instead of materializing at the scan: filters evaluate predicates "
    "on the dictionary, joins probe on integer codes, group-bys/sorts "
    "run on codes, and the shuffle serializer ships narrowed codes with "
    "each dictionary sent once per batch (or once per exchange via the "
    "ref cache).  This is the structural kill switch: off means no "
    "encoded column is ever created, so every plan takes the raw path.",
    True, commonly_used=True)
ENCODED_MAX_CARDINALITY = register(
    "spark.rapids.tpu.sql.encoded.maxDictionaryCardinality",
    "Columns with more distinct values than this decline dictionary "
    "encoding at the scan (and dictionary unification declines at "
    "concat).  Encoding also declines when distinct values exceed half "
    "the rows.", 4096)
ENCODED_FILTER_ENABLED = register(
    "spark.rapids.tpu.sql.encoded.filter.enabled",
    "Evaluate eligible single-column filter predicates once over the "
    "dictionary (plus its null slot) and select rows by code lookup "
    "instead of evaluating on every row.  Read at kernel-trace time.",
    True)
ENCODED_JOIN_ENABLED = register(
    "spark.rapids.tpu.sql.encoded.join.enabled",
    "Lower equi-join keys whose both sides are dictionary-encoded into "
    "the build side's integer code space (probe codes remapped on the "
    "host via the dictionary registry) so the join sorts/searches int32 "
    "codes instead of padded string matrices.", True)
ENCODED_AGG_SORT_ENABLED = register(
    "spark.rapids.tpu.sql.encoded.aggSort.enabled",
    "Group and sort dictionary-encoded columns by their integer codes "
    "(sorted dictionaries make code order == value order).  Read at "
    "kernel-trace time.", True)
ENCODED_SHUFFLE_ENABLED = register(
    "spark.rapids.tpu.sql.encoded.shuffle.enabled",
    "Ship encoded columns over the shuffle/broadcast wire as narrowed "
    "codes + dictionary (the encoded-batch wire format, frame version "
    "2) instead of materialized value buffers.", True)
ENCODED_SHUFFLE_DICT_REFS = register(
    "spark.rapids.tpu.sql.encoded.shuffle.dictRefs.enabled",
    "Replace repeated dictionaries in shuffle frames with a content-hash "
    "reference resolved from the in-process dictionary registry, so "
    "repeated batches of one exchange pay only code bytes.  Automatically "
    "bypassed (inline dictionaries) on multi-slice topologies where "
    "frames cross process boundaries.", True)

#: per-op opt-out lookup used by columnar.encoded.op_enabled
ENCODED_OP_CONFS = {
    "filter": ENCODED_FILTER_ENABLED,
    "join": ENCODED_JOIN_ENABLED,
    "aggsort": ENCODED_AGG_SORT_ENABLED,
    "shuffle": ENCODED_SHUFFLE_ENABLED,
}

OOM_SYNC_WATERMARK = register(
    "spark.rapids.memory.oom.syncWatermark",
    "Accounted-pool usage fraction above which syncMode=auto blocks "
    "after every kernel to catch allocation failures eagerly.", 0.6)
HOST_SPILL_STORAGE_SIZE = register(
    "spark.rapids.memory.host.spillStorageSize",
    "Host memory budget for spilled device buffers.", 1 << 30)
SPILL_DIR = register(
    "spark.rapids.memory.spillDir", "Directory for the disk spill tier.",
    "/tmp/rapids_tpu_spill")
GPU_DEBUG = register(
    "spark.rapids.memory.gpu.debug",
    "Log every spill-catalog registration/removal with the owning call "
    "site — the reference's RMM debug allocation logging analog "
    "(RapidsConf.scala:366); leak_report() names still-registered "
    "handles and their origins.", False)
TEST_INJECT_RETRY_OOM = register(
    "spark.rapids.sql.test.injectRetryOOM",
    "Test hook: make the Nth retryable block throw a synthetic RetryOOM "
    "(reference RapidsConf.scala:1371).", 0, internal=True)
TEST_INJECT_SPLIT_OOM = register(
    "spark.rapids.sql.test.injectSplitAndRetryOOM",
    "Test hook: make the Nth retryable block throw SplitAndRetryOOM.",
    0, internal=True)

# --- adaptive execution + cost optimizer -----------------------------------
ADAPTIVE_ENABLED = register(
    "spark.sql.adaptive.enabled",
    "Adaptive query execution: joins re-decide broadcast-vs-shuffle from "
    "the build side's OBSERVED size at runtime (reference AQE integration, "
    "GpuOverrides.scala:4392-4452 + GpuCustomShuffleReaderExec).", True)
ADAPTIVE_COALESCE_ROWS = register(
    "spark.sql.adaptive.coalescePartitions.minRows",
    "Exchanges whose total map output has at most this many rows route "
    "everything to one reduce partition (AQE partition coalescing, "
    "GpuCustomShuffleReaderExec analog): tiny post-aggregation states "
    "stop paying per-partition split/launch/sync overhead.", 1 << 16)
SKEW_JOIN_ENABLED = register(
    "spark.sql.adaptive.skewJoin.enabled",
    "Skewed-partition splitting at exchange materialization (the "
    "reference's GpuCustomShuffleReaderExec skewed-partition specs, "
    "GpuCustomShuffleReaderExec.scala:87): a reduce partition whose row "
    "count exceeds skewedPartitionFactor x the median non-empty "
    "partition (and the row threshold) is kept as contiguous chunks "
    "instead of one batch, so a downstream shuffled hash join probes it "
    "chunk-by-chunk against the full build partition with bounded "
    "memory — proactively, not via the OOM-retry path.", True)
SKEW_JOIN_FACTOR = register(
    "spark.sql.adaptive.skewJoin.skewedPartitionFactor",
    "A partition is skewed when its rows exceed this factor times the "
    "median non-empty partition's rows (Spark's default).", 5)
SKEW_JOIN_ROWS = register(
    "spark.sql.adaptive.skewJoin.skewedPartitionRowsThreshold",
    "...and also exceed this absolute row count (the rows analog of "
    "Spark's skewedPartitionThresholdInBytes).", 1 << 17)
OPTIMIZER_ENABLED = register(
    "spark.rapids.sql.optimizer.enabled",
    "Cost-based optimizer: flips subtrees back to the host engine when the "
    "estimated device benefit does not cover transition costs (reference "
    "CostBasedOptimizer.scala:54; off by default like the reference).",
    False)
OPTIMIZER_CPU_COST = register(
    "spark.rapids.sql.optimizer.cpu.exec.default",
    "Default CPU cost (seconds/row) per operator "
    "(RapidsConf.scala:1870).", 0.0002)
OPTIMIZER_GPU_COST = register(
    "spark.rapids.sql.optimizer.gpu.exec.default",
    "Default device cost (seconds/row) per operator "
    "(RapidsConf.scala:1882-1886).", 0.0001)
OPTIMIZER_TRANSITION_COST = register(
    "spark.rapids.sql.optimizer.transition.default",
    "Cost (seconds/row) of a host<->device transition boundary.", 0.0001)
OPTIMIZER_TRANSITION_FIXED = register(
    "spark.rapids.sql.optimizer.transition.fixedSeconds",
    "FIXED cost (seconds) of each host<->device transition boundary, "
    "independent of row count: every host pull is a sync round trip "
    "that dwarfs per-row costs for small batches.  -1 (default) = auto: "
    "measure the sync round trip once per process and use that.", -1.0)

RAGGED_STRING_SPLIT_BYTES = register(
    "spark.rapids.sql.strings.raggedSplitBytes",
    "Scans split a batch into string width classes when its padded "
    "[capacity x width] byte-matrix footprint would exceed this many "
    "bytes and the split saves >=4x — so one long string doesn't make "
    "every row pay its width.  0 disables.", 16 << 20)

APPROX_PERCENTILE_STRATEGY = register(
    "spark.rapids.sql.approxPercentile.strategy",
    "approx_percentile implementation: 'exact' = sorted ordinal selection "
    "(Spark's exact-percentile rule; tighter than Spark's own sketch but "
    "needs every group's rows co-resident), 'tdigest' = device t-digest "
    "sketch (bounded O(groups x delta/2) state, interpolated results — "
    "the reference's documented-incompat behavior, "
    "GpuApproximatePercentile.scala), 'auto' = exact below "
    "tdigestThresholdRows, t-digest above.", "auto")
APPROX_PERCENTILE_TDIGEST_ROWS = register(
    "spark.rapids.sql.approxPercentile.tdigestThresholdRows",
    "In 'auto' mode, batches at or above this capacity digest via "
    "t-digest instead of exact selection.", 1 << 18)

BLOOM_JOIN_ENABLED = register(
    "spark.rapids.sql.join.bloomFilter.enabled",
    "Bloom-filter join runtime filters: the build side of a shuffled hash "
    "join builds a bloom filter over its join keys and the probe side "
    "drops non-members BELOW its exchange, shrinking both the shuffle and "
    "the probe (reference GpuBloomFilterMightContain.scala, "
    "shims/BloomFilterShims.scala spark330+).  Inner/left-semi joins only.",
    True)
BLOOM_JOIN_MAX_BUILD_ROWS = register(
    "spark.rapids.sql.join.bloomFilter.maxBuildRows",
    "Skip bloom construction when the build side exceeds this many rows "
    "(the filter stores one device byte per bit position, i.e. "
    "~bitsPerRow BYTES per build row after power-of-two rounding).",
    4_000_000)
BLOOM_JOIN_BITS_PER_ROW = register(
    "spark.rapids.sql.join.bloomFilter.bitsPerRow",
    "Bloom filter density; 8 bits/row with the derived hash count gives "
    "a ~2% false-positive rate.", 8)

# --- shuffle ---------------------------------------------------------------
SHUFFLE_DEVICE_RESIDENT = register(
    "spark.rapids.shuffle.localDeviceResident.enabled",
    "Keep local SORT/MULTITHREADED shuffle blocks device-resident in the "
    "spill catalog instead of serializing to host, when the producer and "
    "consumer share one process and slice.  Skips a D2H+H2D round trip "
    "per block; the spill catalog still "
    "demotes blocks under memory pressure (reference device-direct "
    "shuffle: ShuffleBufferCatalog.scala + RapidsCachingWriter).", True)
SHUFFLE_MODE = register(
    "spark.rapids.shuffle.mode",
    "UCX|MULTITHREADED|SORT in the reference; here ICI|MULTITHREADED|SORT — "
    "ICI keeps partitions in device memory and exchanges over the "
    "interconnect with XLA collectives.", "MULTITHREADED")
SHUFFLE_TRANSPORT_CLASS = register(
    "spark.rapids.shuffle.transport.type",
    "LOCAL (in-process store) or TCP (cross-process block server + driver "
    "registry, the UCX-transport analog for cross-host fetches; "
    "RapidsShuffleTransport SPI).", "LOCAL")
SHUFFLE_TOPOLOGY_SLICES = register(
    "spark.rapids.shuffle.topology.numSlices",
    "Number of TPU slices the job spans.  1 (default) = single-slice: "
    "every exchange rides ICI (XLA collectives).  >1 enables the two-"
    "tier plane: a slice's own reduce partitions stay on ICI while "
    "blocks owned by peer slices cross DCN via the TCP transport "
    "(parallel/topology.py; reference UCX transport + peer registry).",
    1)
SHUFFLE_TOPOLOGY_SLICE_ID = register(
    "spark.rapids.shuffle.topology.sliceId",
    "This process's slice ordinal in [0, numSlices).", 0)
SHUFFLE_TCP_DRIVER_ENDPOINT = register(
    "spark.rapids.shuffle.tcp.driverEndpoint",
    "host:port of the driver heartbeat registry for the TCP transport "
    "(RapidsShuffleHeartbeatManager analog); empty = standalone.", "")
SHUFFLE_TCP_BIND_HOST = register(
    "spark.rapids.shuffle.tcp.bindHost",
    "Address the TCP shuffle block server binds and advertises; set to "
    "this host's reachable address for multi-host deployments.",
    "127.0.0.1")
SHUFFLE_TCP_NATIVE = register(
    "spark.rapids.shuffle.tcp.native.enabled",
    "Serve the TCP shuffle data plane from the native C++ transport "
    "(epoll block server + pooled client, native/srt_transport.cpp — the "
    "UCX-module analog); wire-compatible with the Python transport, "
    "which remains the fallback when the library can't build.", True)
SHUFFLE_EXECUTOR_ID = register(
    "spark.rapids.shuffle.executorId",
    "This process's executor id for shuffle peer discovery.", "exec-0")
SHUFFLE_WRITER_THREADS = register(
    "spark.rapids.shuffle.multiThreaded.writer.threads",
    "Threads for the multithreaded shuffle writer.", 8)
SHUFFLE_READER_THREADS = register(
    "spark.rapids.shuffle.multiThreaded.reader.threads",
    "Threads for the multithreaded shuffle reader.", 8)
SHUFFLE_COMPRESSION_CODEC = register(
    "spark.rapids.shuffle.compression.codec",
    "Shuffle batch compression codec: none|zstd|lz4hc.", "zstd")
SHUFFLE_CHECKSUM = register(
    "spark.rapids.shuffle.checksum",
    "Frame integrity checksum: auto (only when the native xxhash64 "
    "library is available — the pure-Python fallback is too slow for the "
    "hot path), true (always), false (never).", "auto")
SHUFFLE_TCP_CONNECT_TIMEOUT_MS = register(
    "spark.rapids.shuffle.tcp.connectTimeoutMs",
    "Connect timeout for TCP shuffle block fetches and the driver "
    "registry client (previously hardcoded at 10s).", 10_000)
SHUFFLE_TCP_READ_TIMEOUT_MS = register(
    "spark.rapids.shuffle.tcp.readTimeoutMs",
    "Socket read/write timeout for TCP shuffle block fetches; a peer "
    "that accepts the connection but stops responding mid-frame "
    "surfaces as ShuffleFetchFailed instead of hanging the reduce "
    "task forever.", 30_000)

# --- robustness: resilient shuffle fetch ------------------------------------
SHUFFLE_FETCH_MAX_RETRIES = register(
    "spark.rapids.tpu.shuffle.fetch.maxRetries",
    "Bounded retries per shuffle block fetch before the manager falls "
    "back to lost-block recompute (or fails the read).  Each retry "
    "backs off exponentially from fetch.backoffMs with jitter.", 4)
SHUFFLE_FETCH_BACKOFF_MS = register(
    "spark.rapids.tpu.shuffle.fetch.backoffMs",
    "Base backoff between shuffle fetch retries; attempt N sleeps "
    "backoffMs * 2^(N-1) (+ up to 25% jitter), capped by the remaining "
    "per-reduce deadline.", 10)
SHUFFLE_FETCH_DEADLINE_MS = register(
    "spark.rapids.tpu.shuffle.fetch.deadlineMs",
    "Wall-clock deadline for assembling one reduce partition; retries "
    "stop when it expires (the FetchFailed->stage-retry analog of "
    "spark.network.timeout).", 30_000)
SHUFFLE_FETCH_BLACKLIST_AFTER = register(
    "spark.rapids.tpu.shuffle.fetch.blacklistAfter",
    "Consecutive fetch failures from one peer before it is transiently "
    "blacklisted (moved to last-resort ordering, not dropped — "
    "correctness never depends on the blacklist).", 2)
SHUFFLE_FETCH_BLACKLIST_MS = register(
    "spark.rapids.tpu.shuffle.fetch.blacklistMs",
    "How long a blacklisted peer stays benched; the next heartbeat "
    "refresh after expiry reinstates it with a clean slate.", 5_000)
SHUFFLE_FETCH_SPECULATIVE_P99 = register(
    "spark.rapids.tpu.shuffle.fetch.speculativeP99Factor",
    "Straggler mitigation for remote shuffle fetches: when a fetch "
    "against one peer runs longer than this factor times the rolling "
    "p99 of recent remote-fetch latencies, a speculative duplicate "
    "fetch is issued against the next candidate peer and the first "
    "answer wins (the hung fetch is abandoned, its socket dropped).  "
    "0 (default) disables speculation.", 0.0)

# --- robustness: pod-scale peer failure domain ------------------------------
PEERS_HEARTBEAT_MS = register(
    "spark.rapids.tpu.peers.heartbeatMs",
    "Interval of the shuffle manager's background heartbeat loop "
    "against the driver peer registry, which also feeds the phi-accrual "
    "failure detector (robustness/failure_detector.py).  0 (default) "
    "disables the background loop: heartbeats then ride fetch-time "
    "refreshes only, as before the failure detector existed.", 0)
PEERS_SUSPECT_MS = register(
    "spark.rapids.tpu.peers.suspectMs",
    "A peer with no heartbeat for this long (scaled by the phi-accrual "
    "estimate of its normal arrival jitter) transitions alive -> "
    "suspect: it drops to last-resort fetch ordering but is still "
    "tried.  Hysteresis: returning to alive requires consecutive "
    "on-time heartbeats, so a flapping peer doesn't thrash the "
    "ordering.", 3_000)
PEERS_DEAD_MS = register(
    "spark.rapids.tpu.peers.deadMs",
    "A peer with no heartbeat for this long is declared dead: in-flight "
    "fetches against it fail over immediately (no retry/backoff "
    "budget), its blocks recompute proactively via registered lineage "
    "callbacks, and its registry entry is fenced — re-registration "
    "bumps the peer's epoch so a zombie returning later cannot serve "
    "stale blocks.", 10_000)

# --- mesh data plane robustness ---------------------------------------------
MESH_COLLECTIVE_DEADLINE_MS = register(
    "spark.rapids.tpu.mesh.collectiveDeadlineMs",
    "Wall-clock deadline for one compiled mesh all_to_all exchange "
    "(parallel/mesh.py).  On expiry the exchange raises a typed "
    "timeout and the stage degrades to the local/TCP shuffle plane "
    "with a loud metric (mesh_collective_timeouts_total) instead of "
    "hanging; the launched program itself cannot be recalled (the "
    "watchdog is cooperative, like query deadlines).  0 (default) "
    "disables the watchdog and runs the collective inline.", 0)

# --- robustness: seeded chaos / fault injection -----------------------------
CHAOS_ENABLED = register(
    "spark.rapids.tpu.chaos.enabled",
    "Master switch for the seeded fault-injection registry "
    "(robustness/faults.py).  Off (default) costs one dict lookup per "
    "instrumented chokepoint; on, each armed site draws a deterministic "
    "seeded decision per traversal and raises a site-appropriate "
    "injected fault.  The unified surface also drives the synthetic-OOM "
    "sites the retry framework previously armed separately.", False)
CHAOS_SEED = register(
    "spark.rapids.tpu.chaos.seed",
    "Seed for the deterministic fault schedule: site X's Nth traversal "
    "makes the same inject/pass decision on every run with the same "
    "seed, independent of thread interleaving across sites.", 0)
CHAOS_SITES = register(
    "spark.rapids.tpu.chaos.sites",
    "Comma list of armed injection sites, each optionally 'site:prob' "
    "to override the global probability (e.g. "
    "'shuffle.fetch:0.3,spill.disk_read').  Empty arms EVERY site — "
    "note sites without a built-in recovery protocol (transfer.h2d, "
    "transfer.d2h, kernel.compile, device.fatal, query.cancel.race) "
    "then fail queries by design — with a TYPED error, never a wedged "
    "thread.  See docs/robustness.md for the site catalog.", "",
    type_=str)
CHAOS_PROBABILITY = register(
    "spark.rapids.tpu.chaos.probability",
    "Default injection probability per armed-site traversal.", 0.05)

# --- I/O -------------------------------------------------------------------
PARQUET_READER_TYPE = register(
    "spark.rapids.sql.format.parquet.reader.type",
    "AUTO|PERFILE|MULTITHREADED|COALESCING multi-file reader strategy "
    "(reference GpuMultiFileReader.scala:176-373).", "AUTO")
MULTITHREAD_READ_NUM_THREADS = register(
    "spark.rapids.sql.multiThreadedRead.numThreads",
    "Thread pool size for multithreaded file reads.", 20)
CSV_DEVICE_DECODE = register(
    "spark.rapids.sql.format.csv.deviceDecode.enabled",
    "Parse CSV on the device: the host scans only newline/delimiter "
    "structure (vectorized); field bytes gather into matrices and parse "
    "through the same Spark-exact cast_strings kernels the CAST matrix "
    "uses.  Quoted fields, custom null markers, CRLF, ragged rows and "
    "parse failures against the plan schema decline to the host pyarrow "
    "reader (reference device parse: GpuCSVScan.scala:355 "
    "Table.readCSV).", True)
JSON_DEVICE_DECODE = register(
    "spark.rapids.sql.format.json.deviceDecode.enabled",
    "Parse JSON-lines on the device: the host scans only structure "
    "(quote spans by parity, structural colons/commas/braces outside "
    "strings, key and value byte spans — all vectorized), and value "
    "bytes gather into matrices and parse through the same Spark-exact "
    "cast_strings kernels the CAST matrix uses.  Escapes, nested "
    "objects/arrays, multiLine mode, single-quote syntax, CRLF and any "
    "value failing to parse as the plan schema's type decline to the "
    "host pyarrow reader (reference device parse: GpuJsonScan via "
    "GpuTextBasedPartitionReader, Table.readJSON).", True)
ORC_DEVICE_DECODE = register(
    "spark.rapids.sql.format.orc.deviceDecode.enabled",
    "Decode ORC stripes on the device: the host parses only structure "
    "(protobuf footers, compression block framing, RLEv2/byte-RLE run "
    "headers) and XLA programs do the per-value work — MSB bit-unpack, "
    "zigzag, DELTA prefix sums, PRESENT bit expansion, null scatter, "
    "dictionary remap, string-matrix gather.  Columns outside the "
    "envelope (timestamps, decimals, nested, RLEv1, PATCHED_BASE) fall "
    "back to host decode individually (reference device decode: "
    "GpuOrcScan.scala:893 Table.readORC).", True)
PARQUET_DEVICE_DECODE = register(
    "spark.rapids.sql.format.parquet.deviceDecode.enabled",
    "Decode parquet pages on the device: the host parses only structure "
    "(footer, page headers, RLE/bit-packed run boundaries) and XLA "
    "programs do all per-value work — bit-unpacking, dictionary gather, "
    "def-level null scatter, physical->logical finishing.  Columns "
    "outside the envelope (nested, mixed-encoding, exotic codecs) fall "
    "back to host decode individually.  Applies to PERFILE and "
    "MULTITHREADED parquet scans; COALESCING reads stay on the host "
    "decode (reference device decode: GpuParquetScan.scala:2649 "
    "Table.readParquet).", True)
PARQUET_PUSHDOWN_ENABLED = register(
    "spark.rapids.sql.format.parquet.filterPushdown.enabled",
    "Prune parquet row groups with footer column statistics against "
    "scan-adjacent filter conjuncts before decode (reference "
    "GpuParquetScan footer parse + block filtering, "
    "GpuParquetScan.scala:2765).", True)
READER_CHUNKED = register(
    "spark.rapids.sql.reader.chunked",
    "Read input files in multiple output batches (one per row-group run) "
    "instead of one batch per file, bounding peak memory (reference "
    "chunked readers, RapidsConf.scala:568).", True)
READER_CHUNKED_TARGET_ROWS = register(
    "spark.rapids.sql.reader.chunked.targetRows",
    "Row threshold that closes a chunk when chunked reading is on.",
    1 << 21)
FILECACHE_ENABLED = register(
    "spark.rapids.filecache.enabled",
    "Cache input data files on local disk keyed by (path, size, mtime) — "
    "the reference's file-cache feature (hook points "
    "GpuParquetScan/GpuOrcDataReader; impl shipped in the private jar).",
    False)
FILECACHE_PATH = register(
    "spark.rapids.filecache.path",
    "Directory for the local file cache (empty = system temp).", "")
FILECACHE_MAX_BYTES = register(
    "spark.rapids.filecache.maxBytes",
    "Evict least-recently-used cached files past this total size.",
    16 << 30)
FATAL_DUMP_PATH = register(
    "spark.rapids.tpu.fatalDump.path",
    "Directory for fatal-device-error diagnostics bundles (exception, "
    "backend/device state, spill catalog) — the GpuCoreDumpHandler "
    "analog; empty disables capture.", "")
FATAL_ERROR_EXIT = register(
    "spark.rapids.tpu.fatalErrorExit",
    "Self-terminate the process with exit code 20 on a fatal device "
    "error so an external scheduler replaces it (the reference "
    "executor's behavior, Plugin.scala:515-539). Off by default: this "
    "engine usually runs inside the user's process.", False)
PYTHON_WORKER_ISOLATED = register(
    "spark.rapids.python.worker.isolated",
    "Run pandas UDFs in separate worker PROCESSES with Arrow IPC "
    "exchange (reference python/rapids/daemon.py): a user function that "
    "kills its interpreter fails the task, not the session, and the "
    "concurrentPythonWorkers cap gates real processes.  false = "
    "in-process fast path (no crash containment).", True)
CONCURRENT_PYTHON_WORKERS = register(
    "spark.rapids.python.concurrentPythonWorkers",
    "Max concurrently-running user-Python sections (pandas UDFs, "
    "applyInPandas, mapInPandas) — bounds host memory held by parallel "
    "Arrow/pandas materializations (reference PythonWorkerSemaphore).", 4)
IO_REPLACE_PATHS = register(
    "spark.rapids.tpu.io.replacePaths",
    "Comma-separated 'scheme://old->new' prefix rewrites applied to scan "
    "paths before reading — the Alluxio path-replacement analog "
    "(reference AlluxioUtils.scala:671 spark.rapids.alluxio.pathsToReplace).",
    "")

# --- pipelined async execution ----------------------------------------------
TASK_PARALLELISM = register(
    "spark.rapids.tpu.task.parallelism",
    "Number of partitions execute_all runs concurrently on a bounded "
    "thread pool (the local-mode analog of Spark running N tasks per "
    "executor; reference SURVEY §2.7 per-task concurrency under the GPU "
    "semaphore).  1 (default) is the serial driver loop — bit-identical "
    "results either way: per-partition batch order and cross-partition "
    "result order are both preserved.  Device admission is still gated "
    "by spark.rapids.sql.concurrentGpuTasks; set it >= this value to "
    "actually overlap host and device work.  Nested plans (exchange map "
    "sides, broadcast builds, subqueries) always run serially inside "
    "their owning task.", 1, commonly_used=True)
PREFETCH_ENABLED = register(
    "spark.rapids.tpu.prefetch.enabled",
    "Insert AsyncPrefetchExec boundaries after planning: a bounded "
    "background queue decouples the expensive seams (file scans, "
    "host->device uploads, exchange reduce sides) from their consumer, "
    "so host decode/upload overlaps downstream compute (the reference's "
    "multithreaded reader prefetch, GpuMultiFileReader.scala:176).  "
    "Exceptions (including injected chaos faults) propagate through the "
    "queue to the consumer with their original type.  Off (default) "
    "keeps the fully synchronous pipeline.", False, commonly_used=True)
PREFETCH_DEPTH = register(
    "spark.rapids.tpu.prefetch.depth",
    "Bound on batches buffered per AsyncPrefetchExec queue; the producer "
    "blocks when the consumer falls this many batches behind (memory "
    "backpressure, the maxBytesInFlight analog at pipeline seams).", 2)
TRANSFER_DOUBLE_BUFFER = register(
    "spark.rapids.tpu.transfer.doubleBuffer.enabled",
    "Double-buffer backend transitions: HostToDeviceExec dispatches "
    "batch N+1's upload while batch N is consumed downstream, and "
    "DeviceToHostExec issues the prepacked fetch for batch N+1 before "
    "yielding batch N's result — at most ONE transfer in flight ahead "
    "of the consumer, still under the OOM-guard/spill protocol "
    "(reference stream-overlapped transfers, SURVEY §2.2).  Off "
    "(default) keeps transfers serialized with compute.", False)

# --- metrics / debug -------------------------------------------------------
METRICS_LEVEL = register(
    "spark.rapids.sql.metrics.level",
    "ESSENTIAL|MODERATE|DEBUG operator metric verbosity.", "MODERATE")
TRACE_ENABLED = register(
    "spark.rapids.tpu.trace.enabled",
    "Emit jax.profiler TraceMe ranges around operator execution "
    "(NVTX-range equivalent).", False)
TRACE_SINK = register(
    "spark.rapids.tpu.trace.sink",
    "Query-timeline tracer sink: '' (off), 'memory' (keep the ring "
    "buffer in process for profile_last_query() / "
    "session.export_chrome_trace(path)), or a directory path — each "
    "query additionally appends its timeline as a JSONL event log "
    "(query-<pid>-<n>.jsonl, the Spark eventLog/history analog).  The "
    "tracer attributes blocked readbacks, kernel trace+compile and "
    "H2D/D2H bytes to exec nodes; spark.rapids.tpu.profile.enabled "
    "implies sink=memory.", "")
TRACE_BUFFER_EVENTS = register(
    "spark.rapids.tpu.trace.bufferEvents",
    "Capacity of the tracer's bounded event ring buffer.  On overflow "
    "the OLDEST events are dropped (newest kept) and the trace summary "
    "reports dropped_events.", 65536)
PROFILE_ENABLED = register(
    "spark.rapids.tpu.profile.enabled",
    "Record per-exec wall time + batch counts during execution; read the "
    "report with session.profile_last_query() (the SQL-UI per-op "
    "GpuMetric view).", False)
METRICS_ENABLED = register(
    "spark.rapids.tpu.metrics.enabled",
    "Feed the process-wide metrics registry (observability/metrics.py): "
    "counters, gauges and log-bucketed latency histograms (p50/p95/p99) "
    "from the tracer, shuffle, spill/retention and kernel-cache "
    "chokepoints, labeled by query id and session id.  Export with "
    "session.metrics_prometheus() / metrics_snapshot().  Off (default) "
    "costs one dict lookup per chokepoint.", False, commonly_used=True)
METRICS_MAX_SERIES = register(
    "spark.rapids.tpu.metrics.maxSeries",
    "Cardinality bound on the metrics registry: past this many distinct "
    "(name, labels) series, NEW series are dropped and counted in "
    "metrics_dropped_series — an exec-name or label explosion can never "
    "OOM the driver.", 4096)
HISTORY_ENABLED = register(
    "spark.rapids.tpu.history.enabled",
    "Query flight recorder (observability/history.py): every query "
    "leaves one record (plan fingerprint, duration, last_query_metrics, "
    "trace_summary, decode engagement, wire bytes) in a bounded "
    "in-memory ring read back via session.query_history().  One dict "
    "build + list append per query.", True)
HISTORY_MAX_QUERIES = register(
    "spark.rapids.tpu.history.maxQueries",
    "Flight-recorder ring bound, in memory and on disk (the JSONL file "
    "compacts to the newest maxQueries records when it outgrows twice "
    "this).", 128)
HISTORY_PATH = register(
    "spark.rapids.tpu.history.path",
    "On-disk JSONL ring for the query flight recorder (the Spark "
    "history-server analog at flight-recorder weight); empty (default) "
    "keeps history in memory only.  Read back with "
    "observability.history.read_history_file().", "")
DUMP_ON_ERROR_PATH = register(
    "spark.rapids.sql.debug.dumpPath",
    "If set, dump failing batches to parquet here (DumpUtils equivalent).",
    "")

# --- multi-tenant serving (serving/, docs/serving.md) -----------------------
SERVING_TENANT = register(
    "spark.rapids.tpu.serving.tenant",
    "Tenant identity of this session.  Stamped on metric series (the "
    "registry's `tenant` label), trace spans, and flight-recorder "
    "records; the serving tier's admission queue schedules and budgets "
    "by it.  Empty (default) means the anonymous single-tenant mode.",
    "")
SERVING_MAX_CONCURRENT = register(
    "spark.rapids.tpu.serving.maxConcurrentQueries",
    "How many admitted queries a ServingEngine lets execute at once "
    "across ALL tenants.  This caps driver-side concurrency; device "
    "admission below it is still arbitrated per task by "
    "spark.rapids.sql.concurrentGpuTasks and the device semaphore.",
    8, commonly_used=True)
SERVING_ADMISSION_TIMEOUT_MS = register(
    "spark.rapids.tpu.serving.admission.timeoutMs",
    "Upper bound on how long a query may wait in the admission queue "
    "before AdmissionTimeout is raised; 0 (default) waits forever.", 0)
SERVING_TENANT_WEIGHTS = register(
    "spark.rapids.tpu.serving.tenant.weights",
    "Comma list of tenant:weight pairs (e.g. 'etl:4,adhoc:1') for the "
    "weighted-fair admission queue: a tenant's share of admission slots "
    "is proportional to its weight.  Tenants not listed get "
    "spark.rapids.tpu.serving.tenant.defaultWeight.", "")
SERVING_TENANT_DEFAULT_WEIGHT = register(
    "spark.rapids.tpu.serving.tenant.defaultWeight",
    "Admission weight for tenants absent from "
    "spark.rapids.tpu.serving.tenant.weights.", 1.0)
SERVING_TENANT_BUDGETS = register(
    "spark.rapids.tpu.serving.tenant.memoryBudgets",
    "Comma list of tenant:bytes pairs capping the estimated input bytes "
    "a tenant may have ADMITTED at once.  The budget gates admission "
    "only — actual device memory stays arbitrated by the semaphore, "
    "OOM-guard and spill machinery.  A query whose lone estimate "
    "exceeds the budget still admits when the tenant has nothing else "
    "in flight (a budget must throttle, never wedge).", "")
SERVING_TENANT_DEFAULT_BUDGET = register(
    "spark.rapids.tpu.serving.tenant.defaultMemoryBudgetBytes",
    "Admission memory budget for tenants absent from "
    "spark.rapids.tpu.serving.tenant.memoryBudgets; 0 (default) means "
    "unbudgeted.", 0)
SERVING_RESULT_CACHE_ENABLED = register(
    "spark.rapids.tpu.serving.resultCache.enabled",
    "Cross-query result cache: a collect whose plan content fingerprint "
    "(operators + literals + input identity) matches a cached entry "
    "returns the cached Arrow table without executing.  Entries are "
    "invalidated when any input file's mtime/size changes and on every "
    "write through io_/writers.py; plans containing non-deterministic "
    "expressions or opaque UDFs are never cached.  Off (default) "
    "outside serving engines.", False, commonly_used=True)
SERVING_RESULT_CACHE_MAX_BYTES = register(
    "spark.rapids.tpu.serving.resultCache.maxBytes",
    "Byte bound on the result cache (Arrow table nbytes); least-"
    "recently-used entries evict past it.", 256 << 20)
SERVING_BROADCAST_SHARE = register(
    "spark.rapids.tpu.serving.broadcastShare.enabled",
    "Share materialized broadcast batches ACROSS queries and sessions "
    "by plan-content key (child subtree + literals + input identity + "
    "encode params).  Shared batches are pinned in the retention "
    "registry so whole-stage donation stays safe; entries follow the "
    "same file-mtime/write invalidation contract as the result cache.  "
    "Off (default) keeps broadcasts per-plan.", False)
SERVING_BROADCAST_SHARE_MAX_BYTES = register(
    "spark.rapids.tpu.serving.broadcastShare.maxBytes",
    "Byte bound on the shared broadcast cache; LRU entries evict (and "
    "unpin) past it.", 256 << 20)

# --- query lifecycle: cancellation, deadlines, degradation, quarantine ------
QUERY_DEADLINE_MS = register(
    "spark.rapids.tpu.query.deadlineMs",
    "Per-query wall-clock deadline: a collect running past it raises "
    "QueryDeadlineExceeded at the next lifecycle poll site (partition "
    "scheduler, prefetch queues, transfer stager, shuffle fetch, "
    "semaphore wait, spill I/O), releasing the semaphore, unpinning "
    "retention and draining prefetch queues on the way out.  0 "
    "(default) means no deadline.  Enforcement latency is bounded by "
    "the 50ms poll interval plus the longest uninterruptible device "
    "dispatch (serving/lifecycle.py).", 0, commonly_used=True)
QUERY_CANCEL_POLL_SITES = register(
    "spark.rapids.tpu.query.cancel.pollSites",
    "Comma list restricting which chokepoints poll the query's "
    "cancellation token (site catalog: admission, partition, sem_wait, "
    "prefetch, stager, shuffle, exchange, spill — docs/robustness.md). "
    "Empty (default) polls every site; a restricted list trades drain "
    "latency for even less poll overhead.", "", type_=str)
PRESSURE_ENABLED = register(
    "spark.rapids.tpu.serving.pressure.enabled",
    "Admission-aware graceful degradation (kill switch): when the "
    "serving admission queue is under pressure (depth or recent-wait "
    "thresholds below), newly-admitted queries plan with a shrunken "
    "resource profile — reduced concurrentGpuTasks share, smaller "
    "batch-rows target, speculative join sizing off — so a saturated "
    "engine degrades throughput-per-query gracefully instead of piling "
    "device working sets.  Off (default) plans every query identically "
    "regardless of queue state.", False, commonly_used=True)
PRESSURE_QUEUE_DEPTH = register(
    "spark.rapids.tpu.serving.pressure.queueDepth",
    "Admission queue depth at or above which the PressureSignal reports "
    "pressure (serving/lifecycle.py).", 4)
PRESSURE_WAIT_MS = register(
    "spark.rapids.tpu.serving.pressure.waitMs",
    "Recent admission-wait (rolling median across tenants) at or above "
    "which the PressureSignal reports pressure; 0 disables the wait "
    "signal (depth still applies).", 250.0)
PRESSURE_SHARE = register(
    "spark.rapids.tpu.serving.pressure.concurrentShare",
    "Fraction of spark.rapids.sql.concurrentGpuTasks a degraded plan "
    "keeps (floored at 1 task).", 0.5)
PRESSURE_BATCH_ROWS = register(
    "spark.rapids.tpu.serving.pressure.batchTargetRows",
    "Batch-rows target cap applied to degraded plans (only ever "
    "lowers spark.rapids.sql.batchSizeRows).", 1 << 18)
QUARANTINE_TTL_MS = register(
    "spark.rapids.tpu.serving.quarantine.ttlMs",
    "How long a plan fingerprint whose execution produced a "
    "FatalDeviceError stays quarantined (immediate retries raise "
    "QueryQuarantined instead of re-killing the device); 0 disables "
    "quarantine.", 60_000)
QUARANTINE_MAX_ENTRIES = register(
    "spark.rapids.tpu.serving.quarantine.maxEntries",
    "Size bound on the quarantine registry; oldest entries evict past "
    "it.", 128)
DEGRADED_PROBE_INTERVAL_MS = register(
    "spark.rapids.tpu.serving.degraded.probeIntervalMs",
    "Minimum spacing between device probe attempts while the engine is "
    "degraded after a fatal device error; admissions arriving between "
    "probes are refused with EngineDegraded.", 1_000)

# --- telemetry plane: scrape/health endpoint + SLO objectives ---------------
TELEMETRY_ENABLED = register(
    "spark.rapids.tpu.telemetry.enabled",
    "Kill switch for the embedded telemetry HTTP server "
    "(observability/server.py): a daemon-thread ThreadingHTTPServer "
    "bound to 127.0.0.1 serving /metrics (Prometheus exposition), "
    "/healthz (degraded/quarantine/admission/semaphore state, non-200 "
    "when the engine is degraded), /queries (flight-recorder ring), "
    "/doctor (last ranked verdicts) and /slo (per-tenant burn rates). "
    "Owned by the ServingEngine when serving, else by the TpuSession; "
    "shutdown is leak-free (no lingering thread or bound port).  Off "
    "(default) starts nothing and changes no behavior.",
    False, commonly_used=True)
TELEMETRY_PORT = register(
    "spark.rapids.tpu.telemetry.port",
    "TCP port for the telemetry server; 0 (default) binds an ephemeral "
    "port (read it back from engine.telemetry.port / "
    "session.telemetry.port).", 0, commonly_used=True)
SLO_LATENCY_MS = register(
    "spark.rapids.tpu.slo.latencyObjectiveMs",
    "Per-tenant latency objective: a query slower than this is a "
    "'slow' event against the latency error budget (observability/"
    "slo.py reads the per-tenant query_ms histograms).  0 (default) "
    "disables the latency SLO leg.", 0.0, commonly_used=True)
SLO_LATENCY_TARGET = register(
    "spark.rapids.tpu.slo.latencyTarget",
    "Fraction of queries that must meet the latency objective (the "
    "latency error budget is 1 - target).", 0.99)
SLO_ERROR_TARGET = register(
    "spark.rapids.tpu.slo.availabilityTarget",
    "Fraction of queries that must succeed (status=ok in "
    "queries_total); the availability error budget is 1 - target.",
    0.999)
SLO_WINDOWS_S = register(
    "spark.rapids.tpu.slo.burnWindowsS",
    "Comma list of burn-rate window lengths in seconds, shortest "
    "first; a tenant burning its error budget at rate >= 1 in the "
    "shortest window is 'burning' (slo-burn doctor verdict).",
    "300,3600", type_=str)


class RapidsConf:
    """Immutable-ish snapshot of config values, resolved from defaults +
    overrides + ``SPARK_RAPIDS_*`` style environment variables."""

    _global_lock = threading.Lock()
    _global: Optional["RapidsConf"] = None

    def __init__(self, overrides: Optional[Dict[str, Any]] = None):
        self._values: Dict[str, Any] = {}
        overrides = dict(overrides or {})
        for key, entry in ENTRIES.items():
            env_key = key.upper().replace(".", "_")
            raw = overrides.pop(key, os.environ.get(env_key))
            self._values[key] = entry.convert(raw)
        # unknown keys are kept verbatim (forward compat, like SQLConf)
        self._extra = overrides

    def get(self, key_or_entry, default: Any = None) -> Any:
        key = key_or_entry.key if isinstance(key_or_entry, ConfEntry) else key_or_entry
        if key in self._values:
            return self._values[key]
        return self._extra.get(key, default)

    def get_bool(self, key: str, default: bool = True) -> bool:
        """Boolean read of a possibly-unregistered key (per-expression /
        per-exec enable flags are dynamic: one per registered rule, like
        the reference's auto-generated conf-per-rule entries)."""
        raw = self.get(key, default)
        if isinstance(raw, bool):
            return raw
        return str(raw).strip().lower() in ("true", "1", "yes")

    def set(self, key: str, value: Any) -> "RapidsConf":
        if key in ENTRIES:
            self._values[key] = ENTRIES[key].convert(value)
        else:
            self._extra[key] = value
        return self

    def copy(self, overrides: Optional[Dict[str, Any]] = None) -> "RapidsConf":
        c = RapidsConf()
        c._values = dict(self._values)
        c._extra = dict(self._extra)
        for k, v in (overrides or {}).items():
            c.set(k, v)
        return c

    # Convenience typed accessors used across the engine -------------------
    @property
    def is_sql_enabled(self) -> bool:
        return bool(self.get(SQL_ENABLED))

    @property
    def is_explain_only(self) -> bool:
        return str(self.get(SQL_MODE)).lower() == "explainonly"

    @property
    def explain(self) -> str:
        return str(self.get(EXPLAIN)).upper()

    @property
    def shuffle_partitions(self) -> int:
        return int(self.get(SHUFFLE_PARTITIONS))

    @classmethod
    def get_global(cls) -> "RapidsConf":
        with cls._global_lock:
            if cls._global is None:
                cls._global = RapidsConf()
            return cls._global

    @classmethod
    def set_global(cls, conf: "RapidsConf") -> None:
        with cls._global_lock:
            cls._global = conf


def help_text(include_internal: bool = False) -> str:
    """Markdown config documentation, mirroring RapidsConf.help() docgen
    (reference RapidsConf.scala:2057-2103 emits docs/configs.md)."""
    lines = ["# Configuration", "",
             "Name | Description | Default Value", "-----|-------------|--------------"]
    for key in sorted(ENTRIES):
        e = ENTRIES[key]
        if e.internal and not include_internal:
            continue
        doc = e.doc.replace("|", "\\|")
        lines.append(f"{e.key} | {doc} | {e.default}")
    return "\n".join(lines) + "\n"
