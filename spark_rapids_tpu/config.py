"""Typed configuration registry.

TPU-native analogue of the reference's RapidsConf (RapidsConf.scala:116-256):
a registry of typed ConfEntry objects with defaults and doc strings, plus
markdown doc generation (RapidsConf.scala:717,814 generates docs/configs.md).
Per-operator enable keys (``spark.rapids.sql.exec.<Name>`` etc.,
GpuOverrides.scala:129-137) are registered dynamically by the planner rules.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, Generic, List, Optional, TypeVar

T = TypeVar("T")

_REGISTRY: "Dict[str, ConfEntry]" = {}
_REGISTRY_LOCK = threading.Lock()


class ConfEntry(Generic[T]):
    def __init__(self, key: str, default: T, doc: str, converter: Callable[[str], T],
                 internal: bool = False):
        self.key = key
        self.default = default
        self.doc = doc
        self.converter = converter
        self.internal = internal

    def get(self, conf: "RapidsConf") -> T:
        return conf.get(self.key)

    def __repr__(self):
        return f"ConfEntry({self.key}={self.default!r})"


def _to_bool(s: str) -> bool:
    return str(s).strip().lower() in ("true", "1", "yes", "on")


def _register(entry: ConfEntry) -> ConfEntry:
    with _REGISTRY_LOCK:
        if entry.key in _REGISTRY:
            return _REGISTRY[entry.key]
        _REGISTRY[entry.key] = entry
    return entry


def conf_bool(key: str, default: bool, doc: str, internal: bool = False) -> ConfEntry:
    return _register(ConfEntry(key, default, doc, _to_bool, internal))


def conf_int(key: str, default: int, doc: str, internal: bool = False) -> ConfEntry:
    return _register(ConfEntry(key, default, doc, int, internal))


def conf_float(key: str, default: float, doc: str, internal: bool = False) -> ConfEntry:
    return _register(ConfEntry(key, default, doc, float, internal))


def conf_str(key: str, default: str, doc: str, internal: bool = False) -> ConfEntry:
    return _register(ConfEntry(key, default, doc, str, internal))


def conf_bytes(key: str, default: int, doc: str, internal: bool = False) -> ConfEntry:
    def parse(s: str) -> int:
        s = str(s).strip().lower()
        mult = 1
        for suffix, m in (("k", 1 << 10), ("m", 1 << 20), ("g", 1 << 30), ("t", 1 << 40)):
            if s.endswith(suffix + "b"):
                s, mult = s[:-2], m
                break
            if s.endswith(suffix):
                s, mult = s[:-1], m
                break
        return int(float(s) * mult)
    return _register(ConfEntry(key, default, doc, parse, internal))


class RapidsConf:
    """An immutable-ish snapshot of configuration values.

    Values resolve in order: explicit settings > environment variables
    (``SPARK_RAPIDS_TPU_<KEY_WITH_UNDERSCORES>``) > registered default.
    """

    def __init__(self, settings: Optional[Dict[str, Any]] = None):
        self._settings: Dict[str, Any] = dict(settings or {})

    def set(self, key: str, value: Any) -> "RapidsConf":
        self._settings[key] = value
        return self

    def get(self, key: str, default: Any = None) -> Any:
        entry = _REGISTRY.get(key)
        if key in self._settings:
            raw = self._settings[key]
            if entry is not None and isinstance(raw, str):
                return entry.converter(raw)
            return raw
        env_key = "SPARK_RAPIDS_TPU_" + key.replace(".", "_").upper()
        if env_key in os.environ:
            raw = os.environ[env_key]
            return entry.converter(raw) if entry is not None else raw
        if entry is not None:
            return entry.default
        return default

    def copy(self, **overrides: Any) -> "RapidsConf":
        c = RapidsConf(dict(self._settings))
        for k, v in overrides.items():
            c.set(k, v)
        return c

    def explicitly_set(self, key: str) -> bool:
        """True when the user pinned ``key`` — an explicit session
        setting or an environment override.  Adaptive controllers use
        this to honor pinned values instead of tuning over them (e.g.
        scan.readAhead.depth set explicitly disables the adaptive
        read-ahead controller)."""
        if key in self._settings:
            return True
        env_key = "SPARK_RAPIDS_TPU_" + key.replace(".", "_").upper()
        return env_key in os.environ

    def is_operator_enabled(self, key: str, default: bool = True) -> bool:
        v = self.get(key)
        if v is None:
            return default
        return v if isinstance(v, bool) else _to_bool(v)

    # ---- core entries (mirroring RapidsConf.scala:271-700) ----

    @property
    def sql_enabled(self) -> bool:
        return SQL_ENABLED.get(self)

    @property
    def explain(self) -> str:
        return EXPLAIN.get(self)

    @property
    def batch_size_bytes(self) -> int:
        return BATCH_SIZE_BYTES.get(self)

    @property
    def max_readers_batch_size_rows(self) -> int:
        return READER_BATCH_SIZE_ROWS.get(self)

    @property
    def concurrent_tpu_tasks(self) -> int:
        return CONCURRENT_TPU_TASKS.get(self)

    @property
    def test_enforce_tpu(self) -> bool:
        return TEST_ENFORCE_TPU.get(self)

    @property
    def allow_incompat(self) -> bool:
        return INCOMPATIBLE_OPS.get(self)

    @property
    def has_nans(self) -> bool:
        return HAS_NANS.get(self)

    @property
    def variable_float_agg(self) -> bool:
        return VARIABLE_FLOAT_AGG.get(self)

    @property
    def host_spill_storage_size(self) -> int:
        return HOST_SPILL_STORAGE_SIZE.get(self)

    @property
    def replace_sort_merge_join(self) -> bool:
        return REPLACE_SORT_MERGE_JOIN.get(self)

    @property
    def explain_enabled(self) -> bool:
        return str(self.explain).upper() not in ("NONE", "FALSE", "")

    @property
    def shuffle_partitions(self) -> int:
        return SHUFFLE_PARTITIONS.get(self)

    @property
    def coalesce_target_rows(self) -> int:
        return COALESCE_TARGET_ROWS.get(self)


SQL_ENABLED = conf_bool(
    "spark.rapids.sql.enabled", True,
    "Enable (true) or disable (false) TPU acceleration of SQL operators.")
EXPLAIN = conf_str(
    "spark.rapids.sql.explain", "NONE",
    "Explain why parts of a query were or were not placed on the TPU. "
    "Options: NONE, ALL, NOT_ON_TPU.")
BATCH_SIZE_BYTES = conf_bytes(
    "spark.rapids.sql.batchSizeBytes", 512 * 1024 * 1024,
    "The target size in bytes of columnar batches processed on the TPU. "
    "The coalesce layer concatenates smaller batches up to this goal.")
READER_BATCH_SIZE_ROWS = conf_int(
    "spark.rapids.sql.reader.batchSizeRows", 1 << 20,
    "Soft cap on the number of rows the file readers put in one batch.")
CONCURRENT_TPU_TASKS = conf_int(
    "spark.rapids.sql.concurrentTpuTasks", 1,
    "Number of tasks that can execute concurrently on a single TPU chip. "
    "Tasks above the limit block in the TpuSemaphore.")
TEST_ENFORCE_TPU = conf_bool(
    "spark.rapids.sql.test.enabled", False,
    "Testing only: fail query planning if any supported operator would "
    "fall back to the CPU.", internal=True)
INCOMPATIBLE_OPS = conf_bool(
    "spark.rapids.sql.incompatibleOps.enabled", False,
    "Enable operators that produce results that differ in corner cases "
    "from Spark CPU semantics.")
HAS_NANS = conf_bool(
    "spark.rapids.sql.hasNans", True,
    "Whether float/double data is assumed to possibly contain NaNs; when "
    "true some float aggregations and joins stay on CPU for exactness.")
VARIABLE_FLOAT_AGG = conf_bool(
    "spark.rapids.sql.variableFloatAgg.enabled", False,
    "Allow float/double aggregations whose result can vary run-to-run "
    "because of non-deterministic reduction order.")
HOST_SPILL_STORAGE_SIZE = conf_bytes(
    "spark.rapids.memory.host.spillStorageSize", 1 << 30,
    "Bytes of host memory used to cache spilled device data before "
    "overflowing to disk.")
DEVICE_POOL_FRACTION = conf_float(
    "spark.rapids.memory.tpu.allocFraction", 0.9,
    "Fraction of usable HBM to reserve for the device buffer pool at startup.")
REPLACE_SORT_MERGE_JOIN = conf_bool(
    "spark.rapids.sql.replaceSortMergeJoin.enabled", True,
    "Replace sort-merge joins with TPU hash joins and drop the now "
    "unneeded sorts (reference: RapidsConf.scala:423).")
AUTO_BROADCAST_THRESHOLD = conf_bytes(
    "spark.sql.autoBroadcastJoinThreshold", 10 << 20,
    "Max estimated build-side bytes for choosing a broadcast hash join "
    "over a shuffled hash join; -1 disables broadcast.")
SHUFFLE_PARTITIONS = conf_int(
    "spark.sql.shuffle.partitions", 8,
    "Number of partitions used for shuffle exchanges.")
SHUFFLE_COMPRESSION_CODEC = conf_str(
    "spark.rapids.shuffle.compression.codec", "copy",
    "Codec for compressing shuffled table buffers (copy = passthrough). "
    "`nativelz` is the project-specific C++ LZ-family block codec — its "
    "wire format is NOT standard LZ4; there is deliberately no `lz4` "
    "alias.")
STRING_HASH_JOIN = conf_bool(
    "spark.rapids.sql.stringHashGroupJoin.enabled", True,
    "Group by / join on string keys via 64-bit hashes computed on device; "
    "collisions are astronomically unlikely but theoretically possible.")
ENABLE_ICI_SHUFFLE = conf_bool(
    "spark.rapids.shuffle.ici.enabled", False,
    "Route shuffle exchanges through the ICI lax.all_to_all collective "
    "over the device mesh when >1 device is available.  Opt-in, like the "
    "reference's RapidsShuffleManager (docs/get-started.md); off means the "
    "single-host exchange path.")
MESH_SPMD_ENABLED = conf_bool(
    "spark.rapids.sql.tpu.mesh.spmd.enabled", True,
    "Fuse contiguous plan segments on either side of a mesh shuffle into "
    "ONE shard_map program: exchanges (hash, round-robin AND range — "
    "range bounds are sampled/sorted/picked in-program) lower to "
    "in-program lax.all_to_all collectives, joins run per-shard with "
    "capacity-bucketed static output sizing, broadcast-join build sides "
    "replicate (PartitionSpec ()) and the whole stage runs with zero "
    "host syncs (host-driven mesh shuffle pays 1 sync + a restage per "
    "exchange).  Requires shuffle.ici.enabled and >1 device; "
    "single-partition collapses are the only remaining host-driven "
    "fallback (see mesh.spmd.autoFallback).  Bit-identical either way.")
MESH_SPMD_AUTO_FALLBACK = conf_bool(
    "spark.rapids.sql.tpu.mesh.spmd.autoFallback", True,
    "With mesh.spmd.enabled, silently route mesh-incompatible exchanges "
    "(single-partition collapses) through the host-driven mesh shuffle, "
    "and rerun a fused stage host-driven when a join's bucketed output "
    "capacity overflows, instead of failing.  false raises on the first "
    "incompatible exchange — a debugging aid to catch segments dropping "
    "out of whole-stage SPMD fusion.")
MESH_SPMD_JOIN_GROWTH = conf_float(
    "spark.rapids.sql.tpu.mesh.spmd.join.growthFactor", 2.0,
    "Pair-capacity growth factor for joins fused into a mesh-SPMD "
    "program: the per-shard static pair capacity is the bucket-quantized "
    "probe capacity times this factor (the host-driven path instead "
    "host-syncs the exact total).  Joins whose true pair count exceeds "
    "the bucket set an in-program overflow flag and the stage reruns "
    "host-driven (mesh.spmd.autoFallback).")
PINNED_POOL_SIZE = conf_bytes(
    "spark.rapids.memory.pinnedPool.size", 0,
    "Size of the pinned host staging pool used by the native runtime for "
    "host<->HBM transfers (0 = disabled).")
CPU_RANGE_PARTITIONING_SAMPLE = conf_int(
    "spark.rapids.sql.rangePartitioning.sampleSize", 1 << 16,
    "Rows sampled per partition when computing range-partitioning bounds.")
MULTITHREADED_READ_THREADS = conf_int(
    "spark.rapids.sql.format.parquet.multiThreadedRead.numThreads", 8,
    "Threads used to read+decode file footers and column chunks in "
    "parallel ahead of device staging.")
STAGE_READAHEAD_BATCHES = conf_int(
    "spark.rapids.sql.tpu.stage.readAheadBatches", 2,
    "Host batches decoded AND staged into HBM ahead of the consumer by a "
    "background thread, so scan decode + host->device transfer overlap "
    "downstream device compute (the reference's read-ahead + semaphore "
    "pattern, GpuParquetScan.scala:647-700).  0 = synchronous staging.")
PARQUET_ENABLED = conf_bool(
    "spark.rapids.sql.format.parquet.enabled", True,
    "Enable the accelerated parquet scan path: multi-threaded read-ahead "
    "decode plus row-group predicate pushdown.  Disabled falls back to "
    "single-threaded plain decode.")
SCAN_PUSHDOWN_ENABLED = conf_bool(
    "spark.rapids.sql.scan.pushdown.enabled", True,
    "Push filter conjuncts into file scans: parquet row groups are "
    "skipped on min/max statistics and Hive key=value partition "
    "directories are pruned before any decode.")
SCAN_V2_ENABLED = conf_bool(
    "spark.rapids.sql.tpu.scan.v2.enabled", True,
    "Parallel scan pipeline (io/scan_v2): sub-file decode parallelism "
    "(parquet row groups / ORC stripes as independent tasks on a "
    "process-shared decode pool), streaming chunk emission so decode "
    "overlaps host->device staging, plus the dictEncoding and "
    "lateMaterialization features below.  Off restores the v1 "
    "file-at-a-time scan (bit-identical results either way).")
SCAN_READAHEAD_DEPTH = conf_int(
    "spark.rapids.sql.tpu.scan.readAhead.depth", 4,
    "Decode tasks kept in flight ahead of the scan consumer by the v2 "
    "pipeline (bounded sliding window over the shared decode pool). "
    "Chunks are still yielded in deterministic file/chunk order.  "
    "<=1 decodes one chunk at a time (no read-ahead).")
SCAN_READAHEAD_ADAPTIVE = conf_bool(
    "spark.rapids.sql.tpu.scan.readAhead.adaptive.enabled", True,
    "Close the read-ahead control loop: the v2 scan adjusts its in-flight "
    "decode-task depth between chunk drains from its own blocked-drain "
    "ratio and the decode pool's utilization gauge — deepening while the "
    "consumer starves and the pool has headroom, shallowing when chunks "
    "are always ready (less host memory pinned in decoded-but-unconsumed "
    "chunks).  Clamped to [1, scan.readAhead.maxDepth].  Ignored (static "
    "depth honored) when scan.readAhead.depth is set explicitly.")
SCAN_READAHEAD_MAX_DEPTH = conf_int(
    "spark.rapids.sql.tpu.scan.readAhead.maxDepth", 16,
    "Upper clamp for the adaptive read-ahead controller "
    "(scan.readAhead.adaptive.enabled) — at most this many decode tasks "
    "in flight ahead of the scan consumer, bounding decoded-chunk host "
    "memory no matter how starved the consumer looks.")
SCAN_PAGE_CHUNK_MIN_BYTES = conf_bytes(
    "spark.rapids.sql.tpu.scan.pageChunk.minBytes", 64 << 20,
    "Sub-row-group decode granularity (v2 parquet): a row group whose "
    "compressed footprint exceeds this is decoded as several column-slab "
    "subtasks on the pool (the projected columns split into balanced "
    "subsets) and reassembled column-wise on the consumer thread, so one "
    "fat row group cannot serialize the decode pool.  <=0 disables "
    "(always one task per row group).")
SCAN_FILE_HANDLE_CACHE_SIZE = conf_int(
    "spark.rapids.sql.tpu.scan.fileHandleCache.size", 8,
    "Per-thread pyarrow file-handle cache capacity (io.decode_pool): "
    "scan chunk tasks reuse the thread's open ParquetFile/ORC reader for "
    "the same path instead of paying open()+footer-parse per row group; "
    "least-recently-used handles past the bound are closed.  <=0 "
    "disables caching (open per chunk, the v1 behavior).")
SCAN_DICT_ENCODING_ENABLED = conf_bool(
    "spark.rapids.sql.tpu.scan.dictEncoding.enabled", True,
    "Keep parquet dictionary-encoded string columns encoded through "
    "host->device staging: the device carries int32 codes plus the "
    "(small) dictionary buffers, so H2D moves indices instead of string "
    "bytes and encode-aware kernels (filter eq, hash/group keys) work "
    "on codes; other kernels materialize on demand.  Only active under "
    "scan v2 when the scan feeds the device directly.")
SCAN_LATE_MAT_ENABLED = conf_bool(
    "spark.rapids.sql.tpu.scan.lateMaterialization.enabled", True,
    "Late materialization for pushed-predicate scans (v2): decode the "
    "predicate columns of a row-group chunk first, evaluate the pushed "
    "conjuncts, and decode the remaining projected columns only when "
    "the chunk has surviving rows — chunks with zero survivors are "
    "skipped entirely (the Filter above re-applies the predicate, so "
    "this only ever drops whole all-false chunks).")
AQE_COALESCE_ENABLED = conf_bool(
    "spark.rapids.sql.adaptive.coalescePartitions.enabled", True,
    "Group small post-shuffle partitions so each downstream task covers "
    "a worthwhile row count (GpuCustomShuffleReaderExec role); join pairs "
    "coalesce by combined size to stay co-partitioned.")
AQE_TARGET_ROWS = conf_int(
    "spark.rapids.sql.adaptive.targetPartitionRows", 1 << 16,
    "Row-count target per coalesced post-shuffle partition (used only "
    "when the exchange did not record byte sizes).")
AQE_TARGET_BYTES = conf_bytes(
    "spark.rapids.sql.adaptive.advisoryPartitionSizeInBytes", 64 << 20,
    "Byte-size target per coalesced post-shuffle partition; preferred "
    "over the row target whenever the exchange recorded per-piece bytes "
    "(the reference coalesces by map-status bytes, GpuCoalesceBatches "
    "goals).")
AQE_REPLAN_JOINS = conf_bool(
    "spark.rapids.sql.adaptive.replanJoins.enabled", True,
    "At execution time, convert a shuffled hash join whose build side "
    "came in under spark.sql.autoBroadcastJoinThreshold (by shuffle-known "
    "bytes) into the broadcast path (GpuCustomShuffleReaderExec / AQE "
    "OptimizeShuffledHashJoin role).")
AQE_SKEW_FACTOR = conf_float(
    "spark.rapids.sql.adaptive.skewJoin.skewedPartitionFactor", 5.0,
    "A coalesced join partition is considered skewed when its size "
    "exceeds this multiple of the median partition size (and the "
    "advisory target); the stream side is then joined in bounded chunks "
    "against the full build side.")
TPU_ADAPTIVE_ENABLED = conf_bool(
    "spark.rapids.sql.tpu.adaptive.enabled", True,
    "Master gate for the runtime-stats replanning layer (plan/adaptive): "
    "post-shuffle partition coalescing, the dynamic shuffled->broadcast "
    "join switch and skew splitting all read ONLY statistics the shuffle "
    "split already fetched (piece_rows/piece_bytes), so turning this on "
    "adds zero host syncs.  Off forces the statically planned shapes.")
ADAPTIVE_COALESCE_TARGET_BYTES = conf_bytes(
    "spark.rapids.sql.tpu.adaptive.coalesce.targetBytes", 0,
    "Byte target per coalesced post-shuffle partition for the adaptive "
    "layer.  0 (default) inherits "
    "spark.rapids.sql.adaptive.advisoryPartitionSizeInBytes (64MB), so "
    "the two knobs cannot fight; set nonzero to tune the adaptive layer "
    "independently of the legacy advisory target.")
ADAPTIVE_SKEW_THRESHOLD_BYTES = conf_bytes(
    "spark.rapids.sql.tpu.adaptive.skew.thresholdBytes", 0,
    "Absolute floor a partition must also exceed (besides "
    "skewedPartitionFactor x median) to be treated as skewed and split "
    "back into its per-source pieces.  0 (default) inherits the adaptive "
    "coalesce byte target, i.e. a partition under one coalesce target is "
    "never worth splitting.")
HASH_AGG_MXU_ENABLED = conf_bool(
    "spark.rapids.sql.agg.mxuHash.enabled", True,
    "Aggregate update batches on the MXU via slot one-hot contractions "
    "when the agg list is sum/count/avg/min/max/first/last and the group "
    "keys are integral/date/bool columns or dictionary-encoded string "
    "columns, grouped by their codes (multi-key via mixed-radix slot "
    "packing): one matmul (plus a scatter pass for min/max-class aggs) "
    "replaces the sort-based groupby's argsort + gathers + scatters.  "
    "Batches whose packed key space exceeds the slot table (or float "
    "sums over NaN/Inf) transparently re-run the exact sort path.")
HASH_AGG_MXU_SLOTS = conf_int(
    "spark.rapids.sql.agg.mxuHash.tableSlots", 8192,
    "Slot-table capacity of the MXU hash aggregate: the product of the "
    "per-key value ranges (plus one per nullable key) must fit here or "
    "the batch falls back to the sort path.  Larger tables admit wider "
    "key spaces at the cost of one-hot contraction FLOPs/memory.")
NLJ_PAIR_CAPACITY = conf_int(
    "spark.rapids.sql.nestedLoopJoin.pairCapacity", 1 << 22,
    "Max cross-pair slots a single nested-loop-join step may allocate; "
    "a stream side whose pair space exceeds this is joined in row chunks "
    "(the reference streams broadcast NLJ per stream batch).")
CSV_ENABLED = conf_bool(
    "spark.rapids.sql.format.csv.enabled", True,
    "Enable the accelerated CSV scan path (multi-threaded read-ahead "
    "decode).  Disabled falls back to single-threaded decode.")
COALESCE_TARGET_ROWS = conf_int(
    "spark.rapids.sql.coalesce.targetRows", 1 << 20,
    "Row goal for the batch-coalesce layer (TargetSize analogue).")
UDF_COMPILER_ENABLED = conf_bool(
    "spark.rapids.sql.udfCompiler.enabled", False,
    "Compile python row UDFs into columnar expressions when possible.")
EXCHANGE_COLLAPSE_LOCAL = conf_bool(
    "spark.rapids.sql.tpu.exchange.collapseLocal", True,
    "Collapse shuffle exchanges to a single logical partition in "
    "single-process execution: partitioning only constrains placement, "
    "which one partition trivially satisfies, so the per-batch pid "
    "compute + split is pure overhead on one device.")
SHUFFLE_SPLIT_V2 = conf_bool(
    "spark.rapids.sql.tpu.exchange.splitV2.enabled", True,
    "Use the one-sync coalescing shuffle split: every input batch's "
    "pid-sort program is dispatched before ONE bulk count/byte-total "
    "fetch, then each target partition is assembled from all sorted "
    "batches by a single k-way segment-gather dispatch (<=N pieces, "
    "~B+N dispatches).  false restores the legacy per-batch split "
    "(B host syncs, one gather per batch x partition pair).")
SHUFFLE_COALESCE_MAX_BYTES = conf_bytes(
    "spark.rapids.sql.tpu.exchange.splitCoalesceMaxBytes", 256 << 20,
    "Spill-budget cap for the coalescing shuffle split: a target "
    "partition whose combined size exceeds this stays as per-batch "
    "pieces so the catalog can spill early pieces while later input "
    "batches still materialize.  <=0 coalesces unconditionally.")
SHUFFLE_DICT_AWARE = conf_bool(
    "spark.rapids.sql.tpu.exchange.dictAware.enabled", True,
    "Dict-aware shuffle split (v2 split only): when input columns are "
    "dictionary-encoded, the pid-sort permutes 4-byte codes and each "
    "coalesced piece carries codes plus ONE merged dictionary instead of "
    "materialized string bytes — the encoded corridor survives the "
    "exchange, and shuffleEncodedBytesSaved records the bytes not moved. "
    "Bit-identical results; piece sizing/AQE statistics still report "
    "materialized bytes so plan decisions match encoded-off exactly.")
JOIN_DICT_KEYS_ENABLED = conf_bool(
    "spark.rapids.sql.tpu.join.dictKeys.enabled", True,
    "Encoded equi-join string keys: when both sides of a hash join key "
    "are dictionary-encoded, probe on int32 codes — directly when the "
    "sides share one dictionary object, else after rendezvous-translating "
    "the smaller side's codes into the larger dictionary's space via a "
    "device entry-matching table (docs/io.md, encoded corridor v2).  "
    "Divergent dictionaries whose entry-pair table would exceed ~4M "
    "cells skip translation and hash entry content through the codes "
    "instead (still encoded, no materialization).")
PIPELINE_SHRINK_BYTES = conf_bytes(
    "spark.rapids.sql.tpu.pipeline.shrinkBytes", 4 << 20,
    "Padded stage outputs at or below this byte total skip the sizes "
    "round-trip + re-bucketing gather at pipeline stage breaks.")
COMPILE_CACHE_DIR = conf_str(
    "spark.rapids.sql.tpu.compileCacheDir", "",
    "Directory for JAX's persistent XLA compilation cache.  When set, "
    "compiled executables survive the process so re-runs (and "
    "session.prewarm()) skip recompilation; empty leaves persistence "
    "to the entry point (benchmark/run.py, chip_smoke.py and the tests use "
    "<checkout>/.jax_cache).  Ignored, with one log line, where the "
    "JAX_COMPILATION_CACHE_DIR environment variable is set: the "
    "operator has placed the cache from outside.")
RETRY_MAX_ATTEMPTS = conf_int(
    "spark.rapids.sql.tpu.retry.maxAttempts", 3,
    "Total attempts (first try included) the unified RetryPolicy allows "
    "a retryable operation: OOM spill-retries, device-lost partition "
    "replays and whole-pipeline recoveries all share this bound.  "
    "Exhausted device-class errors degrade to the per-partition CPU "
    "fallback (fallback.onDeviceError).")
RETRY_BACKOFF_MS = conf_float(
    "spark.rapids.sql.tpu.retry.backoffMs", 50.0,
    "Base backoff milliseconds between retry attempts.  Delays are "
    "deterministic — backoffMs * 2^(attempt-1), a pure function of the "
    "attempt index with no jitter — so faulted runs replay identically.")
PARTITION_TIMEOUT_SEC = conf_float(
    "spark.rapids.sql.tpu.partition.timeoutSec", 0.0,
    "Deadline in seconds for driving one partition (and for one "
    "whole-pipeline stage).  On expiry a watchdog thread raises a "
    "classified PartitionTimeout into the driving thread — the wedged "
    "partition then enters device-lost recovery instead of hanging the "
    "process.  0 disables (the test-tier default; the bench driver "
    "arms it).")
FALLBACK_ON_DEVICE_ERROR = conf_bool(
    "spark.rapids.sql.tpu.fallback.onDeviceError", True,
    "After retry.maxAttempts device replays of a failed partition "
    "(device lost, wedged, or OOM that spilling cannot fix), re-run "
    "just that partition through the CPU operator path so the query "
    "completes with Spark-CPU-identical results.  false surfaces the "
    "raw device error instead.")
FAULTS_SPEC = conf_str(
    "spark.rapids.sql.tpu.faults.spec", "",
    "Deterministic fault injection spec, e.g. "
    "\"dispatch:oom@3;d2h:device_lost@1;spill:slow=200ms@2\": at each "
    "named site (dispatch, h2d, d2h, spill, unspill, exchange, scan, "
    "mesh) the Nth "
    "call raises the named error class (or stalls, for slow=<dur>); @N+ "
    "fires from the Nth call onward.  Call counters reset per query.  "
    "Empty disables injection.")
SPILL_ASYNC_ENABLED = conf_bool(
    "spark.rapids.sql.tpu.spill.async.enabled", True,
    "Run budget-triggered spills on a bounded background writer pool: "
    "reserve() transitions victims to the SPILLING tier under the "
    "catalog lock and returns immediately; the D2H copy and any "
    "compress+disk write overlap compute.  A get() racing an unstarted "
    "spill cancels it cheaply; one racing a started spill joins just "
    "that handle's completion.  false restores the v1 synchronous "
    "spill (every tier move completes before the triggering call "
    "returns).  OOM-triggered spills (run_with_oom_retry) are always "
    "synchronous — eager, but off the catalog lock.")
SPILL_WRITER_THREADS = conf_int(
    "spark.rapids.sql.tpu.spill.writer.threads", 2,
    "Background writer threads draining the async spill queue "
    "(spill.async.enabled).  Each thread performs the D2H copy and the "
    "host-budget compress+write for one victim at a time.")
SPILL_CHUNK_BYTES = conf_bytes(
    "spark.rapids.sql.tpu.spill.chunkBytes", 8 << 20,
    "Frame size for disk spill files: the serialized batch streams "
    "through the compression codec in chunks of this many bytes, so "
    "compression overlaps the file write and unspill starts "
    "decompressing before the whole file is read.  <=0 writes one "
    "whole-batch frame.")
TASK_MAX_FAILURES = conf_int(
    "spark.rapids.task.maxFailures", 0,
    "Legacy cap on partition replay attempts, honored only when set "
    "explicitly on the session; otherwise "
    "spark.rapids.sql.tpu.retry.maxAttempts governs (fault.recovery)."
    "  0 defers to the retry ladder.")
SORT_STRING_PREFIX_BYTES = conf_int(
    "spark.rapids.sql.tpu.sort.stringPrefixBytes", 64,
    "Bytes of each string sort key encoded into u32 comparison words "
    "(kernels.sortkeys): order beyond the prefix is approximate "
    "(documented incompat), larger values cost sort bandwidth.")
OBS_ENABLED = conf_bool(
    "spark.rapids.sql.tpu.obs.enabled", True,
    "Observability event bus (obs.events): instrumentation chokepoints "
    "emit span/instant events into a bounded per-query ring, folded into "
    "session.query_history() profiles.  Disabled cost is one branch per "
    "site; enabled cost is one lock-protected append per event.")
OBS_RING_MAX_EVENTS = conf_int(
    "spark.rapids.sql.tpu.obs.ring.maxEvents", 65536,
    "Event-ring capacity per query; once full, further events increment "
    "last_metrics['obsEventsDropped'] instead of growing memory.")
OBS_HISTORY_MAX = conf_int(
    "spark.rapids.sql.tpu.obs.history.maxQueries", 16,
    "Queries session.query_history() retains (oldest profiles — events "
    "included — are evicted past the bound).")
OBS_EVENT_LOG_DIR = conf_str(
    "spark.rapids.sql.tpu.obs.eventLogDir", "",
    "When set, each query appends its profile header + events as JSONL "
    "to <dir>/events-<pid>.jsonl (the Spark event-log analogue), the "
    "input to tools/rapidsprof.py.  Empty disables the log.")
OBS_TELEMETRY_ENABLED = conf_bool(
    "spark.rapids.sql.tpu.obs.telemetry.enabled", True,
    "Continuous time-series telemetry (obs.timeseries): every obs span "
    "also folds into a process-wide fixed-interval aggregation ring "
    "(per-site count/wall/bytes plus sampled gauges), exported as "
    "Prometheus-style text and JSONL flushes to obs.eventLogDir "
    "(telemetry-<pid>.jsonl, the tools/rapidstop.py input).  Disabled "
    "cost is one branch per emit.")
OBS_TELEMETRY_INTERVAL_MS = conf_int(
    "spark.rapids.sql.tpu.obs.telemetry.intervalMs", 1000,
    "Width of one telemetry aggregation interval: spans landing in the "
    "same wall-clock bucket fold into one ring entry.  Smaller values "
    "give rapidstop finer live resolution at more ring turnover.")
OBS_TELEMETRY_MAX_INTERVALS = conf_int(
    "spark.rapids.sql.tpu.obs.telemetry.maxIntervals", 512,
    "Completed intervals the telemetry ring retains (drop-OLDEST past "
    "the bound — unlike the per-query event ring, the live view must "
    "keep the newest data; drops are counted and exported as a gauge).")
SERVE_MAX_CONCURRENCY = conf_int(
    "spark.rapids.sql.tpu.serve.maxConcurrency", 2,
    "Runner threads the serving scheduler (serve.scheduler) drives "
    "queries with — the number of session.execute calls in flight at "
    "once.  Device admission is still governed per dispatch by "
    "spark.rapids.sql.concurrentTpuTasks; this bounds host-side query "
    "parallelism (planning, staging, result assembly).")
SERVE_BATCH_ENABLED = conf_bool(
    "spark.rapids.sql.tpu.serve.batch.enabled", True,
    "Micro-query batching (serve.batching): queued template queries "
    "that resolve to the same (plan fingerprint, schema, bucket) are "
    "coalesced into one dispatch — rows concatenated, one execute, "
    "results split back per caller bit-identically.  false executes "
    "every submission individually.")
SERVE_BATCH_MAX_DELAY_MS = conf_float(
    "spark.rapids.sql.tpu.serve.batch.maxDelayMs", 2.0,
    "How long a poppable micro-query may wait for coalescing partners "
    "before it dispatches alone — the latency ceiling batching is "
    "allowed to add.  0 batches only queries already queued together.")
SERVE_BATCH_MAX_QUERIES = conf_int(
    "spark.rapids.sql.tpu.serve.batch.maxQueries", 16,
    "Cap on queries coalesced into one micro-batch dispatch (bounds "
    "result-splitting latency and keeps the combined rows inside one "
    "bucket step).")
SERVE_DEADLINE_SEC = conf_float(
    "spark.rapids.sql.tpu.serve.deadlineSec", 0.0,
    "Default per-query deadline, measured from submit: on expiry the "
    "watchdog raises a NON_RETRYABLE DeadlineExceeded into the running "
    "query (no recovery replay — fail fast, neighbors unaffected).  "
    "Per-submission deadlines override; 0 disables.")
SERVE_PLAN_CACHE_MAX = conf_int(
    "spark.rapids.sql.tpu.serve.planCache.maxPlans", 256,
    "LRU bound on the process-wide shared plan/executable cache "
    "(serve.excache) — entries pin their physical plans and compiled "
    "stage programs; past the bound the least-recently-hit plan is "
    "dropped (its executables fall out with it).")
SERVE_BATCH_ADAPTIVE = conf_bool(
    "spark.rapids.sql.tpu.serve.batch.adaptive.enabled", False,
    "Adaptive micro-batch linger (serve.scheduler): instead of the "
    "static serve.batch.maxDelayMs window, size each linger from the "
    "telemetry ring's observed arrival rate — roughly two expected "
    "inter-arrival gaps, clamped to [0, maxDelayMs] — so an idle server "
    "dispatches immediately and a busy one waits just long enough for "
    "the stragglers that are statistically coming.  Falls back to the "
    "static window while telemetry is disabled.")
SERVE_FRONTEND_HOST = conf_str(
    "spark.rapids.sql.tpu.serve.frontend.host", "127.0.0.1",
    "Interface the serve front door (serve.frontend) binds.  The "
    "loopback default keeps the server private to the machine; bind a "
    "routable address only behind real network controls — the NDJSON "
    "protocol itself is unauthenticated.")
SERVE_FRONTEND_PORT = conf_int(
    "spark.rapids.sql.tpu.serve.frontend.port", 0,
    "TCP port of the serve front door.  0 (default) binds an ephemeral "
    "port; read it back from FrontDoorServer.port (tools/rapidsserve.py "
    "--server prints it on its banner line).")
SERVE_FRONTEND_MAX_LINE = conf_bytes(
    "spark.rapids.sql.tpu.serve.frontend.maxLineBytes", 64 << 20,
    "Largest single protocol line (one NDJSON request or response) the "
    "front door will read or a client will accept — bounds per-request "
    "buffering against a runaway or malicious peer.  Submissions "
    "carrying inline columnar data must fit under it.")
SERVE_RESULT_CACHE_ENABLED = conf_bool(
    "spark.rapids.sql.tpu.serve.resultCache.enabled", True,
    "Front-door query result cache (serve.resultcache): final result "
    "sets keyed by (plan fingerprint, conf signature, input identity) "
    "kept as catalog-registered spillable batches, so a repeat query "
    "over unchanged inputs answers with zero compiles and zero "
    "dispatches.  Invalidation follows the fragment-cache rules: input "
    "mtime/size change, plan-relevant conf change, device-generation "
    "bump.  Per-request opt-out via the protocol's cache flag.")
SERVE_RESULT_CACHE_MAX_ENTRIES = conf_int(
    "spark.rapids.sql.tpu.serve.resultCache.maxEntries", 64,
    "LRU entry bound on the front-door result cache.")
SERVE_RESULT_CACHE_MAX_BYTES = conf_bytes(
    "spark.rapids.sql.tpu.serve.resultCache.maxBytes", 128 << 20,
    "Payload-byte bound on the front-door result cache (device bytes of "
    "the cached result batches, LRU-evicted past the bound).  <= 0 "
    "disables insertion while still serving existing entries' "
    "invalidation semantics.")
SERVE_RESULT_CACHE_MIN_NS_PER_BYTE = conf_float(
    "spark.rapids.sql.tpu.serve.resultCache.minNsPerByte", 10.0,
    "Cost-weighted admission floor for the result cache: a result is "
    "cached only when its recorded compute wall (ns) >= this many ns "
    "per payload byte — cheap-to-recompute bulky results (e.g. a "
    "projection of the whole input) are not worth the HBM/spill "
    "footprint they would occupy.  0 admits everything.")
SERVE_ADMISSION_ENABLED = conf_bool(
    "spark.rapids.sql.tpu.serve.admission.enabled", True,
    "Sentinel-driven admission control at the front door: before "
    "executing a deadlined query, consult the history store's "
    "median/MAD wall-time aggregate for its plan fingerprint and shed "
    "it (fail fast with DeadlineExceeded, counted per tenant as "
    "admissionShed) when the prediction already misses the deadline.  "
    "Inactive without a history dir; queries with no deadline or no "
    "baseline are never shed.")
SERVE_ADMISSION_MIN_RUNS = conf_int(
    "spark.rapids.sql.tpu.serve.admission.minRuns", 3,
    "Minimum history-store runs of a fingerprint before admission "
    "control trusts its wall-time prediction — below this an unknown "
    "query always executes (same thin-baseline rule as the regression "
    "sentinel).")
SERVE_ADMISSION_MAD_K = conf_float(
    "spark.rapids.sql.tpu.serve.admission.madK", 3.0,
    "Admission prediction = median + K * MAD of the fingerprint's "
    "recorded wall_ns: K widens the band so run-to-run noise does not "
    "shed queries that usually make their deadline.")
HISTORY_ENABLED = conf_bool(
    "spark.rapids.sql.tpu.history.enabled", True,
    "Master switch for the query-intelligence layer (history/): the "
    "persistent plan-fingerprint statistics store, history-seeded "
    "planning and the cross-query fragment cache.  Takes effect only "
    "when spark.rapids.sql.tpu.history.dir is also set; false pins "
    "byte-for-byte the history-free plans and behavior.")
HISTORY_DIR = conf_str(
    "spark.rapids.sql.tpu.history.dir", "",
    "Directory of the persistent statistics store (history.store): each "
    "query appends one JSONL record of runtime facts keyed by plan "
    "fingerprint (per-exchange rows/bytes, skew, spill pressure, "
    "compile wall), read back lazily to seed later plans.  Empty "
    "disables the whole history subsystem.")
HISTORY_SEED_ENABLED = conf_bool(
    "spark.rapids.sql.tpu.history.seed.enabled", True,
    "History-seeded planning (history.seeding): before first execution "
    "consult the store to right-size shuffle partition counts, hint the "
    "broadcast build side and pre-mark skewed partitions — AQE v1's "
    "runtime decisions applied up front.  A stats-absent or stats-stale "
    "store degrades to exactly the unseeded plan.")
HISTORY_FRAGMENTS_ENABLED = conf_bool(
    "spark.rapids.sql.tpu.history.fragments.enabled", True,
    "Cross-query fragment cache (history.fragcache): materialized "
    "root-subtree outputs are kept as catalog-registered spillable "
    "batches keyed by (plan fingerprint, conf signature, input "
    "identity); a repeat query re-executes zero dispatches for the "
    "cached subtree.  Entries ride the device->host->disk spill tiers "
    "and are never pinned.")
HISTORY_MAX_AGE_SEC = conf_float(
    "spark.rapids.sql.tpu.history.maxAgeSec", 604800.0,
    "Staleness horizon for store records consulted by seeding: records "
    "older than this many seconds (or written under a different "
    "plan-relevant conf signature) are ignored, degrading to the "
    "unseeded plan.  <=0 disables the age check.")
HISTORY_STORE_MAX_RECORDS = conf_int(
    "spark.rapids.sql.tpu.history.store.maxRecords", 1024,
    "Per-store record bound honored by tools/rapidshist.py prune and "
    "the in-process loader: when the JSONL holds more records, only the "
    "newest per fingerprint (newest-first overall) are kept.")
HISTORY_FRAGMENTS_MAX_ENTRIES = conf_int(
    "spark.rapids.sql.tpu.history.fragments.maxEntries", 64,
    "LRU entry bound on the process-wide fragment cache; past it the "
    "least-recently-hit fragment's batches are closed and its catalog "
    "bytes released.")
HISTORY_FRAGMENTS_MAX_BYTES = conf_bytes(
    "spark.rapids.sql.tpu.history.fragments.maxBytes", 256 << 20,
    "Byte bound on fragment-cache residency (sum of cached batch "
    "payloads across tiers); inserting past it evicts least-recently-"
    "hit fragments first.  0 disables insertion.")
HISTORY_AGGREGATE_RUNS = conf_int(
    "spark.rapids.sql.tpu.history.aggregateRuns", 8,
    "Runs per plan fingerprint the statistics store folds into its "
    "robust aggregate (median/MAD of wall, dispatches, compiles, "
    "spill/shuffle bytes) — the regression sentinel's baseline and the "
    "ROADMAP 'aggregated over N runs instead of newest-wins' record "
    "shape.  Seeding still reads the newest record.")
SENTINEL_ENABLED = conf_bool(
    "spark.rapids.sql.tpu.sentinel.enabled", True,
    "Cross-run regression sentinel (obs.sentinel): each query's fresh "
    "metrics are compared against the history store's median/MAD "
    "aggregate for its plan fingerprint; a guarded key outside its band "
    "emits a 'regression' obs instant and bumps "
    "last_metrics['regressionAlerts'].  Active only with "
    "spark.rapids.sql.tpu.history.dir set.")
SENTINEL_MIN_RUNS = conf_int(
    "spark.rapids.sql.tpu.sentinel.minRuns", 3,
    "Aggregated runs a fingerprint needs before the sentinel compares "
    "against it — below this the baseline is too thin to call a "
    "regression (cold caches and first-run compiles would all flag).")
SENTINEL_MAD_THRESHOLD = conf_float(
    "spark.rapids.sql.tpu.sentinel.madThreshold", 4.0,
    "Half-width of the sentinel's acceptance band in robust deviations: "
    "a guarded key regresses when value > median + threshold * "
    "max(MAD, 25% of median, key floor).  Larger values tolerate more "
    "run-to-run noise before alerting.")
PALLAS_STRINGS_ENABLED = conf_bool(
    "spark.rapids.sql.tpu.pallas.strings.enabled", True,
    "Kernel-tier gate for the Pallas string contains/LIKE scan "
    "(kernels.pallas_strings): one fused pass over the byte buffer "
    "replacing the shifted-gather + searchsorted XLA formulation.  "
    "Engages on a real TPU backend only (or under pallas.interpret); "
    "anywhere else the bit-identical XLA formulation runs and "
    "pallasFallbackCount increments.  Default on: the v5e compiler "
    "accepts it at TPC-H SF1 shapes (tests/test_chip_compile.py).")
PALLAS_GATHER_SCATTER_ENABLED = conf_bool(
    "spark.rapids.sql.tpu.pallas.gatherScatter.enabled", False,
    "Kernel-tier gate for the segmented k-way gather/scatter Pallas "
    "kernel: one pass per output block walking the per-input segment "
    "table replaces the k drop-mode scatter chain inside concat_kway / "
    "gather_segments_kway (rows and bytes, honoring the live-bytes "
    "window so take_head-truncated inputs cannot leak stale tail "
    "bytes).  Unsupported element dtypes always take the XLA chain.  "
    "Default OFF: the v5e compiler refuses the kernel's 1-D vector "
    "gathers (docs/kernels.md has its message); enabling it on a chip "
    "fails the stage's compile.")
PALLAS_JOIN_PROBE_ENABLED = conf_bool(
    "spark.rapids.sql.tpu.pallas.joinProbe.enabled", False,
    "Kernel-tier gate for the hash-join probe Pallas kernel: when the "
    "sorted build-side arrays fit pallas.vmemBudgetBytes, one fused "
    "kernel performs both searchsorted passes, candidate expansion and "
    "the exact-match word verify of join_pairs_static, emitting the "
    "same capacity-bucketed pair buffers (hash_join_static and the "
    "mesh-fused pipeline consume it unchanged).  Default OFF: the v5e "
    "compiler refuses the kernel's 1-D vector gathers "
    "(docs/kernels.md); enabling it on a chip fails the stage's "
    "compile.")
PALLAS_STRING_HASH_ENABLED = conf_bool(
    "spark.rapids.sql.tpu.pallas.stringHash.enabled", False,
    "Kernel-tier gate for the string key-hash Pallas kernel: a "
    "row-blocked Horner pass over the byte buffer with segment "
    "boundaries from the offsets replaces the pow-table + segment-sum "
    "XLA formulation of string_hash2 (sort/join key hashing).  "
    "Default OFF: the v5e compiler refuses the kernel's 1-D vector "
    "gathers (docs/kernels.md); enabling it on a chip fails the "
    "stage's compile.")
PALLAS_INTERPRET = conf_bool(
    "spark.rapids.sql.tpu.pallas.interpret", False,
    "Debug: run every engaged kernel-tier Pallas kernel in interpret "
    "mode (pure XLA emulation of the kernel program) so CPU-backend "
    "tests can pin bit-identity against the XLA fallbacks.  Orders of "
    "magnitude slower than compiled kernels — never enable in "
    "production.")
PALLAS_VMEM_BUDGET = conf_bytes(
    "spark.rapids.sql.tpu.pallas.vmemBudgetBytes", 8 << 20,
    "VMEM residency budget shared by the kernel tier: a kernel whose "
    "resident working set (e.g. the join probe's sorted build arrays) "
    "exceeds this many bytes takes the XLA formulation and "
    "counts into pallasFallbackCount.  Sized well under a TPU core's "
    "~16 MB VMEM to leave room for per-block buffers.")


def registry() -> List[ConfEntry]:
    return sorted(_REGISTRY.values(), key=lambda e: e.key)


def generate_docs() -> str:
    """Markdown doc generation (analogue of RapidsConf.main -> docs/configs.md)."""
    lines = [
        "# spark_rapids_tpu configuration",
        "",
        "| Key | Default | Description |",
        "|---|---|---|",
    ]
    for e in registry():
        if not e.internal:
            lines.append(f"| `{e.key}` | {e.default} | {e.doc} |")
    return "\n".join(lines) + "\n"


#: Process-wide active configuration (sessions may carry their own copies).
conf = RapidsConf()
