"""Engine-wide configuration for the TensorFrame relational engine.

Mirrors MojoFrame's user-facing knobs (§VI-A of the paper): the
cardinality threshold that decides dictionary-encoding vs offloading,
plus TPU-adaptation knobs (measure dtype, device string path).
"""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass
class EngineConfig:
    # Columns with (n_distinct / n_rows) <= card_threshold are
    # dictionary-encoded into the int tensor; above it they are offloaded
    # (paper §III-c/d uses 50%).
    card_threshold: float = 0.5
    # Measure tensor dtype: float64 on every backend, so answers match
    # the references.  The TPU has no native float64 and emulates it
    # with pairs of float32; narrowing the measures is an open decision
    # (it changes results).
    float_dtype: str = "float64"
    # When True, string predicates on offloaded columns run on the packed
    # (n, maxlen) uint8 device tensor (Pallas kernel on TPU, jnp ref on
    # CPU) instead of the host dictionary-LUT path.
    use_device_strings: bool = False
    # Maximum packed string width for the device string path.
    max_packed_len: int = 128
    # Distributed execution route (repro.dist): 'off' never shards,
    # 'force' always takes the sharded route (tests exercise it on a
    # 1-device mesh), 'auto' shards group-by reduction sums and
    # semi/anti-join probes when more than one device is visible and the
    # input has at least dist_min_rows rows.
    distributed: str = "auto"
    dist_min_rows: int = 1 << 16
    # Late materialization (ISSUE 5): when True, row-subsetting ops
    # (take / mask_rows / filter / join / sort) return RowView frames
    # that compose gather indices instead of copying payload tensors;
    # payloads materialize once at pipeline exits.  False restores the
    # eager copy-per-op engine (benchmark baseline / debugging).
    late_materialization: bool = True
    # Whole-plan compilation (repro.sql.compile): 'off' never compiles,
    # 'force' compiles every supported plan regardless of size, 'auto'
    # compiles when the scanned base tables total at least
    # compiled_min_rows rows (small interactive queries skip the trace
    # cost; repeated large ones amortize it through the plan cache).
    # Plans with untraceable constructs fall back to op-by-op dispatch
    # in every mode.
    compiled: str = "auto"
    compiled_min_rows: int = 1 << 15
    # Concurrent SQL serving (repro.serve.sql): the admission queue
    # drains up to serve_max_batch requests per micro-batch, waiting at
    # most serve_batch_window_ms after the first request for stragglers
    # to accumulate.  serve_shared_scans=False disables the shared
    # zone-map scan pass (every query scans its store tables alone —
    # the benchmark baseline for the sharing win); serve_coalesce=False
    # disables duplicate-query coalescing within a batch.
    serve_max_batch: int = 32
    serve_batch_window_ms: float = 2.0
    serve_shared_scans: bool = True
    serve_coalesce: bool = True
    # Resilient serving (repro.resilience): default per-request deadline
    # (None = unbounded; Executor.execute(timeout_s=...) overrides per
    # call), admission-queue depth bound (None = unbounded) with the
    # load-shedding policy applied when it fills ('reject-new' fails the
    # incoming request, 'drop-oldest' sheds the head of the queue), and
    # a per-session in-flight request cap (None = uncapped).  Shed and
    # rejected requests resolve with typed QueryErrors and are counted
    # in serve.STATS.
    serve_default_timeout_s: Optional[float] = None
    serve_queue_depth: Optional[int] = None
    serve_shed_policy: str = "reject-new"
    serve_session_inflight: Optional[int] = None
    # Transient-I/O retry budget (repro.resilience.retry): spill/store
    # reads and writes retry OSError-class failures up to io_retries
    # times with exponential backoff starting at io_retry_base_s.
    io_retries: int = 3
    io_retry_base_s: float = 0.005
    # Out-of-core execution (repro.core.pipeline / repro.sql.stream):
    # 'off' never streams, 'force' streams every supported store-backed
    # aggregate/join pipeline chunk-by-chunk, 'auto' streams when the
    # probe-side store table has at least ooc_min_rows rows (mirrors
    # distributed/compiled).  Unsupported plan shapes fall back to the
    # eager path in every mode (counted in pipeline.STATS['fallbacks']).
    out_of_core: str = "auto"
    ooc_min_rows: int = 1 << 20
    # Byte budget for host-side intermediates held by the out-of-core
    # layer (partial aggregates, spillable frames).  None = unbounded
    # (never spill); small budgets force LRU spills to .tfb v2 chunk
    # files with transparent re-hydration on access.
    memory_budget_bytes: Optional[int] = None
    # Directory for spill files; None = a per-process temp dir cleaned
    # at exit (spilled frames are additionally deleted on GC).
    spill_dir: Optional[str] = None
    # Host-side chunk prefetch depth of the streaming scan: chunk k+1
    # decodes/filters on a worker thread while chunk k runs on device.
    # 0 disables the overlap (the bench_spill baseline).
    ooc_prefetch: int = 2
    # Merge accumulated per-chunk partial aggregates every N chunks
    # (bounds the partial pool even when the budget is unbounded).
    ooc_merge_every: int = 8
    # Span tracing (repro.obs): 'off' = no spans (one branch, no
    # allocation on every instrumented path), 'on' = operator-level
    # spans (plan nodes, join algorithm picks, compile phases, serve
    # batch phases, spill events), 'detailed' = additionally per-chunk
    # spans (chunk decode, prefetch waits, per-chunk probes/merges).
    # EXPLAIN ANALYZE flips this to 'on' for the analyzed execution.
    tracing: str = "off"


CONFIG = EngineConfig()
