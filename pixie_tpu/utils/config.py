"""Flag/config system.

Ref: the reference's C++ gflags-with-env-defaults pattern
(pem_main.cc:28-36, DECLARE_int32(table_store_table_size_limit)
table.h:51) and Go pflag+viper. Flags are declared where they are used
(``define_flag``), read env overrides ``PIXIE_TPU_<UPPER_NAME>`` at first
access, and can be set programmatically (tests, embedders) via
``flags.set(name, value)``.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable, Optional


class _Flags:
    def __init__(self):
        self._lock = threading.Lock()
        self._defs: dict[str, tuple[Any, Callable, str]] = {}
        self._values: dict[str, Any] = {}

    def define(
        self,
        name: str,
        default: Any,
        parser: Optional[Callable] = None,
        help_: str = "",
    ) -> None:
        with self._lock:
            if name in self._defs:
                return  # first definition wins (idempotent imports)
            if parser is None:
                if isinstance(default, bool):
                    parser = lambda s: s in (True, "1", "true", "True")
                elif isinstance(default, int):
                    parser = int
                elif isinstance(default, float):
                    parser = float
                else:
                    parser = str
            self._defs[name] = (default, parser, help_)

    def get(self, name: str) -> Any:
        with self._lock:
            if name in self._values:
                return self._values[name]
            if name not in self._defs:
                raise KeyError(f"flag {name!r} is not defined")
            default, parser, _ = self._defs[name]
            env = os.environ.get(f"PIXIE_TPU_{name.upper()}")
            value = parser(env) if env is not None else default
            self._values[name] = value
            return value

    def set(self, name: str, value: Any) -> None:
        with self._lock:
            if name not in self._defs:
                raise KeyError(f"flag {name!r} is not defined")
            self._values[name] = value

    def reset(self, name: str) -> None:
        """Forget a cached/overridden value (re-reads env on next get)."""
        with self._lock:
            self._values.pop(name, None)

    def describe(self) -> dict[str, tuple[Any, str]]:
        with self._lock:
            return {
                name: (self._values.get(name, d[0]), d[2])
                for name, d in sorted(self._defs.items())
            }

    def __getattr__(self, name: str) -> Any:
        if name.startswith("_"):
            raise AttributeError(name)
        return self.get(name)

    def __setattr__(self, name: str, value: Any) -> None:
        # `flags.x = v` must be equivalent to set("x", v): a plain
        # instance attribute would SHADOW __getattr__ forever, silently
        # decoupling later set() calls from reads.
        if name.startswith("_"):
            object.__setattr__(self, name, value)
        else:
            self.set(name, value)


flags = _Flags()


def define_flag(
    name: str,
    default: Any,
    parser: Optional[Callable] = None,
    help_: str = "",
) -> None:
    flags.define(name, default, parser, help_)


# -- engine-wide knobs (declared centrally; component-local flags are
#    declared next to their use) -------------------------------------------
define_flag(
    "device_block_rows",
    1 << 17,
    help_="Rows per staged device block (parallel/staging.py).",
)
define_flag(
    "streaming_stage",
    True,
    help_="Stream cold-path staging as a double-buffered window pipeline "
    "(host pack ∥ HBM transfer ∥ device fold) instead of materializing "
    "the whole table in HBM before the first FLOP (MeshExecutor). The "
    "monolithic path remains the fallback (multi-pass group windows, "
    "streaming failures) and still serves warm cache hits.",
)
define_flag(
    "streaming_window_rows",
    1 << 23,
    help_="Rows per streamed staging window (clamped to the table size; "
    "a single-window stream reproduces the monolithic geometry exactly).",
)
define_flag(
    "signature_buckets",
    True,
    help_="Bucket staging geometry so compiled-program signatures are "
    "coarse: block counts round up to quarter-octave pow2-scaled buckets "
    "(<=25% padding, masked) and stream-window geometry derives from the "
    "pow2-padded row count — two tables or stream windows landing in the "
    "same bucket share ONE compiled executable, and the bucketed shapes "
    "are process-stable so the persistent .jax_cache hits across runs.",
)
define_flag(
    "aot_compile",
    True,
    help_="AOT-compile the streamed-staging fold program "
    "(jit.lower().compile()) on a background thread while host pack and "
    "HBM transfer stream, so the cold XLA compile overlaps staging "
    "instead of preceding it; failures fall back to the in-line jit path "
    "(MeshExecutor.stream_fallback_errors).",
)
define_flag(
    "program_decompose",
    True,
    help_="Run warm/monolithic queries through separately-jitted, "
    "separately-cached init/fold/merge/finalize program units instead of "
    "one fused program: a query differing only in finalize reuses the "
    "expensive fold executable, and each smaller unit compiles faster. "
    "Off = the fused single-dispatch program (r6 behavior).",
)
define_flag(
    "sorted_compact",
    True,
    help_="Enable the r8 sort–compact segment-reduction lane on TPU-class "
    "platforms: HLL register maxes, count-min bucket counts, and "
    "high-cardinality min/max group-bys above segment.SORTED_MIN_ROWS "
    "ride sort → first-occurrence → compact → O(num_segments) scatter "
    "instead of the ~7ns/row full-length scalar scatter "
    "(ops/segment.sorted_segment_reduce_compact). CPU always keeps the "
    "direct scatter; tests can force either lane via "
    "segment.set_sorted_strategy().",
)
define_flag(
    "prewarm_compile",
    False,
    help_="At table-create time, kick the background AOT machinery for "
    "the table's bucketed stream-window geometry: a canonical "
    "count+sum(float64 columns) group-by(first string column) fold is "
    "lower().compile()d on the AOT thread, so a matching first query "
    "skips its fold compile (cold-breakdown key prewarm_hit) and the "
    "persistent .jax_cache deserializes during table setup instead of "
    "on the query's critical path (MeshExecutor.prewarm_table).",
)
define_flag(
    "staged_cache_cap",
    4,
    help_="LRU capacity of HBM-resident staged tables (MeshExecutor).",
)
define_flag(
    "keyplan_cache_cap",
    4,
    help_="LRU capacity of host-densified group-key plans (MeshExecutor).",
)
define_flag(
    "broker_max_pending",
    256,
    help_="Bound on buffered result messages per query at the broker; "
    "producers block when full (flow control, ref: "
    "query_result_forwarder.go:502).",
)
define_flag(
    "broker_publish_timeout_s",
    10.0,
    help_="How long a producer blocks on a full result queue before the "
    "message is dropped and counted (bus_publish_dropped_total).",
)
define_flag(
    "device_group_state_budget_mb",
    512,
    help_="Memory budget for per-group UDA state on device; group-bys "
    "whose state would exceed it run in multiple gid-window passes "
    "(high-cardinality spill/recombine).",
)
define_flag(
    "device_scan_limit_cap",
    1 << 20,
    help_="Largest LimitOp n the device scan path accepts; bigger outputs "
    "are host-engine work (shipping the whole selection back forfeits "
    "the offload).",
)
define_flag(
    "device_join",
    True,
    help_="Device sort-merge join lane (r19): standalone INNER/LEFT/RIGHT/"
    "OUTER equijoins ride the r8 sort–compact machinery instead of the "
    "host JoinNode when the shape qualifies (parallel/pipeline.py "
    "match_join). Off = every join runs on the host engine.",
)
define_flag(
    "device_join_min_rows",
    1 << 18,
    help_="Combined build+probe row floor below which a join stays on the "
    "host engine — staging two sides for a small join costs more than "
    "the Python hash join (analogous to SORTED_MIN_ROWS; provisional, "
    "CPU-tuned, pending the TPU campaign).",
)
define_flag(
    "device_join_max_out",
    1 << 24,
    help_="Largest device-join output cardinality (matches + sentinel "
    "null rows) accepted on the merge lane; bigger joins are host work "
    "(the bounded-fanout gather pads to a power-of-two cap and i32 "
    "prefix math must stay exact).",
)
define_flag(
    "mesh_axes",
    "",
    help_="Mesh geometry for MeshExecutor when no mesh is passed "
    "explicitly, as comma-separated name:size pairs, outermost axis "
    "first (e.g. 'hosts:2,d:4'). A size of -1 (at most one axis) "
    "means 'all remaining devices'. Empty: a flat single-host mesh "
    "'d:<ndevices>'. Geometry is part of every compiled program "
    "signature, so a geometry change can never reuse a stale "
    "executable (pixie_tpu/distributed/mesh.py).",
)
define_flag(
    "mesh_distributed_join",
    True,
    help_="On a multi-axis mesh, run device equijoins as a distributed "
    "sort-merge: range-partition both sides by packed key across the "
    "hosts axis (balanced by per-key join work from the exact host "
    "bincounts), sort + merge locally per shard, concatenate — "
    "instead of the v1 replicated all_gather sort. Bit-identical to "
    "the host EquijoinNode. Off, or on a flat mesh: the v1 replicated "
    "path runs unchanged.",
)
define_flag(
    "mesh_fold_placement",
    True,
    help_="Adds the mesh_fold rung to the placement ladder: when a "
    "query's estimated staging span exceeds every live agent's "
    "advertised HBM headroom, admission stops forcing a single-agent "
    "pick and plans the fold across the full fleet (spanning "
    "placement) instead of thrashing one agent's residency ring.",
)
define_flag(
    "mesh_fold_checkpoint",
    True,
    help_="Window-level fold checkpointing on multi-axis meshes (r23): "
    "the stream fold pulls its carried per-device UDA state host-side "
    "at every window boundary, so a mid-stream geometry failure "
    "(host loss, hung collective) resumes from the last completed "
    "window on the degraded geometry instead of refolding from "
    "scratch. Merge order is preserved, so sketches and group order "
    "stay bit-identical. No effect on a flat (single-host) mesh.",
)
define_flag(
    "mesh_dispatch_timeout_s",
    0.0,
    help_="Collective watchdog deadline (seconds) around each sharded "
    "mesh fold dispatch: a dispatch that blocks past the deadline is "
    "treated as a hung collective and re-planned on the next "
    "degradation rung (pixie_tpu/distributed/mesh.py ladder). 0 = "
    "derive the deadline from the executor's own completed walls: "
    "max(0.25, mesh_watchdog_rail_factor x the fold signature's slowest "
    "wall, 4 x the slowest wall of any signature), and no watchdog for "
    "a signature that has not completed a dispatch yet. Negative "
    "disables the watchdog outright.",
)
define_flag(
    "mesh_watchdog_rail_factor",
    32.0,
    help_="Multiplier on a fold signature's slowest completed dispatch "
    "wall when deriving the collective-watchdog deadline (only when "
    "mesh_dispatch_timeout_s is 0). Generous by design: the watchdog "
    "exists to catch HUNG collectives, not slow ones — a false trip "
    "costs a full re-plan on the degraded rung.",
)
define_flag(
    "mesh_breaker_threshold",
    2,
    help_="Consecutive geometry failures (host loss / collective "
    "timeout) on one mesh signature before the per-geometry breaker "
    "opens and new folds skip straight to the next degradation rung. "
    "0 disables the per-geometry breaker (every fold starts at full "
    "geometry).",
)
define_flag(
    "mesh_breaker_cooldown_s",
    30.0,
    help_="Seconds an open mesh-geometry rung stays skipped before a "
    "half-open trial is allowed back on that geometry (success closes "
    "the breaker and restores the rung; failure re-opens it).",
)
define_flag(
    "view_tail_placement",
    True,
    help_="Route a view hit's unflushed-tail delta fold to the view's "
    "maintain agent (the r18 tracker pick recorded at registration) "
    "instead of folding on the broker — the agent already holds the "
    "table's resident ring and the view's carried state. Off: tail "
    "folds run wherever the probe runs (broker-local).",
)
define_flag(
    "agent_expiry_s",
    2.0,
    help_="Heartbeat silence before an agent is pruned from plans "
    "(ref: 1 minute, agent_topic_listener.go:41; scaled down).",
)
define_flag(
    "agent_heartbeat_interval_s",
    0.5,
    help_="Agent heartbeat period (ref: ~5s, scaled down).",
)

# -- robustness (r9): deadlines, partial results, backoff, breaker ----------
define_flag(
    "query_deadline_s",
    0.0,
    help_="Per-query hard deadline propagated broker→agent→exec graph so "
    "a stalled fragment aborts everywhere, not just at the client "
    "(QueryDeadlineExceeded). 0 disables; the broker uses "
    "min(timeout_s, query_deadline_s) when set.",
)
define_flag(
    "partial_results",
    True,
    help_="When an agent dies, errors, or misses the deadline mid-query, "
    "the broker returns the rows it has plus a structured per-agent "
    "``degraded`` annotation on the QueryResult instead of raising "
    "(ref: query_result_forwarder.go:395 forwards partial results with "
    "per-agent timeout/cancel annotations). Off = r8 raise behavior.",
)
define_flag(
    "agent_backoff_initial_s",
    0.05,
    help_="Initial delay for agent control-bus reconnect backoff "
    "(transport.py RemoteBus; doubles per attempt up to "
    "agent_backoff_max_s, with jitter).",
)
define_flag(
    "agent_backoff_max_s",
    2.0,
    help_="Ceiling for the agent reconnect exponential backoff.",
)
define_flag(
    "agent_backoff_jitter",
    0.25,
    help_="Fractional jitter applied to each reconnect delay (delay *= "
    "1 + jitter*U[0,1)) so a restarted broker is not thundering-herded.",
)
define_flag(
    "agent_reconnect_max_tries",
    64,
    help_="Reconnect attempts before a RemoteBus gives up and stays "
    "closed (0 = retry forever).",
)
define_flag(
    "device_breaker_threshold",
    3,
    help_="Consecutive device fold/compile failures for one program key "
    "before the circuit breaker trips that key to the host engine "
    "(parallel/pipeline.py). 0 disables the breaker.",
)
define_flag(
    "device_breaker_cooldown_s",
    30.0,
    help_="Seconds a tripped device program key stays on the host engine "
    "before a half-open trial is allowed back on the mesh.",
)

# -- serving (r12): HBM residency, shared scans, admission control ----------
define_flag(
    "serving_enabled",
    False,
    help_="Multi-query serving mode (pixie_tpu/serving/): "
    "QueryBroker.execute_script routes through admission control "
    "(concurrency limit + per-tenant weighted fair queueing + HBM "
    "byte-budget check), rejecting with a structured AdmissionRejected "
    "on overload instead of queueing unboundedly. Off = the r11 "
    "one-query-at-a-time relay behavior.",
)
define_flag(
    "hbm_budget_mb",
    0,
    help_="HBM byte budget for the staged-table residency pool "
    "(serving/residency.py). Inserting past the high watermark (95% of "
    "the budget) evicts LRU unpinned entries until under the low "
    "watermark (80%); pinned entries (in-flight folds) are never "
    "evicted. 0 = no byte budget (entry-count staged_cache_cap only).",
)
define_flag(
    "shared_scans",
    True,
    help_="Coalesce concurrent compatible queries over the same staged "
    "table into ONE device fold dispatch (serving/shared_scan.py): "
    "queries whose fold signatures match (r7 decomposed units — output "
    "names and finalize modes excluded) share the leader's merged "
    "states and fan out per-query finalizes. Results are bit-identical "
    "to serial execution; saved dispatches are counted "
    "(serving_shared_scan_saved_dispatches_total) and each query's "
    "trace records shared_scan_batch_size.",
)
define_flag(
    "shared_scan_window_ms",
    0.0,
    help_="Batching window before a shared-scan leader dispatches: the "
    "leader waits this long for compatible queries to join its batch. "
    "0 (default) coalesces only queries that overlap the dispatch "
    "itself — no added latency; soak/serving harnesses raise it to "
    "trade p50 for dispatch reduction.",
)
define_flag(
    "admission_max_concurrent",
    8,
    help_="Queries executing concurrently through the broker's admission "
    "controller (serving/admission.py) before new arrivals queue.",
)
define_flag(
    "admission_max_queue",
    64,
    help_="Queued queries the admission controller holds before "
    "rejecting new arrivals with AdmissionRejected(reason=queue_full).",
)
define_flag(
    "admission_timeout_s",
    10.0,
    help_="Longest a query waits in the admission queue before a "
    "structured AdmissionRejected(reason=timeout) — a rejected query "
    "returns an error, never hangs.",
)
define_flag(
    "admission_tenant_weights",
    "",
    help_="Per-tenant weighted-fair-queueing weights, "
    "'tenant:weight,tenant:weight'. Unlisted tenants get weight 1.0; a "
    "tenant's queued queries accrue virtual time at 1/weight, so a "
    "2x-weighted tenant drains twice as fast under contention and a "
    "starved tenant's first query always schedules ahead of a heavy "
    "tenant's backlog tail.",
)

# -- predicate-batched shared scans + closed-loop admission (r16) ------------
define_flag(
    "shared_scan_predicate_batching",
    True,
    help_="Widen shared-scan compatibility from identical-signature to "
    "predicate-COMPATIBLE (serving/shared_scan.py ladder rung 2): "
    "concurrent queries matching on everything except their predicates "
    "batch into ONE fold dispatch whose per-query predicate masks "
    "evaluate inside a single scan of the staged blocks (masked "
    "partial-agg state lanes stacked on a slot axis, per-query finalize "
    "fan-out — bit-identical to serial). The batched executable is "
    "keyed by a predicate-ERASED fold signature + pow2 batch-width "
    "bucket, so batch composition changes never recompile; the "
    "serving_shared_scan_batch_width histogram is the headline metric.",
)
define_flag(
    "shared_scan_max_batch",
    16,
    help_="Most predicate slots one batched shared-scan dispatch "
    "serves; arrivals past it start the next batch. Bounds the batched "
    "program's state memory (B x per-query state lanes) and compile "
    "variety (widths bucket to pow2 up to this).",
)
define_flag(
    "admission_controller",
    False,
    help_="Close the admission loop (serving/controller.py): an "
    "SLO-window-driven adapter riding the cron runner reads admission "
    "wait quantiles, queue depth, device-dispatch wall time, and HBM "
    "residency, and actuates admission_max_concurrent / "
    "shared_scan_window_ms / hbm_budget_mb within guard rails — a "
    "controller, not a knob. Off = the r12 static flag values.",
)
define_flag(
    "admission_controller_interval_s",
    2.0,
    help_="Seconds between admission-controller evaluation ticks (the "
    "cron ticker period; each tick is one control-law step over the "
    "window since the previous tick).",
)
define_flag(
    "admission_controller_min_concurrent",
    2,
    help_="Guard rail: the controller never moves "
    "admission_max_concurrent below this floor.",
)
define_flag(
    "admission_controller_max_concurrent",
    128,
    help_="Guard rail: the controller never moves "
    "admission_max_concurrent above this ceiling.",
)
define_flag(
    "admission_controller_max_window_ms",
    50.0,
    help_="Guard rail: the controller never raises "
    "shared_scan_window_ms above this ceiling (floor is 0 — the window "
    "is already demand-gated on queue depth).",
)
define_flag(
    "admission_controller_max_hbm_mb",
    0,
    help_="Guard rail: ceiling for controller-raised hbm_budget_mb. 0 "
    "disables HBM actuation entirely (the controller never invents a "
    "budget and never touches one it cannot bound).",
)
define_flag(
    "admission_controller_holddown_windows",
    3,
    help_="Post-brake hold-down (r17 satellite): after the controller "
    "HALVES admission_max_concurrent on HBM pressure, concurrency "
    "raises are suppressed for this many evaluation windows — the "
    "brake's effect must be observed before the MIMD law may climb "
    "again (damps the 8->128->floor->16 oscillation the 1k-client "
    "trail showed). Further braking is always allowed; 0 disables "
    "the hold-down.",
)
define_flag(
    "admission_controller_wait_target_ms",
    250.0,
    help_="Control target: windowed admission-wait p50 above this "
    "raises concurrency (when HBM headroom allows); a p50 under a "
    "tenth of it with an empty queue decays concurrency back toward "
    "the configured baseline.",
)

# -- staging codec + device-resident ingest (r13) ----------------------------
define_flag(
    "staging_codec",
    True,
    help_="Compress host→HBM staging transfers with per-column "
    "lightweight encoders (ops/codec.py): RLE for runs, delta+narrow "
    "for timestamps/monotone ids, passthrough when neither pays. The "
    "host packs ENCODED shards, the wire carries the compressed "
    "representation, and a jitted device program decodes ahead of the "
    "fold — decoded blocks are bit-identical to an uncompressed "
    "transfer, so fold programs, staged-cache entries, and shared "
    "scans are untouched. Cold breakdowns gain stage_encode/"
    "stage_decode/wire_bytes/codec_ratio.",
)
define_flag(
    "staging_codec_min_ratio",
    1.4,
    help_="Minimum compression ratio (decoded bytes / wire bytes) an "
    "encoder must achieve at plan time before a column ships encoded; "
    "below it the column ships passthrough (encode+decode cycles are "
    "cheap but not free).",
)
define_flag(
    "resident_ingest",
    False,
    help_="Device-resident incremental ingest (serving/resident.py): "
    "table appends accumulate into HBM-resident ring windows (the r6 "
    "windowed layout, raw dtypes, codec-compressed on the wire), so a "
    "query over a hot table finds full windows already in HBM and "
    "stages only the cold tail — stage_transfer ≈ 0 for the "
    "in-window span. Ring entries are pinned and byte-accounted in "
    "the residency pool like staged entries.",
)
define_flag(
    "resident_window_rows",
    1 << 21,
    help_="Rows per device-resident ring window. Queries over a ring "
    "table stream at this window size so plan windows align with ring "
    "windows exactly (a resident window substitutes for a "
    "pack+transfer, bit for bit).",
)
define_flag(
    "resident_max_windows",
    64,
    help_="Ring depth per table: oldest resident windows are released "
    "(and their pool bytes freed) past this bound — the device-side "
    "ring-buffer analogue of the table store's size_limit expiry.",
)

# -- durability (r14): crash-restart recovery --------------------------------
define_flag(
    "durable_transport",
    False,
    help_="Persist the RemoteBus delivery identity (agent_id + epoch) "
    "and spill the in-flight ack window to a checksummed WAL under "
    "wal_dir (vizier/durability.py TransportWAL), so a full agent "
    "process restart replays unacked frames above the server's applied "
    "watermark — exactly-once across crash, not just reconnect. "
    "Requires wal_dir; no-op without it.",
)
define_flag(
    "durable_resident",
    False,
    help_="Mirror each ResidentRing's full HBM windows and its partial "
    "host buffer to a per-table spill log under wal_dir "
    "(vizier/durability.py RingSpill): a restarted agent re-stages its "
    "rings into HBM from disk before accepting queries instead of "
    "losing every hot window (stage_resident_hits recover without "
    "replaying appends). Requires wal_dir and resident_ingest.",
)
define_flag(
    "wal_dir",
    "",
    help_="Directory for durable-restart state: the transport WAL "
    "(transport.wal), the agent's durable registration/query markers "
    "(agent-<id>.db, id-keyed so co-located agents never share "
    "state), and per-table resident-ring spill files "
    "(resident/<table>.wal). Empty disables all durability even when "
    "the durable_* flags are set.",
)
define_flag(
    "wal_fsync",
    "always",
    help_="WAL fsync policy: 'always' fsyncs every appended record "
    "(survives node power loss), 'never' flushes to the OS page cache "
    "only (survives process crash — OOM-kill, deploy, SIGKILL — but "
    "not a kernel panic). tools/microbench_fault_overhead.py reports "
    "the cost of each under durability_overhead.",
)
define_flag(
    "transport_wal_mem_frames",
    64,
    help_="In-flight window frames kept decoded in memory when the "
    "transport WAL is on; older unacked frames keep only their seq and "
    "byte count in RAM and are re-read from the WAL at replay time "
    "(the ARIES-style spill bound).",
)

# -- transparent fragment failover (r17) -------------------------------------
define_flag(
    "fragment_failover",
    False,
    help_="Transparent fragment failover (vizier/broker.py): when a "
    "fragment is lost mid-query (heartbeat death, execute error, "
    "restart refusal, forwarder drop) the broker re-launches it on a "
    "surviving capable agent instead of synthesizing eos — the query "
    "completes with FULL, bit-identical results and a ``recovered`` "
    "annotation instead of a ``degraded`` one. Retries are "
    "exactly-once: every attempt carries a per-fragment result epoch, "
    "the broker applies exactly one attempt's output, and bridge "
    "pushes commit atomically per attempt (exec/router.py). Off = the "
    "r9 partial-results behavior.",
)
define_flag(
    "fragment_max_retries",
    2,
    help_="Most failover re-launches one fragment slot gets before the "
    "broker gives up and degrades the query (the r9 partial-results "
    "fallback). Hedged duplicates do not count against this budget.",
)
define_flag(
    "hedged_requests",
    False,
    help_="Hedged fragment dispatch (vizier/broker.py; Dean & Barroso, "
    "'The Tail at Scale'): when a fragment is still pending past the "
    "hedge delay — the per-program-key fold-latency quantile from "
    "agent heartbeats (``hedge_quantile``), or ``hedge_delay_ms`` when "
    "set — the broker launches a duplicate attempt on another capable "
    "agent. First fragment_done wins; the loser is cancelled through "
    "the r9 abort path and its output is dropped by the same "
    "fragment-epoch dedup retries use. Requires fragment_failover.",
)
define_flag(
    "hedge_quantile",
    0.99,
    help_="Fold-latency quantile (from the r11 per-program-key "
    "heartbeat histograms) a pending fragment must exceed before a "
    "hedge launches. Only 0.5 and 0.99 are tracked; values >= 0.99 "
    "read p99, lower values p50.",
)
define_flag(
    "hedge_delay_ms",
    0.0,
    help_="Fixed hedge delay override in milliseconds. 0 derives the "
    "delay from the fold-latency view (no latency data for the "
    "fragment's program keys = no hedge).",
)
define_flag(
    "ring_replication_factor",
    1,
    help_="Resident-ring replication (serving/resident.py + "
    "vizier/agent.py): hot ring windows replicate to factor-1 follower "
    "agents over the existing codec'd wire (the encoded window payload "
    "is republished, follower decodes device-side), byte-accounted in "
    "the follower's ResidencyPool and advertised in heartbeat "
    "residency snapshots — so fragment failover lands on an agent "
    "whose HBM already holds the hot windows (wire ~ 0) instead of a "
    "cold re-stage. A lagging replica (bounded by the leader's "
    "advertised watermark) falls back to re-staging from the table "
    "store — bit-identical either way. 1 disables replication.",
)
define_flag(
    "residency_placement",
    False,
    help_="Admission-time placement plane (serving/placement.py + "
    "vizier/broker.py): before planning, score every live data-plane "
    "agent for the query's table span by heartbeat-advertised HBM "
    "residency (staged-cache tables + resident/replica rings), then "
    "the r11 fold-latency view, then WFQ-weighted load, and route the "
    "scan to the winner by narrowing the planner's agent->table view. "
    "Shares one scorer with r17 fragment failover. Decisions surface "
    "as broker_placement_decisions_total{outcome=} and the /statusz "
    "placement section. Off routes by the planner's static ownership "
    "view as before.",
)
define_flag(
    "ring_rebalance",
    False,
    help_="Adaptive replica-ring rebalancer (serving/placement.py): a "
    "broker loop drains per-table placement heat each interval and "
    "reassigns WHICH tables replicate to WHICH followers, skipping "
    "followers above ring_rebalance_high_pct of their heartbeat HBM "
    "budget. Assignments ride the ring_replica topic as "
    "ring_replica_assign messages; agents without an assignment keep "
    "the deterministic r17 leader-rank attachment. Every move lands on "
    "an actuation trail (statusz placement.rebalancer). Requires "
    "residency_placement for the heat signal.",
)
define_flag(
    "ring_rebalance_interval_s",
    1.0,
    help_="Seconds between rebalancer ticks. Each tick is a hold "
    "unless the placement-heat window since the last tick is non-empty.",
)
define_flag(
    "ring_rebalance_high_pct",
    0.9,
    help_="HBM rail for the rebalancer: followers whose heartbeat "
    "ResidencyPool reports used_bytes above this fraction of "
    "budget_bytes are skipped when assigning replica followers "
    "(budget 0 = unlimited = always eligible).",
)

# -- robustness (r10): acked delivery + cluster health plane -----------------
# (transport_ack_* / transport_window_block_s are declared next to their
# use in vizier/transport.py.)
define_flag(
    "health_plane",
    True,
    help_="Broker-side cluster health view (vizier/broker.py): agent "
    "heartbeats carry device-breaker state, staging depth, and fold "
    "latency; execute_script skips agents whose OPEN breaker matches the "
    "query's program shape at planning time (recorded in "
    "degraded.skipped with reason breaker_open) instead of discovering "
    "them sick mid-query. Half-open breakers plan normally.",
)

# -- materialized views (r20) ------------------------------------------------
define_flag(
    "materialized_views",
    False,
    help_="Incremental materialized-view plane (serving/views.py + "
    "vizier/broker.py): registered PxL aggregation scripts are "
    "maintained by folding only new-since-watermark rows into "
    "persisted partial-agg state (StateBatch codec, datastore-backed "
    "like SLO rules / the admission controller), and "
    "QueryBroker.execute_script answers view-matching queries (fold "
    "signature + normalized predicate digest) from the merged state "
    "BEFORE admission ever queues them — a view_hit rung above "
    "ring_hit on the placement ladder. Reads merge the carried state "
    "with a delta fold over the unflushed tail and finalize, "
    "bit-identical to folding from scratch; freshness is stamped on "
    "every served QueryResult. Off: the probe short-circuits to a "
    "single attribute check on the query path.",
)
define_flag(
    "view_refresh_interval_s",
    1.0,
    help_="Default maintenance cadence for registered views: each "
    "view's CronScript ticker folds the new-since-watermark rows into "
    "the carried StateBatch and persists state + watermark every this "
    "many seconds (per-view override at register()).",
)
define_flag(
    "view_max_staleness_s",
    30.0,
    help_="Stale-view rail: when a view's last successful maintenance "
    "is older than this (maintenance wedged, breaker open, agent "
    "restarted long ago), the probe reports a miss and the query "
    "falls through to normal admission + execution instead of paying "
    "an unbounded tail fold on the read path. 0 disables the rail.",
)
