"""Query broker: compile → distributed plan → launch → forward results.

Ref: src/vizier/services/query_broker/ — Server.ExecuteScript
(controllers/server.go:308), QueryExecutorImpl.Run (query_executor.go:166),
LaunchQuery publishing per-agent plans on NATS Agent/<id> topics
(launch_query.go:36-82), QueryResultForwarder matching agent result streams
to the client with timeouts/cancellation (query_result_forwarder.go:395,
502,571), and the heartbeat-expiry agent tracker (tracker/agents.go +
agent_topic_listener.go:41,322 — 1-minute expiry, scaled down here).
"""

from __future__ import annotations

import logging
import threading
import time
import uuid
from typing import Callable, Optional

from pixie_tpu.compiler import Compiler
from pixie_tpu.distributed import AgentInfo, DistributedPlanner, DistributedState
from pixie_tpu.engine import QueryResult
from pixie_tpu.exec import BridgeRouter
from pixie_tpu.plan.operators import BridgeSinkOp, MemorySourceOp
from pixie_tpu.plan.plan import Plan
from pixie_tpu.plan.program_key import fragment_program_key
from pixie_tpu.types import Relation
from pixie_tpu.vizier.bus import (
    MessageBus,
    agent_topic,
)
from pixie_tpu.utils import faults, flags, metrics_registry, trace
from pixie_tpu.vizier.agent import AGENT_STATUS_TOPIC, RESULTS_TOPIC_PREFIX


# ref: 1 minute (agent_topic_listener.go:41), scaled; env-overridable via
# PIXIE_TPU_AGENT_EXPIRY_S (read once at import).
AGENT_EXPIRY_S = flags.agent_expiry_s

_log = logging.getLogger("pixie_tpu.broker")

# Broker-side query counters on the shared registry so /metrics reflects
# them (r11 satellite — ad-hoc totals were invisible to the endpoint).
_M = metrics_registry()
_QUERIES = _M.counter(
    "broker_queries_total", "Queries executed through the broker."
)
_DEGRADED = _M.counter(
    "broker_degraded_queries_total",
    "Queries that returned a partial result with a degraded annotation.",
)
_FORWARD_DROPPED = _M.counter(
    "broker_forward_dropped_total",
    "Result messages dropped in the broker's forwarder (fault site "
    "broker.forward).",
)
_QUERY_SECONDS = _M.histogram(
    "broker_query_seconds",
    "End-to-end broker query latency, by tenant (r15: per-tenant SLO "
    "rules get native series; aggregate views read the label-merged "
    "distribution via Histogram.agg_quantile).",
)
_ALERTS_EMITTED = _M.counter(
    "broker_alert_events_total",
    "SLO alert events fanned out through the broker's alert listeners, "
    "by rule and state.",
)
_REOFFERS = _M.counter(
    "broker_launch_reoffers_total",
    "execute_fragment launches re-offered to an agent that re-registered "
    "while a launch was still unacknowledged (reconnect-gap hole, r12), "
    "by reason: 'reconnect' (same process, new connection) vs 'restart' "
    "(new process with durable identity, r14).",
)
_RETRIES = _M.counter(
    "broker_fragment_retries_total",
    "Fragments re-launched onto a surviving agent after their executing "
    "agent was lost mid-query (r17, flag fragment_failover), by reason: "
    "agent_lost | agent_error | restart_lost | forward_dropped.",
)
_HEDGES = _M.counter(
    "broker_hedged_fragments_total",
    "Duplicate fragment attempts launched because the original was "
    "still pending past the hedge delay (r17, flag hedged_requests).",
)
_HEDGE_BOTH = _M.counter(
    "broker_hedge_both_complete_total",
    "Hedge/retry attempts whose results arrived AFTER another attempt "
    "already won the slot — dropped by the fragment-epoch dedup (the "
    "wasted-work count; fault site hedge.both_complete forces the "
    "race deterministically).",
)
_RECOVERED_Q = _M.counter(
    "broker_recovered_queries_total",
    "Queries that completed with FULL results only because fragment "
    "failover retried or hedged at least one fragment (the degraded "
    "annotation these queries would have carried pre-r17 is replaced "
    "by a recovered annotation).",
)
_RECOVERY_SECONDS_H = _M.histogram(
    "broker_fragment_recovery_seconds",
    "Wall seconds from a fragment attempt's detected loss to the "
    "replacement attempt completing its slot (r17: what failover adds "
    "to a faulted query's latency).",
)
_RESTARTS = _M.counter(
    "broker_agent_restarts_total",
    "Register messages from a RESTARTED agent incarnation (r14: durable "
    "identity restored from its WAL, epoch bumped past the dead "
    "process's persisted counter) — distinct from plain reconnect "
    "re-registers.",
)


class AgentTracker:
    """Liveness + table topology + device health from register/heartbeat
    messages, keyed on ``agent_id`` with ONLY the latest registration
    epoch retained (r10 satellite): a reconnecting agent re-registers
    with a bumped epoch, and any straggler message from its superseded
    incarnation (an old connection's buffered heartbeat arriving late)
    is dropped instead of resurrecting pre-reconnect table/health
    state."""

    def __init__(self, bus: MessageBus):
        self._bus = bus
        self._sub = bus.subscribe(AGENT_STATUS_TOPIC)
        self._lock = threading.Lock()
        self._agents: dict[str, dict] = {}
        self._stop = threading.Event()
        # fn(agent_id, epoch, restarted) fired on every "register"
        # message (r12): the broker re-offers unacknowledged fragment
        # launches to an agent that re-registered after a reconnect gap
        # (or, r14, after a full process restart — restarted=True).
        self._register_listeners: list = []
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def add_register_listener(self, fn) -> None:
        with self._lock:
            self._register_listeners.append(fn)

    def _loop(self) -> None:
        while not self._stop.is_set():
            msg = self._sub.get(timeout=0.05)
            if msg is None:
                continue
            if msg.get("type") in ("register", "heartbeat"):
                epoch = msg.get("epoch", 0)
                # r14: a register from a RESTARTED incarnation (durable
                # identity, epoch continued past the dead process's
                # counter) supersedes the zombie entry like any higher
                # epoch, but is counted and surfaced separately so
                # operators can tell crash recovery from network flaps.
                restarted = bool(
                    msg["type"] == "register" and msg.get("restarted")
                )
                with self._lock:
                    cur = self._agents.get(msg["agent_id"])
                    if cur is not None and epoch < cur["epoch"]:
                        continue  # stale straggler from an old incarnation
                    self._agents[msg["agent_id"]] = {
                        "is_kelvin": msg["is_kelvin"],
                        "tables": frozenset(msg.get("tables", ())),
                        # r17: tables this agent can serve WITHOUT
                        # owning (replica rings / shared store) — never
                        # planned over, but failover and the no-owner
                        # planning fallback route here.
                        "replica_tables": frozenset(
                            msg.get("replica_tables", ())
                        ),
                        "last_seen": time.monotonic(),
                        "epoch": epoch,
                        "health": msg.get("health"),
                        "restarts": (
                            (cur.get("restarts", 0) if cur else 0)
                            + (1 if restarted else 0)
                        ),
                    }
                    listeners = (
                        list(self._register_listeners)
                        if msg["type"] == "register"
                        else ()
                    )
                if restarted:
                    _RESTARTS.inc(agent=msg["agent_id"])
                for fn in listeners:
                    try:
                        fn(msg["agent_id"], epoch, restarted)
                    except Exception:
                        _log.exception(
                            "register listener failed (ignored)"
                        )

    def planning_view(self) -> tuple[DistributedState, list[str]]:
        """(alive agents for planning, skipped agent ids) — query planning
        only covers agents within the heartbeat-expiry window (ref:
        agent_topic_listener expiry + prune_unavailable_sources_rule); the
        skipped list rides the query's degraded annotation so callers can
        see whose data the plan never covered (r9)."""
        now = time.monotonic()
        with self._lock:
            alive, skipped = {}, []
            for aid, a in self._agents.items():
                silent = now - a["last_seen"]
                if silent < AGENT_EXPIRY_S:
                    alive[aid] = a
                elif silent < 10 * AGENT_EXPIRY_S:
                    # Recently expired: keep the record (UNRESPONSIVE in
                    # the status UDTF) and report it skipped.
                    skipped.append(aid)
                # Long-silent agents are forgotten entirely.
            self._agents = {
                aid: a
                for aid, a in self._agents.items()
                if now - a["last_seen"] < 10 * AGENT_EXPIRY_S
            }
        state = DistributedState(
            agents=[
                AgentInfo(aid, a["tables"], a["is_kelvin"])
                for aid, a in sorted(alive.items())
            ]
        )
        return state, sorted(skipped)

    def distributed_state(self) -> DistributedState:
        return self.planning_view()[0]

    def expired_among(self, agent_ids) -> list[str]:
        """Subset of ``agent_ids`` whose heartbeat has expired — the
        broker polls this mid-query to detect agents dying while their
        fragments run (ref: the forwarder cancelling dead-agent streams,
        query_result_forwarder.go:395)."""
        now = time.monotonic()
        with self._lock:
            return sorted(
                aid
                for aid in agent_ids
                if aid not in self._agents
                or now - self._agents[aid]["last_seen"] >= AGENT_EXPIRY_S
            )

    def failover_view(self) -> list[dict]:
        """Alive agents with everything failover candidate selection
        needs (r17): owned tables, replica tables, role, and the latest
        heartbeat health (replica ring coverage/lag rides in
        health['replicas'])."""
        now = time.monotonic()
        with self._lock:
            return [
                {
                    "agent_id": aid,
                    "tables": frozenset(a["tables"]),
                    "replica_tables": frozenset(
                        a.get("replica_tables") or ()
                    ),
                    "is_kelvin": a["is_kelvin"],
                    "health": a.get("health"),
                }
                for aid, a in sorted(self._agents.items())
                if now - a["last_seen"] < AGENT_EXPIRY_S
            ]

    def health_view(self) -> dict[str, dict]:
        """Aggregated broker-side cluster health (r10): agent_id ->
        liveness + registration epoch + the latest device-health payload
        from its heartbeat (breaker state per program key, staging depth,
        last fold latency). Consumed by execute_script's breaker-aware
        planning and the health HTTP endpoint."""
        now = time.monotonic()
        with self._lock:
            return {
                aid: {
                    "alive": now - a["last_seen"] < AGENT_EXPIRY_S,
                    "epoch": a["epoch"],
                    "is_kelvin": a["is_kelvin"],
                    "health": a.get("health"),
                    # r14: observed crash-restart registers; the agent's
                    # own recovery stats (wal_replayed_frames,
                    # ring_restaged_windows, recovery_seconds) ride in
                    # health["recovery"].
                    "restarts": a.get("restarts", 0),
                }
                for aid, a in sorted(self._agents.items())
            }

    def open_breaker_keys(self) -> dict[str, frozenset]:
        """agent_id -> program keys with an OPEN device breaker (from the
        latest heartbeat). Half-open keys are absent: a half-open breaker
        admits its trial, so the planner schedules normally."""
        out = {}
        with self._lock:
            for aid, a in self._agents.items():
                health = a.get("health") or {}
                keys = health.get("breaker_open") or ()
                if keys:
                    out[aid] = frozenset(keys)
        return out

    def fold_latency_view(self) -> dict[str, dict]:
        """program_key -> {agent_id: {p50_ms, p99_ms, n}} from the latest
        heartbeats (r11): the per-program-key fold-latency histograms the
        device executors publish, aggregated for /statusz so operators see
        live per-phase percentiles without running a query."""
        out: dict[str, dict] = {}
        with self._lock:
            for aid, a in sorted(self._agents.items()):
                fl = (a.get("health") or {}).get("fold_latency") or {}
                for key, st in fl.items():
                    out.setdefault(key, {})[aid] = st
        return out

    def mesh_view(self) -> dict[str, dict]:
        """agent_id -> the executor's mesh-recovery section from its
        latest heartbeat (r23): current vs full geometry, degradation
        ladder, per-geometry breaker state, degrade/checkpoint/resume
        counters. Operators read this off /statusz to see which agents
        are running on a degraded mesh rung (and whether the full
        geometry's breaker is open, half-open, or recovered) without
        touching the agents."""
        out = {}
        with self._lock:
            for aid, a in sorted(self._agents.items()):
                mesh = (a.get("health") or {}).get("mesh")
                if mesh:
                    out[aid] = mesh
        return out

    def ingest_view(self) -> dict[str, dict]:
        """agent_id -> the ingest-plane section from its latest
        heartbeat (r24): per-source events fed, rows emitted, total
        drops, live trackers, buffered bytes, current shedding-ladder
        level, and open quarantine breakers. /statusz surfaces it so an
        operator sees WHICH hosts are shedding (and why) during an
        overload without scraping per-host /metrics."""
        out = {}
        with self._lock:
            for aid, a in sorted(self._agents.items()):
                ingest = (a.get("health") or {}).get("ingest")
                if ingest:
                    out[aid] = ingest
        return out

    def agents_snapshot(self) -> list[dict]:
        """Rows for the GetAgentStatus UDTF (ref: md_udtfs.h reads the
        agent manager's registry), plus r10 health-plane columns."""
        now = time.monotonic()
        with self._lock:
            return [
                {
                    "agent_id": aid,
                    "asid": i + 1,
                    "hostname": aid,
                    "agent_state": (
                        "AGENT_STATE_HEALTHY"
                        if now - a["last_seen"] < AGENT_EXPIRY_S
                        else "AGENT_STATE_UNRESPONSIVE"
                    ),
                    # ns SINCE the last heartbeat (elapsed duration), matching
                    # the reference's ns_since_last_heartbeat column
                    # (src/vizier/funcs/md_udtfs/md_udtfs_impl.h) and the
                    # standalone fallback in md_udtfs.py (ADVICE r3).
                    "last_heartbeat_ns": int((now - a["last_seen"]) * 1e9),
                    "kelvin": a["is_kelvin"],
                    "epoch": a["epoch"],
                    "restarts": a.get("restarts", 0),
                    "breaker_open": len(
                        (a.get("health") or {}).get("breaker_open") or ()
                    ),
                }
                for i, (aid, a) in enumerate(sorted(self._agents.items()))
            ]

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2)
        self._sub.unsubscribe()


class TrackerVizierCtx:
    """FunctionContext.vizier_ctx backed by the broker's agent tracker."""

    def __init__(self, tracker: AgentTracker):
        self._tracker = tracker

    def agents(self) -> list[dict]:
        return self._tracker.agents_snapshot()


class QueryBroker:
    def __init__(
        self,
        bus: MessageBus,
        router: BridgeRouter,
        registry=None,
        table_relations: Optional[dict[str, Relation]] = None,
        residency=None,
        staging_estimator=None,
    ):
        if registry is None:
            from pixie_tpu.udf.registry import default_registry

            registry = default_registry()
        self.bus = bus
        self.router = router
        self.registry = registry
        self.compiler = Compiler(registry)
        self.tracker = AgentTracker(bus)
        self.vizier_ctx = TrackerVizierCtx(self.tracker)
        # Schema authority: in the reference the broker gets schemas from
        # the metadata service; here the caller provides them (or agents'
        # heartbeats name tables and the caller maps relations).
        self.table_relations = dict(table_relations or {})
        self._health_srv = None
        # Pluggable OTel exporter for finished query traces (flag
        # trace_otel_export); callers set it to an OTLP/HTTP callable.
        self.otel_exporter = None
        # Serving front door (r12, flag serving_enabled): admission
        # control with per-tenant weighted fair queueing and — when the
        # embedder wires ``residency`` (a serving.ResidencyPool, e.g. the
        # in-process agents' device executor pool) — an HBM byte-budget
        # check before admitting.
        from pixie_tpu.serving.admission import AdmissionController

        self.residency = residency
        self.admission = AdmissionController(
            budget_fn=(
                residency.snapshot if residency is not None else None
            )
        )
        # r16: the shared-scan batching window is demand-gated on live
        # admission queue depth — a solo query on an idle broker no
        # longer sleeps shared_scan_window_ms. Registered/unregistered
        # with THIS broker's bound fn so a stopped broker never yanks a
        # newer one's wiring.
        from pixie_tpu.serving import shared_scan as _shared_scan

        self._queue_depth_fn = self.admission.queue_depth
        _shared_scan.set_queue_depth_fn(self._queue_depth_fn)
        # r16: closed-loop admission control (flag admission_controller)
        # — an SLO-window adapter on the cron runner actuating the
        # serving knobs from the r15 telemetry planes, within guard
        # rails. Explicit start via start_admission_controller() for
        # embedders that want their own datastore.
        self.admission_controller = None
        if flags.admission_controller:
            self.start_admission_controller()
        # r18: admission-time placement plane (flag residency_placement)
        # — score live agents by heartbeat-advertised HBM residency /
        # fold latency / WFQ load and route each query's scan to the
        # winner. Shares its scorer with the r17 failover ranking. The
        # companion ring rebalancer (flag ring_rebalance) drains the
        # plane's per-table heat and adapts replica-follower
        # assignments over the ring_replica topic.
        self.placement = None
        if flags.residency_placement:
            from pixie_tpu.serving.placement import PlacementPlane

            self.placement = PlacementPlane()
        self.ring_rebalancer = None
        if flags.ring_rebalance:
            self.start_ring_rebalancer()
        # r13 satellite: table_name -> estimated staging bytes (e.g.
        # serving.admission.make_store_estimator over the agents' table
        # store). With it, admission rejects a query whose staging
        # could NEVER fit the HBM budget before the doomed cold stage
        # starts, not only once pinned bytes already exceed budget.
        self.staging_estimator = staging_estimator
        # Unacknowledged fragment launches per agent (r12 reconnect-gap
        # fix): a launch published into an agent's reconnect window is
        # silently lost by an at-most-once bus; when the agent
        # re-registers, every still-pending launch for it is re-offered
        # (agents dedup by query_id, so a double delivery is harmless).
        self._launch_lock = threading.Lock()
        self._inflight_launches: dict[str, dict[str, dict]] = {}
        self.tracker.add_register_listener(self._reoffer_launches)
        # SLO/alert plane (r15, vizier/slo.py): an attached SLOManager
        # (``broker.slo``) feeds /alertz; alert listeners receive every
        # rule transition as a structured event (same shape family as
        # the r10 on_event degradation events).
        self.slo = None
        self._alert_listeners: list = []
        # r20: materialized-view plane (flag materialized_views) —
        # registered aggregation scripts maintained as persisted
        # partial-agg state; matching queries are served from the
        # merged state BEFORE admission. Explicit start via
        # start_views() (needs a table store to fold against).
        self.views = None

    def start_admission_controller(self, datastore=None):
        """Attach the r16 closed-loop admission controller
        (serving/controller.py): persisted as a CronScript on its own
        runner (restart survival like SLO rules), reading the broker's
        admission/residency planes and actuating the serving flags
        within guard rails. Idempotent; returns the loop."""
        if self.admission_controller is not None:
            return self.admission_controller
        from pixie_tpu.serving.controller import AdmissionControlLoop

        self.admission_controller = AdmissionControlLoop(
            residency_fn=(
                self.residency.snapshot
                if self.residency is not None
                else None
            ),
            queue_depth_fn=self.admission.queue_depth,
        ).attach(self, datastore=datastore)
        return self.admission_controller

    def start_ring_rebalancer(self, interval_s=None):
        """Attach the r18 adaptive replica-ring rebalancer
        (serving/placement.py): drains the placement plane's per-table
        heat each interval and reassigns replica followers over the
        ring_replica topic, railed by heartbeat HBM budgets. Creates
        the placement plane if routing isn't already on (the heat
        window then only fills once placement routing runs, so ticks
        hold). Idempotent; returns the rebalancer."""
        if self.ring_rebalancer is not None:
            return self.ring_rebalancer
        from pixie_tpu.serving.placement import PlacementPlane, RingRebalancer
        from pixie_tpu.vizier.agent import RING_REPLICA_TOPIC

        if self.placement is None:
            self.placement = PlacementPlane()
        self.ring_rebalancer = RingRebalancer(
            publish=lambda msg: self.bus.publish(RING_REPLICA_TOPIC, msg),
            view_fn=self.tracker.failover_view,
            heat_fn=self.placement.drain_heat,
        )
        self.ring_rebalancer.start(interval_s)
        return self.ring_rebalancer

    def start_views(self, table_store, datastore=None):
        """Attach the r20 materialized-view plane (serving/views.py):
        view definitions persist as CronScripts in their own keyspace
        (``/view_scripts/``) on their own runner — restart-surviving
        like the r15 SLO rules and the r16 controller — and carried
        partial-agg state persists under ``/view_state/``, so a
        recovered broker's first read folds only the unflushed tail.
        Idempotent; returns the registry."""
        if self.views is not None:
            return self.views
        from pixie_tpu.serving.views import ViewRegistry

        self.views = ViewRegistry(
            self, table_store, datastore=datastore
        ).attach()
        return self.views

    # -- SLO alert fan-out (r15) --------------------------------------------
    def add_alert_listener(self, fn) -> None:
        """Register ``fn(event: dict)`` for SLO alert transitions
        ({"type": "slo_alert", "rule", "state", "severity", "value",
        "threshold", "tenant", ...}). Exceptions are logged and
        swallowed — alerting must never take the broker down."""
        self._alert_listeners.append(fn)

    def emit_alert(self, event: dict) -> None:
        """Fan a structured alert event out to every listener (called by
        the attached SLOManager on each rule transition)."""
        _ALERTS_EMITTED.inc(
            rule=event.get("rule", ""), state=event.get("state", "")
        )
        for fn in list(self._alert_listeners):
            try:
                fn(dict(event))
            except Exception:
                _log.exception("alert listener failed (ignored)")

    def start_health_server(self, host: str = "127.0.0.1", port: int = 0):
        """Expose the aggregated cluster health view over HTTP (r10):
        /statusz carries ``cluster_health`` (per-agent breaker state,
        staging depth, fold latency, liveness) and /agentz the
        GetAgentStatus-shaped snapshot. Returns the HealthServer (its
        ``.address`` is the bound (host, port))."""
        from pixie_tpu.vizier.health import serve_health

        self._health_srv = serve_health(
            "broker",
            status_fn=lambda: {
                "agents": self.tracker.agents_snapshot(),
                "cluster_health": self.tracker.health_view(),
                # Live per-program-key fold-latency percentiles from the
                # agents' heartbeat-carried histograms (r11).
                "fold_latency": self.tracker.fold_latency_view(),
                # Serving plane (r12): admission queue depth / active /
                # per-tenant virtual clocks, and (when wired) the HBM
                # residency pool's byte accounting.
                "admission": self.admission.snapshot(),
                # r16: the closed-loop controller's live knobs, rails,
                # and recent actuation trail.
                "admission_controller": (
                    self.admission_controller.status()
                    if self.admission_controller is not None
                    else None
                ),
                "residency": (
                    self.residency.snapshot()
                    if self.residency is not None
                    else None
                ),
                # r18: placement decisions/hit-rate/per-agent shares,
                # plus the ring rebalancer's assignments and actuation
                # trail.
                "placement": (
                    {
                        **self.placement.status(),
                        "rebalancer": (
                            self.ring_rebalancer.status()
                            if self.ring_rebalancer is not None
                            else None
                        ),
                    }
                    if self.placement is not None
                    else None
                ),
                # r20: materialized-view plane — per-view watermark,
                # staleness, hit counts, breaker state.
                "views": (
                    self.views.status()
                    if self.views is not None
                    else None
                ),
                # r23: per-agent mesh-recovery plane — degraded
                # geometry rungs, per-geometry breaker state, and
                # checkpoint/resume counters from executor heartbeats.
                "mesh": self.tracker.mesh_view(),
                # r24: per-agent ingest plane — events/rows/drops,
                # tracker and buffer gauges, shedding-ladder level, and
                # quarantine breakers from PEM heartbeats.
                "ingest": self.tracker.ingest_view(),
            },
            extra_routes={
                "/agentz": lambda: self.tracker.agents_snapshot(),
                # r20: the view plane's own route (empty shell when no
                # registry is attached, so the route always exists).
                "/viewz": lambda: (
                    self.views.status()
                    if self.views is not None
                    else {"enabled": False, "views": [],
                          "hits": 0, "misses": 0, "hit_rate": 0.0}
                ),
                # r15: live SLO rule + alert status (empty shell when no
                # SLOManager is attached, so the route always exists).
                "/alertz": lambda: (
                    self.slo.status()
                    if self.slo is not None
                    else {"rules": [], "active": [], "recent": []}
                ),
            },
            host=host,
            port=port,
        )
        return self._health_srv

    def _plan_around_open_breakers(
        self, planner, logical, plan, state
    ) -> tuple[Plan, list[str]]:
        """Health-plane planning step (r10): if any data-holding agent's
        heartbeat reports an OPEN device breaker for the exact program
        key of a fragment this plan assigns to it, replan without that
        agent — it would be discovered sick mid-query anyway (host
        fallback at best, breaker churn at worst). Returns the plan to
        run plus the proactively-skipped agent ids. Falls back to the
        original plan when every capable agent is sick (degraded data
        beats no data) or the replan is impossible."""
        open_keys = self.tracker.open_breaker_keys()
        if not open_keys:
            return plan, []
        kelvins = {a.agent_id for a in state.agents if a.is_kelvin}
        sick = set()
        for frag in plan.fragments:
            inst = plan.executing_instance[frag.fragment_id]
            if inst in open_keys and inst not in kelvins:
                if fragment_program_key(frag) in open_keys[inst]:
                    sick.add(inst)
        if not sick:
            return plan, []
        healthy = DistributedState(
            agents=[a for a in state.agents if a.agent_id not in sick]
        )
        try:
            replanned = planner.plan(logical, healthy)
        except ValueError:
            # No healthy agent holds the needed tables: run the original
            # plan rather than fail the query outright.
            _log.warning(
                "health plane: every capable agent has an open breaker "
                "for this query shape (%s); planning over them anyway",
                sorted(sick),
            )
            return plan, []
        return replanned, sorted(sick)

    # -- transparent fragment failover (r17) ---------------------------------
    @staticmethod
    def _plan_tables(frag_or_plan) -> frozenset:
        """Table names a fragment (or sub-plan) scans — what a failover
        replacement must be able to serve."""
        frags = getattr(frag_or_plan, "fragments", None) or [frag_or_plan]
        return frozenset(
            f.node(nid).table_name
            for f in frags
            for nid in f.nodes()
            if isinstance(f.node(nid), MemorySourceOp)
        )

    def _failover_candidate(
        self,
        needed: frozenset,
        tried: set,
        prefer_kelvin: bool,
        exclude: "tuple | set" = (),
    ) -> Optional[str]:
        """The best surviving agent to re-run a lost fragment on: it
        must cover every scanned table (owned or replica); among
        eligible agents, prefer the matching role, then owners, then
        the agent whose replica rings already hold the MOST windows of
        the needed tables with the least lag (wire ~ 0 on landing),
        then stable name order. When every capable agent has already
        been tried (retry budget permitting), a still-alive
        previously-tried agent is eligible again — transient faults
        (a dropped forwarder frame, one injected error) don't condemn
        an agent — except the one that just failed (``exclude``)."""
        pick = self._best_failover_candidate(
            needed, set(tried) | set(exclude), prefer_kelvin
        )
        if pick is None and tried:
            pick = self._best_failover_candidate(
                needed, set(exclude), prefer_kelvin
            )
        return pick

    def _best_failover_candidate(
        self, needed: frozenset, skip: set, prefer_kelvin: bool
    ) -> Optional[str]:
        # r18: failover and admission-time placement share one scorer
        # (serving/placement.py) — the rank tuple is the r17 one:
        # role match, ownership, replica warmth, lag, name.
        from pixie_tpu.serving.placement import best_failover_candidate

        return best_failover_candidate(
            self.tracker.failover_view(), needed, skip, prefer_kelvin
        )

    def _hedge_delay_s(self, sub_plan: Plan) -> Optional[float]:
        """How long a fragment may stay pending before a hedge launches:
        ``hedge_delay_ms`` when set, else the ``hedge_quantile`` of the
        per-program-key fold-latency view from agent heartbeats (r11).
        None = no data, no hedge (hedging on a guess just doubles
        load)."""
        ms = float(flags.hedge_delay_ms)
        if ms > 0:
            return ms / 1e3
        view = self.tracker.fold_latency_view()
        if not view:
            return None
        q = "p99_ms" if float(flags.hedge_quantile) >= 0.99 else "p50_ms"
        keys = [fragment_program_key(frag) for frag in sub_plan.fragments]
        vals = []
        for pk in keys:
            for st in view.get(pk, {}).values():
                v = st.get(q)
                if v:
                    vals.append(float(v))
        return max(vals) / 1e3 if vals else None

    def _plan_with_replica_fallback(self, planner, logical, state):
        """Distributed planning, with a failover-mode fallback: when NO
        alive agent owns the scanned tables (the owner died between
        queries), plan over ONE replica agent that covers them — its
        shared-store/replicated-ring data serves the scan, so the query
        runs instead of failing with 'no agent holds tables'. Exactly
        one replica is promoted (promoting several would double-count
        the un-sharded data)."""
        try:
            return planner.plan(logical, state), None
        except ValueError:
            if not flags.fragment_failover:
                raise
            needed = self._plan_tables(logical.fragments[0])
            pick = self._failover_candidate(needed, set(), False)
            if pick is None:
                raise
            promoted = DistributedState(
                agents=[
                    AgentInfo(
                        a.agent_id,
                        frozenset(a.tables) | needed
                        if a.agent_id == pick
                        else a.tables,
                        a.is_kelvin,
                    )
                    for a in state.agents
                ]
            )
            _log.info(
                "failover planning: no alive owner for %s; promoting "
                "replica agent %s", sorted(needed), pick,
            )
            return planner.plan(logical, promoted), pick

    def _reoffer_launches(
        self, agent_id: str, epoch: int, restarted: bool = False
    ) -> None:
        """Register-listener (r12): an agent re-registering while the
        broker still holds unacknowledged launches for it lost those
        publishes in its reconnect gap (the bus is at-most-once to
        CURRENT subscribers) — re-offer them. Agents dedup by query_id,
        so the common both-delivered case is harmless. A RESTARTED
        incarnation (r14) gets the same re-offer, but its durable query
        markers decide the outcome: ``done`` → drop (the WAL replay
        already completed the query), ``started`` → structured refusal
        (partial output may be applied), unseen → execute normally."""
        with self._launch_lock:
            msgs = list(self._inflight_launches.get(agent_id, {}).values())
        reason = "restart" if restarted else "reconnect"
        for msg in msgs:
            _REOFFERS.inc(reason=reason)
            _log.info(
                "re-offering query %s launch to re-registered agent %s "
                "(epoch %d, %s)",
                msg.get("query_id"), agent_id, epoch, reason,
            )
            self.bus.publish(agent_topic(agent_id), msg)

    def _launch_done(self, agent_id: str, query_id: str) -> None:
        with self._launch_lock:
            self._inflight_launches.get(agent_id, {}).pop(query_id, None)

    def _estimate_staging(self, query: str) -> int:
        """Sum the staging-bytes estimates of every table the script
        names (syntactic: px.DataFrame(table='...') references — the
        estimate gates admission, it does not need plan precision).
        Returns 0 without an estimator: the check disables cleanly."""
        if self.staging_estimator is None:
            return 0
        import re

        total = 0
        for name in set(
            re.findall(r"table\s*=\s*['\"]([^'\"]+)['\"]", query)
        ):
            try:
                total += int(self.staging_estimator(name) or 0)
            except Exception:
                pass  # advisory: estimation must never fail a query
        return total

    def execute_script(
        self,
        query: str,
        timeout_s: float = 30.0,
        now_ns: Optional[int] = None,
        script_args: Optional[dict] = None,
        analyze: bool = False,
        exec_funcs=None,
        on_batch=None,
        on_event: Optional[Callable[[str, dict], None]] = None,
        tenant: str = "default",
    ) -> QueryResult:
        """ExecuteScript front door. With ``flags.serving_enabled`` the
        query first passes admission control (r12): a concurrency limit
        with per-tenant weighted fair queueing (``tenant`` is the WFQ
        key) and an HBM byte-budget check — on overload it raises a
        structured ``AdmissionRejected`` instead of queueing without
        bound. Flag off: straight through, the pre-r12 behavior.

        r20: with ``flags.materialized_views`` and an attached view
        plane, plain queries (no args/exec_funcs/analyze/streaming)
        probe the ViewRegistry FIRST — a fresh matching view answers
        from its merged partial-agg state before admission ever queues
        the query (``view_hit``, the top rung of the placement
        ladder)."""
        if (
            self.views is not None
            and flags.materialized_views
            and not script_args
            and not exec_funcs
            and not analyze
            and on_batch is None
        ):
            served = self.views.try_serve(query, tenant=tenant)
            if served is not None:
                if self.placement is not None:
                    self.placement.record_view_hit()
                return served
        if not flags.serving_enabled:
            # Tenant still threads through (r15): attribution and the
            # per-tenant serving metrics don't require admission control.
            return self._execute_script_inner(
                query, timeout_s, now_ns, script_args, analyze,
                exec_funcs, on_batch, on_event, tenant=tenant,
            )
        # may raise AdmissionRejected
        est_bytes = self._estimate_staging(query)
        ticket = self.admission.acquire(tenant, estimated_bytes=est_bytes)
        try:
            return self._execute_script_inner(
                query, timeout_s, now_ns, script_args, analyze,
                exec_funcs, on_batch, on_event,
                tenant=tenant, admission_wait_s=ticket.waited_s,
            )
        finally:
            ticket.release()

    def _execute_script_inner(
        self,
        query: str,
        timeout_s: float = 30.0,
        now_ns: Optional[int] = None,
        script_args: Optional[dict] = None,
        analyze: bool = False,
        exec_funcs=None,
        on_batch=None,
        on_event: Optional[Callable[[str, dict], None]] = None,
        tenant: Optional[str] = None,
        admission_wait_s: float = 0.0,
    ) -> QueryResult:
        """The ExecuteScript path (server.go:308 → launch_query.go:36).

        Flow control (ref: query_result_forwarder.go:502,571): the result
        subscription is bounded (flags.broker_max_pending); agents
        publishing into a full queue block up to the publish timeout, so a
        slow consumer backpressures producers instead of growing broker
        memory. Pass ``on_batch(table_name, row_batch)`` to stream batches
        to the consumer as they arrive instead of accumulating them.

        Graceful degradation (r9; ref: query_result_forwarder.go:395's
        partial forwarding with per-agent annotations): with
        ``flags.partial_results`` on, an agent that errors, misses the
        deadline, or stops heartbeating mid-query no longer fails the
        whole query — the broker unregisters the dead agent's bridges (so
        merge fragments finalize with the input they have), keeps the rows
        it received, and returns them with a structured
        ``QueryResult.degraded`` annotation. Flag off restores the r8
        raise-on-failure behavior.

        Streaming degradation events (r10): pass ``on_event(query_id,
        event)`` to learn about mid-query degradation INLINE instead of
        only from the final annotation — it fires when an agent is
        skipped at planning ({"type": "agent_skipped", "agent_id",
        "reason"}), lost ({"type": "agent_lost", "agent_id", "error"}),
        timed out ({"type": "agent_timeout", "agent_id"}), or errors
        ({"type": "agent_error", "agent_id", "error", "error_kind"}) —
        the same entries the final annotation aggregates. Exceptions from
        the callback are logged and swallowed; the final annotation is
        unchanged.

        Health-plane planning (r10, flag ``health_plane``): agents whose
        heartbeats report an OPEN device breaker for this query's program
        shape are skipped proactively at planning time and recorded in
        ``degraded.skipped`` with reason ``breaker_open`` — instead of
        being discovered sick mid-query. Half-open breakers plan
        normally (they admit their trial)."""
        qid = str(uuid.uuid4())
        _QUERIES.inc()
        # The query_id is the trace_id (utils/trace.py): spans, inline
        # degradation events, and the degraded annotation join on it.
        root_attrs = {"query_bytes": len(query)}
        if tenant is not None:
            # Admission plane (r12): who the query ran as and how long
            # it queued, joinable with the admission_wait_seconds
            # histogram on /metrics.
            root_attrs["tenant"] = tenant
            root_attrs["admission_wait_s"] = round(admission_wait_s, 6)
        root = trace.begin(
            "query",
            trace_id=qid,
            parent_id="",
            instance="broker",
            attrs=root_attrs,
        )
        root_span_id = root.span_id if root is not None else ""

        def emit(event: dict) -> None:
            if on_event is None:
                return
            try:
                # trace_id-stamped (r11 satellite): inline events and the
                # query's spans are joinable on the same key.
                on_event(qid, {"trace_id": qid, **event})
            except Exception:
                _log.exception("on_event callback failed (ignored)")
        t0 = time.perf_counter_ns()
        # r15: broker-side CPU (compile + plan) is attributed to the
        # query/tenant so host-profiler samples of this thread label
        # themselves; the forwarding loop below mostly blocks and the
        # agents attribute their own execution.
        with trace.attribution(
            qid, tenant or "default", "broker"
        ), trace.span(
            "compile", trace_id=qid, parent_id=root_span_id,
            instance="broker",
        ):
            logical = self.compiler.compile(
                query,
                self.table_relations,
                now_ns=now_ns,
                script_args=script_args,
                query_id=qid,
                exec_funcs=exec_funcs,
            )
        # Plan only over agents inside the heartbeat-expiry window; the
        # skipped list rides the degraded annotation.
        with trace.attribution(
            qid, tenant or "default", "broker"
        ), trace.span(
            "plan", trace_id=qid, parent_id=root_span_id, instance="broker"
        ) as plan_span:
            state, expired_agents = self.tracker.planning_view()
            planner = DistributedPlanner(self.registry, self.table_relations)
            # r18: admission-time placement — route the scan to the
            # agent whose HBM already holds the span (or the warmest
            # fallback) by narrowing the planner's agent->table view to
            # the pick. decide() is pure; commit() only fires once the
            # placed plan actually succeeds, so a planner refusal falls
            # through to the normal path without polluting metrics.
            plan = None
            promoted_replica = None
            placed_agent = None
            placement_outcome = None
            if self.placement is not None:
                needed = self._plan_tables(logical.fragments[0])
                pick, outcome = self.placement.decide(
                    self.tracker.failover_view(),
                    needed,
                    fold_latency=self.tracker.fold_latency_view(),
                    estimated_bytes=self._estimate_staging(query),
                )
                if pick is None and outcome == "mesh_fold":
                    # r21: the span's estimated staging exceeds every
                    # agent's HBM budget — don't force a single-agent
                    # pick; plan over the UNMODIFIED state so fragments
                    # span the fleet and per-agent folds stay inside
                    # their budgets. Commit under the "__mesh__" pseudo
                    # agent (load/inflight accounting + the outcome
                    # counter; affinity on it never matches a real pick).
                    try:
                        plan = planner.plan(logical, state)
                    except ValueError:
                        plan = None
                    if plan is not None:
                        placed_agent = "__mesh__"
                        placement_outcome = "mesh_fold"
                        self.placement.commit(
                            "__mesh__",
                            "mesh_fold",
                            needed,
                            weight=self.admission._weight(tenant or "default"),
                        )
                elif pick is not None:
                    placed_state = DistributedState(
                        agents=[
                            AgentInfo(
                                a.agent_id,
                                frozenset(a.tables) | needed
                                if a.agent_id == pick
                                else (
                                    a.tables
                                    if a.is_kelvin
                                    else frozenset(a.tables) - needed
                                ),
                                a.is_kelvin,
                            )
                            for a in state.agents
                        ]
                    )
                    try:
                        plan = planner.plan(logical, placed_state)
                    except ValueError:
                        plan = None
                    if plan is not None:
                        placed_agent, placement_outcome = pick, outcome
                        self.placement.commit(
                            pick,
                            outcome,
                            needed,
                            weight=self.admission._weight(tenant or "default"),
                        )
            if plan is None:
                # r17: with failover on, a dead owner's tables can be
                # served by a promoted replica agent instead of failing
                # the plan.
                plan, promoted_replica = self._plan_with_replica_fallback(
                    planner, logical, state
                )
            # Health plane: route around agents whose device breaker is
            # open for this query's program shape.
            breaker_skipped: list[str] = []
            if flags.health_plane:
                plan, breaker_skipped = self._plan_around_open_breakers(
                    planner, logical, plan, state
                )
            plan_span.set(
                fragments=len(plan.fragments),
                agents=len({
                    plan.executing_instance[f.fragment_id]
                    for f in plan.fragments
                }),
                **(
                    {"placed": placed_agent, "placement": placement_outcome}
                    if placed_agent is not None
                    else {}
                ),
            )
        if promoted_replica:
            # r17: a promoted replica COVERS the data the dead owner(s)
            # held — the plan scans every table the query needs, from an
            # agent advertising full replica coverage, so the expired
            # owners' data is NOT missing from this result. Suppress
            # their skip entries: the query is complete and must carry a
            # recovered annotation, not a degraded one. (Tables an
            # expired agent owned that this query never scans are
            # irrelevant to this result's completeness.)
            expired_agents = []
        skipped = [
            {"agent_id": aid, "reason": "heartbeat_expired"}
            for aid in expired_agents
        ] + [
            {"agent_id": aid, "reason": "breaker_open"}
            for aid in breaker_skipped
        ]
        skipped_agents = sorted(expired_agents + breaker_skipped)
        for entry in skipped:
            emit({"type": "agent_skipped", **entry})
        if promoted_replica:
            # r17: no alive owner held the scanned tables — a replica
            # agent was promoted at planning time.
            emit({
                "type": "replica_promoted", "agent_id": promoted_replica,
            })
        if placed_agent is not None:
            emit({
                "type": "query_placed",
                "agent_id": placed_agent,
                "outcome": placement_outcome,
            })
        compile_ns = time.perf_counter_ns() - t0

        # The broker's deadline is also the propagated per-query deadline:
        # every fragment aborts at (about) the same wall-clock moment.
        if flags.query_deadline_s > 0:
            timeout_s = min(timeout_s, flags.query_deadline_s)

        # Central bridge-producer registration over the shared router,
        # remembering which instance feeds which bridges so a dead agent's
        # producers can be unregistered mid-query.
        bridges_by_instance: dict[str, list[str]] = {}
        for frag in plan.fragments:
            inst = plan.executing_instance[frag.fragment_id]
            for nid in frag.nodes():
                op = frag.node(nid)
                if isinstance(op, BridgeSinkOp):
                    self.router.register_producer(qid, op.bridge_id)
                    bridges_by_instance.setdefault(inst, []).append(
                        op.bridge_id
                    )

        results_sub = self.bus.subscribe(
            RESULTS_TOPIC_PREFIX + qid, maxsize=flags.broker_max_pending
        )
        # Launch per-agent plans (launch_query.go:36-82).
        by_instance: dict[str, Plan] = {}
        for frag in plan.fragments:
            inst = plan.executing_instance[frag.fragment_id]
            sub = by_instance.setdefault(inst, Plan(qid))
            sub.fragments.append(frag)
            sub.executing_instance[frag.fragment_id] = inst
        # r17 failover bookkeeping: each original instance is a SLOT
        # (stable across retries) whose live attempts carry result
        # epochs; exactly one attempt's output is ever applied.
        failover = flags.fragment_failover
        hedging = failover and flags.hedged_requests
        kelvin_ids = {a.agent_id for a in state.agents if a.is_kelvin}
        slots: dict[str, dict] = {}
        t1 = time.perf_counter_ns()
        for inst, sub_plan in by_instance.items():
            msg = {
                "type": "execute_fragment",
                "query_id": qid,
                "plan": sub_plan,
                "analyze": analyze,
                "deadline_s": timeout_s,
                # Trace-context propagation (Dapper): the agent's
                # execute span parents to the broker's root span.
                "trace": {"trace_id": qid, "span_id": root_span_id},
                # Attribution propagation (r15): the agent labels its
                # execution threads (and their workers) with the tenant.
                "tenant": tenant or "default",
            }
            if failover:
                msg["slot"] = inst
                msg["result_epoch"] = 1
                slots[inst] = {
                    "plan": sub_plan,
                    "analyze": analyze,
                    "bridges": list(bridges_by_instance.get(inst, ())),
                    "needed_tables": self._plan_tables(sub_plan),
                    "is_kelvin": inst in kelvin_ids,
                    "live": {inst: 1},
                    "epoch": 1,
                    "done": False,
                    "tried": {inst},
                    "bufs": {(inst, 1): []},
                    "retried": [],
                    "retries": 0,
                    "hedge": None,
                    "hedge_at": None,
                    "lost_at": None,
                }
                for bid in slots[inst]["bridges"]:
                    self.router.authorize_producer(qid, bid, inst, 1)
            # Track BEFORE publishing (r12): if the agent re-registers
            # between our publish and its subscribe, the register
            # listener re-offers this launch instead of losing it to
            # the reconnect gap until the reaper degrades the query.
            with self._launch_lock:
                self._inflight_launches.setdefault(inst, {})[qid] = msg
            self.bus.publish(agent_topic(inst), msg)
        if hedging:
            now = time.monotonic()
            for st in slots.values():
                d = self._hedge_delay_s(st["plan"])
                st["hedge_at"] = now + d if d is not None else None

        # Forward results (query_result_forwarder.go:502,571).
        partial_ok = flags.partial_results
        tables: dict[str, list] = {}
        exec_stats: dict[str, dict] = {}
        pending: set = set(by_instance)
        deadline = time.monotonic() + timeout_s
        agent_errors: dict[str, str] = {}
        lost_agents: list[str] = []
        timed_out_agents: list[str] = []
        forward_dropped = 0
        # Spans shipped back by agents on fragment_done/fragment_error,
        # keyed by span_id: in-process agents share this module's buffer,
        # so the final merge dedups instead of double-counting.
        agent_spans: dict[str, dict] = {}
        # r15: forwarding (receiving/relaying this query's result
        # batches on the caller's thread) is per-query work too.
        fwd_attr = trace.attribution(qid, tenant or "default", "forward")
        fwd_attr.__enter__()

        # -- r17 failover machinery (no-ops when the flag is off) ------------
        def _revoke_attempt(st, slot_id, aid, ep):
            st["bufs"].pop((aid, ep), None)
            for bid in st["bridges"]:
                self.router.revoke_producer(qid, bid, slot_id, ep)

        def _launch_attempt(slot_id, st, aid, remaining):
            st["epoch"] += 1
            ep = st["epoch"]
            st["live"][aid] = ep
            st["tried"].add(aid)
            st["bufs"][(aid, ep)] = []
            for bid in st["bridges"]:
                self.router.authorize_producer(qid, bid, slot_id, ep)
            msg2 = {
                "type": "execute_fragment",
                "query_id": qid,
                "plan": st["plan"],
                "analyze": st["analyze"],
                "deadline_s": max(remaining, 0.1),
                "trace": {"trace_id": qid, "span_id": root_span_id},
                "tenant": tenant or "default",
                "slot": slot_id,
                "result_epoch": ep,
            }
            with self._launch_lock:
                self._inflight_launches.setdefault(aid, {})[qid] = msg2
            self.bus.publish(agent_topic(aid), msg2)
            return ep

        def _try_failover(slot_id, st, failed_agent, reason) -> bool:
            remaining = deadline - time.monotonic()
            if (
                st["retries"] >= max(int(flags.fragment_max_retries), 0)
                or remaining <= 0.05
            ):
                return False
            cand = self._failover_candidate(
                st["needed_tables"], st["tried"], st["is_kelvin"],
                exclude={failed_agent},
            )
            if cand is None:
                return False
            st["retries"] += 1
            ep = _launch_attempt(slot_id, st, cand, remaining)
            _RETRIES.inc(reason=reason)
            entry = {
                "slot": slot_id,
                "from": failed_agent,
                "to": cand,
                "reason": reason,
                "epoch": ep,
            }
            st["retried"].append(entry)
            emit({"type": "fragment_retry", **entry})
            if trace.ACTIVE:
                trace.record(
                    "broker.fragment_retry", 0, trace_id=qid,
                    parent_id=root_span_id, instance="broker",
                    attrs=entry,
                )
            _log.info(
                "query %s: fragment slot %s lost on %s (%s); retrying "
                "on %s at epoch %d",
                qid, slot_id, failed_agent, reason, cand, ep,
            )
            return True

        def _attempt_lost(slot_id, st, aid, ep, reason, error, kind="error"):
            """One live attempt died: revoke its bridge authorization
            and discard its buffered output (exactly-once: a dead
            attempt contributes NOTHING). A live hedge sibling keeps
            the slot; else retry; else give the slot up exactly the way
            r9 would have degraded it. Returns True while the slot is
            still going to complete (sibling or retry)."""
            st["live"].pop(aid, None)
            _revoke_attempt(st, slot_id, aid, ep)
            if st["lost_at"] is None:
                st["lost_at"] = time.monotonic()
            if st["live"]:
                return True  # a hedge sibling still owns the slot
            if _try_failover(slot_id, st, aid, reason):
                return True
            pending.discard(slot_id)
            agent_errors.setdefault(aid, error)
            if reason == "agent_lost":
                lost_agents.append(aid)
                emit({"type": "agent_lost", "agent_id": aid,
                      "error": error})
            else:
                if kind == "deadline":
                    timed_out_agents.append(aid)
                emit({
                    "type": "agent_error", "agent_id": aid,
                    "error": error, "error_kind": kind,
                })
            for bid in st["bridges"]:
                self.router.unregister_producer(qid, bid)
            return False

        def _maybe_hedge():
            now = time.monotonic()
            for s2 in list(pending):
                st = slots[s2]
                if (
                    st["hedge_at"] is None
                    or now < st["hedge_at"]
                    or len(st["live"]) != 1
                    or st["hedge"] is not None
                ):
                    continue
                (orig_aid,) = st["live"]
                cand = self._failover_candidate(
                    st["needed_tables"], st["tried"], st["is_kelvin"],
                    exclude=set(st["live"]),
                )
                if cand is None:
                    st["hedge_at"] = None  # nobody to hedge onto
                    continue
                _launch_attempt(s2, st, cand, deadline - now)
                _HEDGES.inc()
                st["hedge"] = {
                    "slot": s2, "original": orig_aid,
                    "duplicate": cand, "winner": None,
                }
                emit({
                    "type": "fragment_hedged", "slot": s2,
                    "original": orig_aid, "duplicate": cand,
                })
                if trace.ACTIVE:
                    trace.record(
                        "broker.fragment_hedged", 0, trace_id=qid,
                        parent_id=root_span_id, instance="broker",
                        attrs={"slot": s2, "duplicate": cand},
                    )

        try:
            while pending:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    if failover:
                        timed_out_agents = sorted(
                            {
                                aid
                                for s in pending
                                for aid in slots[s]["live"]
                            }
                            | {s for s in pending if not slots[s]["live"]}
                        )
                    else:
                        timed_out_agents = sorted(pending)
                    if not partial_ok:
                        raise TimeoutError(
                            f"query {qid}: {len(pending)} agents still "
                            f"running ({timed_out_agents})"
                        )
                    for inst in timed_out_agents:
                        agent_errors.setdefault(
                            inst, "deadline exceeded: no result"
                        )
                        emit({"type": "agent_timeout", "agent_id": inst})
                    break
                msg = results_sub.get(timeout=min(remaining, 0.1))
                if msg is None:
                    # Reap agents that stopped heartbeating mid-query:
                    # with failover, their attempts retry onto survivors;
                    # otherwise release their bridges so merge fragments
                    # finalize with partial input instead of stalling.
                    if failover:
                        live_agents = {
                            aid
                            for s in pending
                            for aid in slots[s]["live"]
                        }
                        for aid in self.tracker.expired_among(live_agents):
                            for s in list(pending):
                                st = slots[s]
                                if st["done"] or aid not in st["live"]:
                                    continue
                                _attempt_lost(
                                    s, st, aid, st["live"][aid],
                                    "agent_lost",
                                    "agent lost: heartbeat expired "
                                    "mid-query",
                                )
                        if hedging:
                            _maybe_hedge()
                    elif partial_ok:
                        for inst in self.tracker.expired_among(pending):
                            pending.discard(inst)
                            lost_agents.append(inst)
                            agent_errors.setdefault(
                                inst, "agent lost: heartbeat expired "
                                "mid-query"
                            )
                            emit(
                                {
                                    "type": "agent_lost",
                                    "agent_id": inst,
                                    "error": agent_errors[inst],
                                }
                            )
                            for bid in bridges_by_instance.get(inst, ()):
                                self.router.unregister_producer(qid, bid)
                    continue
                if failover and msg["type"] in (
                    "result_batch", "fragment_done", "fragment_error"
                ):
                    s = msg.get("slot")
                    st = slots.get(s)
                    aid = msg.get("agent_id")
                    ep = msg.get("result_epoch")
                    if (
                        st is None
                        or st["done"]
                        or st["live"].get(aid) != ep
                    ):
                        # Stale attempt (zombie the reaper declared dead,
                        # hedge loser, superseded epoch): exactly-once is
                        # THIS drop.
                        if msg["type"] == "fragment_done":
                            _HEDGE_BOTH.inc()
                        continue
                    if msg["type"] == "result_batch":
                        if faults.ACTIVE and faults.fires("broker.forward"):
                            # The attempt's stream is now incomplete —
                            # fail the ATTEMPT over instead of silently
                            # applying a truncated buffer. Only an
                            # UNRECOVERED drop degrades the result.
                            _FORWARD_DROPPED.inc()
                            if not _attempt_lost(
                                s, st, aid, ep, "forward_dropped",
                                "result batch dropped in the broker "
                                "forwarder",
                            ):
                                forward_dropped += 1
                            continue
                        st["bufs"][(aid, ep)].append(
                            (msg["table"], msg["batch"])
                        )
                    elif msg["type"] == "fragment_done":
                        # First completed attempt wins the slot: apply
                        # its buffered output atomically, cancel any
                        # sibling through the r9 abort path.
                        st["done"] = True
                        pending.discard(s)
                        self._launch_done(aid, qid)
                        for table, batch in st["bufs"].pop((aid, ep), ()):
                            if on_batch is not None:
                                on_batch(table, batch)
                            else:
                                tables.setdefault(table, []).append(batch)
                        for k, v in msg.get("exec_stats", {}).items():
                            exec_stats[f"{aid}/{k}"] = v
                        for sp in msg.get("spans") or ():
                            agent_spans[sp["span_id"]] = sp
                        if st["lost_at"] is not None:
                            _RECOVERY_SECONDS_H.observe(
                                time.monotonic() - st["lost_at"]
                            )
                        if st["hedge"] is not None:
                            st["hedge"]["winner"] = aid
                        siblings = {
                            a: e for a, e in st["live"].items() if a != aid
                        }
                        st["live"] = {aid: ep}
                        if siblings and not (
                            faults.ACTIVE
                            and faults.fires("hedge.both_complete")
                        ):
                            for sib, sib_ep in siblings.items():
                                _revoke_attempt(st, s, sib, sib_ep)
                                self.bus.publish(
                                    agent_topic(sib),
                                    {
                                        "type": "cancel_query",
                                        "query_id": qid,
                                        "slot": s,
                                        "result_epoch": sib_ep,
                                    },
                                )
                    else:  # fragment_error
                        self._launch_done(aid, qid)
                        for sp in msg.get("spans") or ():
                            agent_spans[sp["span_id"]] = sp
                        kind = msg.get("error_kind", "error")
                        reason = (
                            kind
                            if kind in ("restart_lost", "deadline")
                            else "agent_error"
                        )
                        _attempt_lost(
                            s, st, aid, ep, reason, msg["error"],
                            kind=kind,
                        )
                    continue
                if msg["type"] == "result_batch":
                    if faults.ACTIVE and faults.fires("broker.forward"):
                        forward_dropped += 1
                        _FORWARD_DROPPED.inc()
                        continue
                    if on_batch is not None:
                        on_batch(msg["table"], msg["batch"])
                    else:
                        tables.setdefault(msg["table"], []).append(
                            msg["batch"]
                        )
                elif msg["type"] == "fragment_done":
                    for k, v in msg.get("exec_stats", {}).items():
                        exec_stats[f"{msg['agent_id']}/{k}"] = v
                    for s in msg.get("spans") or ():
                        agent_spans[s["span_id"]] = s
                    pending.discard(msg["agent_id"])
                    self._launch_done(msg["agent_id"], qid)
                elif msg["type"] == "fragment_error":
                    aid = msg["agent_id"]
                    self._launch_done(aid, qid)
                    agent_errors[aid] = msg["error"]
                    for s in msg.get("spans") or ():
                        agent_spans[s["span_id"]] = s
                    if msg.get("error_kind") == "deadline":
                        timed_out_agents.append(aid)
                    emit(
                        {
                            "type": "agent_error",
                            "agent_id": aid,
                            "error": msg["error"],
                            "error_kind": msg.get("error_kind", "error"),
                        }
                    )
                    pending.discard(aid)
                    if partial_ok:
                        # The failed fragments produced no (or partial)
                        # bridge output: release their producer slots so
                        # downstream merge fragments finalize with what
                        # they have instead of stalling on eos markers
                        # that will never come.
                        for bid in bridges_by_instance.get(aid, ()):
                            self.router.unregister_producer(qid, bid)
        finally:
            fwd_attr.__exit__(None, None, None)
            results_sub.unsubscribe()
            if placed_agent is not None and self.placement is not None:
                # Inflight occupancy feeds the placement load tie-break.
                self.placement.release(placed_agent)
            # cleanup_query also tombstones the id: late pushes from
            # still-running fragments are dropped and their polls abort
            # (BridgeCancelled) instead of leaking buffers.
            self.router.cleanup_query(qid)
            # Drop any remaining launch records (timed-out/lost agents):
            # a finished query must never be re-offered.
            with self._launch_lock:
                for inst in list(self._inflight_launches):
                    self._inflight_launches[inst].pop(qid, None)
                    if not self._inflight_launches[inst]:
                        del self._inflight_launches[inst]
        if results_sub.dropped:
            # Result messages were dropped after the flow-control timeout:
            # the stream is incomplete because the CONSUMER is too slow —
            # that is a local flow-control failure, not a degraded cluster;
            # fail loudly rather than return partial data as success
            # (ref: the forwarder cancels the query,
            # query_result_forwarder.go:571).
            raise RuntimeError(
                f"query {qid}: consumer too slow — {results_sub.dropped} "
                "result messages dropped after "
                f"{flags.broker_publish_timeout_s}s of backpressure"
            )
        if agent_errors and not partial_ok:
            raise RuntimeError(
                f"query {qid} failed on agents:\n"
                + "\n".join(f"{a}: {e}" for a, e in sorted(agent_errors.items()))
            )
        # r17: what failover did for this query. A fully-recovered query
        # carries a ``recovered`` annotation INSTEAD of the degraded one
        # (the rows are complete and bit-identical to an unfaulted run);
        # a query that still degraded carries the attempt history inside
        # the degraded annotation for diagnosis.
        retried_all = [
            e for st in slots.values() for e in st["retried"]
        ]
        hedged_all = [
            dict(st["hedge"])
            for st in slots.values()
            if st["hedge"] is not None
        ]
        recovered = None
        degraded = None
        if partial_ok and (
            agent_errors
            or lost_agents
            or timed_out_agents
            or skipped_agents
            or forward_dropped
        ):
            reasons = []
            if lost_agents:
                reasons.append("agent_lost")
            if timed_out_agents:
                reasons.append("deadline")
            if agent_errors and set(agent_errors) - set(lost_agents) - set(
                timed_out_agents
            ):
                reasons.append("agent_error")
            if skipped_agents:
                reasons.append("agents_skipped")
            if breaker_skipped:
                reasons.append("breaker_open")
            if forward_dropped:
                reasons.append("forward_dropped")
            degraded = {
                "partial": True,
                "reasons": reasons,
                "agent_errors": dict(sorted(agent_errors.items())),
                "lost_agents": sorted(lost_agents),
                "timed_out_agents": sorted(set(timed_out_agents)),
                "skipped_agents": list(skipped_agents),
                # Structured skip entries (r10): who planning left out
                # and WHY (heartbeat_expired | breaker_open).
                "skipped": skipped,
                "forward_dropped": forward_dropped,
                # Joins the annotation to the query's spans and inline
                # events (r11 satellite; trace_id == query_id).
                "trace_id": qid,
            }
            if retried_all or hedged_all:
                degraded["failover"] = {
                    "retried": retried_all, "hedged": hedged_all,
                }
            _DEGRADED.inc()
        elif retried_all or hedged_all or promoted_replica:
            recovered = {
                "retried": retried_all,
                "hedged": hedged_all,
                "trace_id": qid,
            }
            if promoted_replica:
                recovered["promoted_replica"] = promoted_replica
            _RECOVERED_Q.inc()
        exec_ns = time.perf_counter_ns() - t1
        _QUERY_SECONDS.observe(
            (compile_ns + exec_ns) / 1e9, tenant=tenant or "default"
        )
        trace_spans = None
        if root is not None:
            root_attrs2 = None
            if degraded:
                root_attrs2 = {
                    "degraded_reasons": ",".join(degraded["reasons"])
                }
            elif recovered:
                root_attrs2 = {
                    "recovered_fragments": len(retried_all)
                    + len(hedged_all)
                }
            trace.finish(
                root,
                status="degraded" if degraded else "ok",
                attrs=root_attrs2,
            )
            # Merge broker-side spans with agent-shipped ones by span_id
            # (one trace_id across the cluster; agents that died mid-query
            # simply contribute fewer spans — the profile marks them via
            # the degraded annotation).
            merged = {
                s.span_id: s.to_dict() for s in trace.spans_for(qid)
            }
            merged.update(agent_spans)
            trace_spans = sorted(
                merged.values(), key=lambda s: s["start_unix_ns"]
            )
            if flags.trace_otel_export and trace_spans:
                self._export_otel_spans(trace_spans)
        return QueryResult(
            query_id=qid,
            tables=tables,
            exec_stats=exec_stats,
            compile_time_ns=compile_ns,
            exec_time_ns=exec_ns,
            degraded=degraded,
            recovered=recovered,
            trace_spans=trace_spans,
        )

    def _export_otel_spans(self, spans: list[dict]) -> None:
        """Optional OTel export of a finished query trace through the
        same payload shape the exec/otel_sink_node.py sink emits. The
        exporter is pluggable (``self.otel_exporter``); unset drops."""
        exporter = getattr(self, "otel_exporter", None)
        if exporter is None:
            return
        try:
            exporter(trace.spans_to_otel(spans, service="broker"))
        except Exception:
            _log.exception("otel span export failed (ignored)")

    def stop(self) -> None:
        from pixie_tpu.serving import shared_scan as _shared_scan

        _shared_scan.clear_queue_depth_fn(self._queue_depth_fn)
        if self.admission_controller is not None:
            self.admission_controller.stop()
            self.admission_controller = None
        if self.ring_rebalancer is not None:
            self.ring_rebalancer.stop()
            self.ring_rebalancer = None
        if self.views is not None:
            self.views.stop()
            self.views = None
        self.tracker.stop()
        if self._health_srv is not None:
            self._health_srv.stop()
            self._health_srv = None
