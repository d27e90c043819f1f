"""Residency-aware fleet placement (r18).

Proactive inversion of the r17 failover ranking: instead of choosing a
replacement agent only AFTER a fragment is lost, the broker scores every
live agent for a query's table span AT ADMISSION and routes the scan to
the agent whose HBM already holds the data. Placement and failover share
one scorer (``coverage`` / ``failover_rank`` below), so "which agent can
serve this span, and how warm is it there" has exactly one definition.

The coverage ladder, classified purely from heartbeat-advertised state
(the broker never touches a device):

``ring_hit``
    every needed table is device-resident on the agent — a staged-cache
    entry in its ResidencyPool snapshot or an owned resident-ingest
    ring. Wire bytes for the scan are ~0.
``replica_hit``
    every needed table is covered by an adopted replica ring with at
    least one window: the replicated payload is already decoded in the
    follower's HBM.
``latency_fallback``
    no advertised residency; the agent is ranked by the r11
    per-program-key fold-latency view (lowest mean p50) and load.
``cold``
    no residency and no latency history — weighted-load round robin.

Within a rung, ties break by span affinity (the agent this exact table
span was last placed on, so placement stays stable across the heartbeat
lag between a placement and the residency it creates), then WFQ-weighted
load (per-tenant admission weights scale each placed query's cost, so a
heavy tenant's queries spread across more of the fleet), then mean fold
p50, then agent id.

``RingRebalancer`` makes r17's static leader-rank follower attachment
adaptive: per-table placement heat (the admission-side view of the
``device_dispatches`` telemetry) decides WHICH tables deserve replicas,
heartbeat ResidencyPool snapshots rail WHERE they may land (followers
above ``ring_rebalance_high_pct`` of their HBM budget are skipped), and
every move rides the existing codec'd ring_replica topic as a
``ring_replica_assign`` message plus an actuation-trail entry shaped
like the r16 admission controller's. An empty heat window holds every
assignment — no signal, no actuation.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable, Dict, FrozenSet, Iterable, List, Optional, Tuple

from pixie_tpu.utils import flags, metrics_registry

_M = metrics_registry()
_DECISIONS = _M.counter(
    "broker_placement_decisions_total",
    "Placement decisions by outcome (ring_hit|replica_hit|latency_fallback|cold).",
)
_HIT_RATE = _M.gauge(
    "broker_placement_hit_rate",
    "Fraction of placement decisions that landed on resident or replica HBM.",
)
_REBALANCE_MOVES = _M.counter(
    "broker_ring_rebalance_moves_total",
    "Replica-ring follower reassignments published by the rebalancer.",
)

# Outcome ladder, most preferred first. view_hit (r20) sits ABOVE
# ring_hit: a query answered from a materialized view's merged state
# never reaches admission, so no agent ranking happens at all — the
# broker records it via record_view_hit(), and decide() never returns
# it. latency_fallback and cold share one RANK rung (they are both
# "no residency" — ranked by load then latency then name, so a fresh
# agent isn't starved just because a warmer-history one exists); the
# labels stay distinct for metrics.
OUTCOMES = (
    "view_hit",
    "ring_hit",
    "replica_hit",
    "latency_fallback",
    "cold",
    "mesh_fold",
)
# mesh_fold (r21) is not an agent rung: decide() returns it INSTEAD of a
# pick when the span's estimated staging bytes exceed every eligible
# agent's advertised HBM budget — forcing a single-agent placement would
# only thrash that agent's residency ring, so the broker plans the fold
# across the full fleet (spanning placement). Order 3 is for the
# metrics/ladder listing only; it never competes in the rank tuple.
_OUTCOME_ORDER = {
    "view_hit": -1,
    "ring_hit": 0,
    "replica_hit": 1,
    "latency_fallback": 2,
    "cold": 2,
    "mesh_fold": 3,
}

View = List[Dict[str, Any]]  # AgentTracker.failover_view() entries


def eligible(agent: Dict[str, Any], needed: FrozenSet[str]) -> bool:
    """An agent can serve ``needed`` if it owns or replicates every table."""
    return needed <= (agent["tables"] | agent["replica_tables"])


def coverage(agent: Dict[str, Any], needed: FrozenSet[str]) -> Dict[str, Any]:
    """Score one failover_view entry's coverage of a table span.

    All signals come from the heartbeat-carried health snapshot:
    ``residency.tables`` (staged-cache entries), ``resident_ingest``
    (owned rings), and ``replicas`` (adopted replica rings with
    windows/lag watermarks).
    """
    health = agent.get("health") or {}
    staged = set((health.get("residency") or {}).get("tables") or ())
    rings = set(health.get("resident_ingest") or ())
    reps = health.get("replicas") or {}
    hot = 0
    lag = 0
    replica_all = bool(needed)
    for t in needed:
        r = reps.get(t) or {}
        w = int(r.get("windows", 0) or 0)
        hot += w
        lag += int(r.get("lag", 0) or 0)
        if w <= 0:
            replica_all = False
    return {
        "owned": needed <= agent["tables"],
        "resident": bool(needed) and needed <= (staged | rings),
        "replica": replica_all,
        "hot": hot,
        "lag": lag,
    }


def failover_rank(
    agent: Dict[str, Any], needed: FrozenSet[str], prefer_kelvin: bool
) -> Tuple:
    """The r17 failover rank tuple, verbatim: role match, then ownership,
    then replica warmth (more windows better), then lag, then name."""
    cov = coverage(agent, needed)
    return (
        0 if bool(agent["is_kelvin"]) == prefer_kelvin else 1,
        0 if cov["owned"] else 1,
        -cov["hot"],
        cov["lag"],
        agent["agent_id"],
    )


def best_failover_candidate(
    view: View,
    needed: FrozenSet[str],
    skip: Iterable[str],
    prefer_kelvin: bool,
) -> Optional[str]:
    """r17 failover candidate selection on the shared scorer."""
    skip = set(skip)
    best: Optional[Tuple[Tuple, str]] = None
    for a in view:
        if a["agent_id"] in skip or not eligible(a, needed):
            continue
        rank = failover_rank(a, needed, prefer_kelvin)
        if best is None or rank < best[0]:
            best = (rank, a["agent_id"])
    return best[1] if best else None


def classify(cov: Dict[str, Any]) -> Optional[str]:
    """Coverage dict -> outcome rung, or None when residency says nothing
    (the caller decides latency_fallback vs cold from the latency view)."""
    if cov["resident"]:
        return "ring_hit"
    if cov["replica"]:
        return "replica_hit"
    return None


def agent_latency(fold_latency_view: Optional[Dict[str, Dict]]) -> Dict[str, float]:
    """Collapse the r11 per-program-key view to agent -> mean p50 ms."""
    sums: Dict[str, List[float]] = {}
    for per_agent in (fold_latency_view or {}).values():
        for aid, stats in per_agent.items():
            p50 = stats.get("p50_ms")
            if not p50:
                continue
            acc = sums.setdefault(aid, [0.0, 0.0])
            acc[0] += float(p50)
            acc[1] += 1.0
    return {aid: acc[0] / acc[1] for aid, acc in sums.items() if acc[1]}


class PlacementPlane:
    """Admission-time placement state: decision counters, span affinity,
    WFQ-weighted load, inflight occupancy, and per-table query heat.

    ``decide`` is pure — it ranks but records nothing — so a placed plan
    that fails (ValueError from the planner) can fall back to the normal
    path without polluting metrics. The broker calls ``commit`` once the
    placed plan succeeds and ``release`` in its finally block.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._outcomes: collections.Counter = collections.Counter()
        self._placed: collections.Counter = collections.Counter()
        self._load: Dict[str, float] = collections.defaultdict(float)
        self._inflight: collections.Counter = collections.Counter()
        self._affinity: Dict[FrozenSet[str], str] = {}
        self._heat: collections.Counter = collections.Counter()
        self._heat_total: collections.Counter = collections.Counter()

    # -- routing ----------------------------------------------------------

    def decide(
        self,
        view: View,
        needed: FrozenSet[str],
        fold_latency: Optional[Dict[str, Dict]] = None,
        estimated_bytes: int = 0,
    ) -> Tuple[Optional[str], Optional[str]]:
        """Rank eligible data-plane agents for ``needed``.

        Returns (agent_id, outcome), (None, "mesh_fold") when the span
        is too big for any single agent's HBM (see _OUTCOME_ORDER), or
        (None, None) when no live non-kelvin agent covers the span.
        """
        if not needed:
            return None, None
        # r21 mesh_fold rung: with a staging estimate in hand, refuse a
        # single-agent pick when the span exceeds EVERY eligible
        # agent's advertised HBM budget (heartbeat residency snapshot).
        # An agent without an advertised budget is unknown — assume it
        # fits, keeping the rung conservative.
        if estimated_bytes > 0 and flags.mesh_fold_placement:
            any_eligible = False
            fits_somewhere = False
            for a in view:
                if a["is_kelvin"] or not eligible(a, needed):
                    continue
                any_eligible = True
                res = (a.get("health") or {}).get("residency") or {}
                budget = int(res.get("budget_bytes") or 0)
                if budget <= 0 or estimated_bytes <= budget:
                    fits_somewhere = True
                    break
            if any_eligible and not fits_somewhere:
                return None, "mesh_fold"
        lat = agent_latency(fold_latency)
        best: Optional[Tuple[Tuple, str, str]] = None
        with self._lock:
            aff = self._affinity.get(needed)
            inflight = dict(self._inflight)
            load = dict(self._load)
        for a in view:
            if a["is_kelvin"] or not eligible(a, needed):
                continue
            aid = a["agent_id"]
            outcome = classify(coverage(a, needed))
            if outcome is None:
                outcome = "latency_fallback" if aid in lat else "cold"
            rank = (
                _OUTCOME_ORDER[outcome],
                0 if aid == aff else 1,
                inflight.get(aid, 0) + load.get(aid, 0.0),
                lat.get(aid, 0.0),
                aid,
            )
            if best is None or rank < best[0]:
                best = (rank, aid, outcome)
        if best is None:
            return None, None
        return best[1], best[2]

    def commit(
        self,
        agent_id: str,
        outcome: str,
        needed: FrozenSet[str],
        weight: float = 1.0,
    ) -> None:
        """Record a routed decision: counters, hit gauge, span affinity,
        WFQ-weighted load, per-table heat, and inflight occupancy."""
        _DECISIONS.inc(outcome=outcome)
        with self._lock:
            self._outcomes[outcome] += 1
            self._placed[agent_id] += 1
            self._load[agent_id] += 1.0 / max(float(weight), 1e-6)
            self._inflight[agent_id] += 1
            self._affinity[needed] = agent_id
            if len(self._affinity) > 4096:
                self._affinity.pop(next(iter(self._affinity)))
            for t in needed:
                self._heat[t] += 1
                self._heat_total[t] += 1
            total = sum(self._outcomes.values())
            hits = (
                self._outcomes["view_hit"]
                + self._outcomes["ring_hit"]
                + self._outcomes["replica_hit"]
            )
        _HIT_RATE.set(hits / total if total else 0.0)

    def record_view_hit(self) -> None:
        """r20: a query served from a materialized view before admission.
        Top rung of the ladder — counts as a hit (the whole point is
        that NO agent had to fold), no agent load/affinity to record."""
        _DECISIONS.inc(outcome="view_hit")
        with self._lock:
            self._outcomes["view_hit"] += 1
            total = sum(self._outcomes.values())
            hits = (
                self._outcomes["view_hit"]
                + self._outcomes["ring_hit"]
                + self._outcomes["replica_hit"]
            )
        _HIT_RATE.set(hits / total if total else 0.0)

    def route_view_tail(
        self,
        agent_id: str,
        needed: FrozenSet[str],
        weight: float = 1.0,
    ) -> None:
        """r21: a view hit's unflushed-tail delta fold, routed to the
        view's maintain agent (the tracker pick recorded at
        registration). Attribution only — not an admission decision,
        so the outcome/hit-rate counters are untouched; the agent's
        WFQ load, inflight occupancy, and table heat do move so the
        rebalancer and the ladder see the tail work where it runs.
        Pair with ``release(agent_id)`` when the fold completes."""
        with self._lock:
            self._placed[agent_id] += 1
            self._load[agent_id] += 1.0 / max(float(weight), 1e-6)
            self._inflight[agent_id] += 1
            for t in needed:
                self._heat[t] += 1
                self._heat_total[t] += 1

    def release(self, agent_id: str) -> None:
        with self._lock:
            if self._inflight[agent_id] > 0:
                self._inflight[agent_id] -= 1

    # -- rebalancer feed --------------------------------------------------

    def drain_heat(self) -> Dict[str, int]:
        """Per-table placement counts since the last drain — the
        rebalancer's query-heat window."""
        with self._lock:
            heat = {t: int(c) for t, c in self._heat.items() if c}
            self._heat.clear()
        return heat

    # -- observability ----------------------------------------------------

    def status(self) -> Dict[str, Any]:
        with self._lock:
            outcomes = dict(self._outcomes)
            placed = dict(self._placed)
            per_agent = {
                aid: {
                    "placed": int(placed.get(aid, 0)),
                    "load": round(self._load.get(aid, 0.0), 3),
                    "inflight": int(self._inflight.get(aid, 0)),
                }
                for aid in sorted(set(placed) | set(self._load) | set(self._inflight))
            }
            heat = dict(self._heat_total)
            affinity_spans = len(self._affinity)
        total = sum(outcomes.values())
        hits = (
            outcomes.get("view_hit", 0)
            + outcomes.get("ring_hit", 0)
            + outcomes.get("replica_hit", 0)
        )
        shares = [c for c in placed.values() if c > 0]
        return {
            "decisions": {o: int(outcomes.get(o, 0)) for o in OUTCOMES},
            "total": int(total),
            "hit_rate": round(hits / total, 4) if total else None,
            "per_agent": per_agent,
            "balance_max_min": (
                round(max(shares) / min(shares), 3) if shares else None
            ),
            "affinity_spans": affinity_spans,
            "table_heat": heat,
        }


class RingRebalancer:
    """Adaptive replica-ring follower assignment (r18).

    Each ``tick`` drains the placement plane's per-table heat window and,
    for every hot table, picks up to ``ring_replication_factor - 1``
    followers among live non-kelvin agents that advertise the table as
    replica-capable WITHOUT owning it, skipping any follower whose
    heartbeat ResidencyPool reports usage above ``ring_rebalance_high_pct``
    of its HBM budget. Changed assignments are published on the codec'd
    ring_replica topic (``ring_replica_assign``) and appended to a
    bounded actuation trail; unchanged assignments publish nothing. An
    empty heat window is a hold: no actuation at all.
    """

    def __init__(
        self,
        publish: Callable[[Dict[str, Any]], None],
        view_fn: Callable[[], View],
        heat_fn: Callable[[], Dict[str, int]],
    ) -> None:
        self._publish = publish
        self._view_fn = view_fn
        self._heat_fn = heat_fn
        self._lock = threading.Lock()
        self._assignments: Dict[str, Tuple[str, ...]] = {}
        self._seq = 0
        self.trail: collections.deque = collections.deque(maxlen=256)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- policy -----------------------------------------------------------

    @staticmethod
    def _headroom_ok(agent: Dict[str, Any], high_pct: float) -> bool:
        res = (agent.get("health") or {}).get("residency") or {}
        budget = int(res.get("budget_bytes") or 0)
        if budget <= 0:
            return True  # unlimited pool: no rail to exceed
        return int(res.get("used_bytes") or 0) < high_pct * budget

    def tick(self) -> List[Dict[str, Any]]:
        """One rebalance pass. Returns the actuations applied (empty
        list = hold). Callable directly from tests; the background
        thread just calls this on an interval."""
        cap = max(int(flags.ring_replication_factor) - 1, 0)
        if cap <= 0:
            return []
        heat = {t: int(c) for t, c in (self._heat_fn() or {}).items() if c > 0}
        if not heat:
            return []  # empty window: hold every assignment
        view = self._view_fn()
        high_pct = float(flags.ring_rebalance_high_pct)
        moves: List[Dict[str, Any]] = []
        assigned_this_tick: collections.Counter = collections.Counter()
        with self._lock:
            # Hottest tables claim follower headroom first.
            for table in sorted(heat, key=lambda t: (-heat[t], t)):
                cands = []
                for a in view:
                    if a["is_kelvin"] or table in a["tables"]:
                        continue  # leaders replicate out, not in
                    if table not in a["replica_tables"]:
                        continue
                    if not self._headroom_ok(a, high_pct):
                        continue
                    res = (a.get("health") or {}).get("residency") or {}
                    cands.append(
                        (
                            assigned_this_tick[a["agent_id"]],
                            int(res.get("used_bytes") or 0),
                            a["agent_id"],
                        )
                    )
                cands.sort()
                followers = tuple(aid for _, _, aid in cands[:cap])
                old = self._assignments.get(table)
                if followers == old or (not followers and old is None):
                    for aid in followers:
                        assigned_this_tick[aid] += 1
                    continue
                self._seq += 1
                self._assignments[table] = followers
                for aid in followers:
                    assigned_this_tick[aid] += 1
                try:
                    self._publish(
                        {
                            "type": "ring_replica_assign",
                            "table": table,
                            "followers": list(followers),
                            "seq": self._seq,
                        }
                    )
                except Exception:
                    pass  # bus teardown race; assignment re-publishes next change
                entry = {
                    "time_ns": time.time_ns(),
                    "knob": f"replica_assign:{table}",
                    "from": list(old) if old is not None else None,
                    "to": list(followers),
                    "reason": "hbm_pressure" if old and not followers else "query_heat",
                    "signals": {"heat": heat[table], "candidates": len(cands)},
                }
                self.trail.append(entry)
                _REBALANCE_MOVES.inc()
                moves.append(entry)
        return moves

    # -- lifecycle --------------------------------------------------------

    def start(self, interval_s: Optional[float] = None) -> None:
        if self._thread is not None:
            return
        period = float(
            interval_s if interval_s is not None else flags.ring_rebalance_interval_s
        )

        def loop() -> None:
            while not self._stop.wait(period):
                try:
                    self.tick()
                except Exception:
                    pass  # a bad snapshot shouldn't kill the loop

        self._thread = threading.Thread(
            target=loop, name="ring-rebalancer", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None

    def status(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "assignments": {
                    t: list(f) for t, f in sorted(self._assignments.items())
                },
                "rails": {
                    "replication_factor": int(flags.ring_replication_factor),
                    "high_pct": float(flags.ring_rebalance_high_pct),
                },
                "actuations": list(self.trail)[-32:],
            }
