"""Broker admission control: concurrency limit + weighted fair queueing.

Ref posture: the reference's query broker accepts every ExecuteScript and
lets timeouts sort out overload; a broker serving heavy traffic needs a
front door. This controller gives ``QueryBroker.execute_script`` one:

- **Concurrency limit.** At most ``admission_max_concurrent`` queries
  execute at once; arrivals past that wait in a bounded queue
  (``admission_max_queue``) and past THAT are rejected immediately with
  a structured ``AdmissionRejected`` — overload degrades into fast
  errors, never into unbounded memory or a hang.
- **Per-tenant weighted fair queueing.** Waiters are granted in
  virtual-finish-time order: a tenant's request is stamped
  ``max(vclock, tenant_last) + 1/weight``, so a tenant's own backlog
  accrues virtual time linearly while a quiet tenant's first request
  lands just after the clock — a starved tenant schedules ahead of a
  heavy tenant's backlog tail, and a 2x-weighted tenant drains twice as
  fast under contention (classic WFQ/SFQ virtual-clock scheduling).
- **HBM byte-budget check.** Before admitting, the controller consults
  the residency pool (when wired): if PINNED bytes already exceed the
  budget, no eviction can make room for this query's staging — reject
  with ``reason="hbm_budget"`` instead of letting it OOM the device.
- **Observability.** Queue depth / active gauges, a wait-time histogram
  (the r11 Histogram kind), and per-reason rejection counters on the
  shared /metrics registry; ``snapshot()`` feeds the broker's /statusz
  (the r10 health plane).

Fault site ``serving.admission_reject`` forces a rejection so chaos
tests can prove the structured-error path end to end.
"""

from __future__ import annotations

import heapq
import itertools
import threading
import time
from typing import Callable, Optional

from pixie_tpu.utils import faults, flags, metrics_registry

_M = metrics_registry()
_QUEUE_DEPTH = _M.gauge(
    "admission_queue_depth", "Queries waiting in the admission queue."
)
_ACTIVE = _M.gauge(
    "admission_active", "Queries currently admitted and executing."
)
_ADMITTED = _M.counter(
    "admission_admitted_total", "Queries admitted, by tenant."
)
_REJECTED = _M.counter(
    "admission_rejected_total",
    "Queries rejected, by reason and tenant (r15: per-tenant SLO rules "
    "get native series; sum across tenants via Counter.total).",
)
_WAIT_SECONDS = _M.histogram(
    "admission_wait_seconds",
    "Time a query spent in the admission queue before grant/rejection, "
    "by tenant (aggregate views read Histogram.agg_quantile).",
)
_LOCK_WAIT = _M.histogram(
    "admission_lock_wait_seconds",
    "Time a caller waited to acquire the admission controller's lock "
    "(only contended acquisitions are observed — the r12 follow-on "
    "lock-profiling signal at ~1k-client depth).",
)


class AdmissionRejected(RuntimeError):
    """Structured overload rejection: carries enough for a client to
    back off intelligently (reason, tenant, live queue depth, how long
    the request waited)."""

    def __init__(
        self,
        tenant: str,
        reason: str,
        queue_depth: int = 0,
        waited_s: float = 0.0,
        detail: str = "",
    ):
        super().__init__(
            f"admission rejected for tenant {tenant!r}: {reason}"
            + (f" ({detail})" if detail else "")
            + f" [queue_depth={queue_depth}, waited={waited_s:.3f}s]"
        )
        self.tenant = tenant
        self.reason = reason
        self.queue_depth = queue_depth
        self.waited_s = waited_s
        self.detail = detail

    def to_dict(self) -> dict:
        return {
            "tenant": self.tenant,
            "reason": self.reason,
            "queue_depth": self.queue_depth,
            "waited_s": round(self.waited_s, 6),
            "detail": self.detail,
        }


def parse_tenant_weights(spec: str) -> dict[str, float]:
    """'tenant:weight,tenant:weight' -> {tenant: weight}; malformed
    entries are skipped (a typo'd weight must not take the broker down)."""
    out: dict[str, float] = {}
    for entry in (spec or "").split(","):
        entry = entry.strip()
        if not entry:
            continue
        name, _, w = entry.rpartition(":")
        try:
            weight = float(w)
        except ValueError:
            continue
        if name and weight > 0:
            out[name] = weight
    return out


class _Waiter:
    __slots__ = ("vtime", "seq", "tenant", "granted", "abandoned")

    def __init__(self, vtime: float, seq: int, tenant: str):
        self.vtime = vtime
        self.seq = seq
        self.tenant = tenant
        self.granted = False
        self.abandoned = False  # timed out: skip when popped

    def __lt__(self, other: "_Waiter") -> bool:
        return (self.vtime, self.seq) < (other.vtime, other.seq)


class _Ticket:
    """Held by an admitted query; release() frees the slot (idempotent).
    Usable as a context manager."""

    def __init__(self, ctl: "AdmissionController", tenant: str, waited_s):
        self._ctl = ctl
        self.tenant = tenant
        self.waited_s = waited_s
        self._released = False

    def release(self) -> None:
        if not self._released:
            self._released = True
            self._ctl._release()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.release()
        return False


class AdmissionController:
    def __init__(
        self,
        max_concurrent: Optional[int] = None,
        max_queue: Optional[int] = None,
        timeout_s: Optional[float] = None,
        tenant_weights: Optional[dict[str, float]] = None,
        budget_fn: Optional[Callable[[], dict]] = None,
    ):
        """Unset limits re-read their flags per call, so runtime flag
        flips apply live. ``budget_fn`` returns a residency snapshot
        (ResidencyPool.snapshot-shaped: pinned_bytes/budget_bytes)."""
        self._max_concurrent = max_concurrent
        self._max_queue = max_queue
        self._timeout_s = timeout_s
        self._weights = tenant_weights
        self._budget_fn = budget_fn
        self._cv = threading.Condition()
        self._active = 0
        self._heap: list[_Waiter] = []
        self._waiting = 0
        self._vclock = 0.0
        self._tenant_vtime: dict[str, float] = {}
        self._seq = itertools.count()

    # -- limits (flag-backed unless pinned at construction) ------------------
    def _limit(self) -> int:
        return (
            self._max_concurrent
            if self._max_concurrent is not None
            else max(int(flags.admission_max_concurrent), 1)
        )

    def _queue_cap(self) -> int:
        return (
            self._max_queue
            if self._max_queue is not None
            else max(int(flags.admission_max_queue), 0)
        )

    def _timeout(self) -> float:
        return (
            self._timeout_s
            if self._timeout_s is not None
            else float(flags.admission_timeout_s)
        )

    def _weight(self, tenant: str) -> float:
        weights = (
            self._weights
            if self._weights is not None
            else parse_tenant_weights(flags.admission_tenant_weights)
        )
        return float(weights.get(tenant, 1.0))

    # -- the front door ------------------------------------------------------
    def acquire(
        self, tenant: str = "default", estimated_bytes: int = 0
    ) -> _Ticket:
        """Block until admitted (WFQ order) or raise AdmissionRejected.
        Every exit path is bounded: queue-full and budget rejections are
        immediate, a queued request rejects at ``admission_timeout_s``.

        ``estimated_bytes`` (r13): the query's predicted staging
        footprint from table metadata (row count × encoded column
        widths — see ``estimate_staging_bytes``). When set, the HBM
        budget check rejects a query whose staging could never fit
        even after evicting every unpinned entry — BEFORE the doomed
        cold stage starts, not once pinned bytes already exceed
        budget."""
        t0 = time.monotonic()
        if not self._cv.acquire(blocking=False):
            w0 = time.perf_counter()
            self._cv.acquire()
            _LOCK_WAIT.observe(time.perf_counter() - w0)
        try:
            if faults.ACTIVE and faults.fires("serving.admission_reject"):
                self._reject(tenant, "fault_injected", t0)
            self._budget_check(tenant, t0, estimated_bytes)
            # Prune timed-out waiters off the heap top so a queue of
            # abandoned entries cannot block the immediate-admit path.
            while self._heap and self._heap[0].abandoned:
                heapq.heappop(self._heap)
            if self._active < self._limit() and not self._heap:
                self._active += 1
                self._vclock = max(
                    self._vclock,
                    self._tenant_vtime.get(tenant, 0.0),
                ) + 1.0 / self._weight(tenant)
                self._tenant_vtime[tenant] = self._vclock
                self._publish()
                _ADMITTED.inc(tenant=tenant)
                _WAIT_SECONDS.observe(0.0, tenant=tenant)
                return _Ticket(self, tenant, 0.0)
            if self._waiting >= self._queue_cap():
                self._reject(tenant, "queue_full", t0)
            w = _Waiter(
                max(self._vclock, self._tenant_vtime.get(tenant, 0.0))
                + 1.0 / self._weight(tenant),
                next(self._seq),
                tenant,
            )
            self._tenant_vtime[tenant] = w.vtime
            heapq.heappush(self._heap, w)
            self._waiting += 1
            self._publish()
            deadline = t0 + self._timeout()
            while not w.granted:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    w.abandoned = True
                    self._waiting -= 1
                    self._publish()
                    self._reject(tenant, "timeout", t0)
                self._cv.wait(timeout=remaining)
            waited = time.monotonic() - t0
            _ADMITTED.inc(tenant=tenant)
            _WAIT_SECONDS.observe(waited, tenant=tenant)
            return _Ticket(self, tenant, waited)
        finally:
            self._cv.release()

    def _budget_check(
        self, tenant: str, t0: float, estimated_bytes: int = 0
    ) -> None:
        """Reject when the HBM residency pool has no reclaimable
        headroom: pinned bytes (in-flight folds) already at/over budget
        means eviction cannot make room for this query's staging — and
        (r13) when the query's ESTIMATED staging bytes cannot fit the
        budget's unpinned headroom either, so a doomed cold stage is
        refused before it moves a single byte."""
        if self._budget_fn is None:
            return
        try:
            snap = self._budget_fn() or {}
        except Exception:
            return  # budget view is advisory; never fail admission on it
        budget = snap.get("budget_bytes") or 0
        pinned = snap.get("pinned_bytes") or 0
        if budget > 0 and pinned >= budget:
            self._reject(
                tenant,
                "hbm_budget",
                t0,
                detail=f"pinned {pinned}B >= budget {budget}B",
            )
        if budget > 0 and estimated_bytes > 0 and (
            pinned + estimated_bytes > budget
        ):
            self._reject(
                tenant,
                "hbm_budget",
                t0,
                detail=(
                    f"estimated staging {estimated_bytes}B > budget "
                    f"{budget}B - pinned {pinned}B"
                ),
            )

    def _reject(self, tenant: str, reason: str, t0: float, detail=""):
        waited = time.monotonic() - t0
        _REJECTED.inc(reason=reason, tenant=tenant)
        _WAIT_SECONDS.observe(waited, tenant=tenant)
        raise AdmissionRejected(
            tenant,
            reason,
            queue_depth=self._waiting,
            waited_s=waited,
            detail=detail,
        )

    def queue_depth(self) -> int:
        """Live queue depth, lock-free (an int read is atomic in
        CPython; this is the advisory gate for the shared-scan window
        skip and the r16 controller — momentary staleness only costs a
        window that slept or skipped one arrival too early)."""
        return self._waiting

    def _release(self) -> None:
        with self._cv:
            self._active -= 1
            while self._heap and self._active < self._limit():
                w = heapq.heappop(self._heap)
                if w.abandoned:
                    continue
                w.granted = True
                self._waiting -= 1
                self._active += 1
                self._vclock = max(self._vclock, w.vtime)
            self._publish()
            self._cv.notify_all()

    def _publish(self) -> None:
        _QUEUE_DEPTH.set(self._waiting)
        _ACTIVE.set(self._active)

    def snapshot(self) -> dict:
        """Admission state for /statusz (the r10 health plane) and the
        soak harness — including queue-wait and lock-wait quantiles,
        the r13 contention signals at ~1k-client depth."""
        with self._cv:
            return {
                "active": self._active,
                "queue_depth": self._waiting,
                "max_concurrent": self._limit(),
                "max_queue": self._queue_cap(),
                "vclock": round(self._vclock, 6),
                "tenants": {
                    t: round(v, 6)
                    for t, v in sorted(self._tenant_vtime.items())
                },
                "wait_p50_ms": round(
                    _WAIT_SECONDS.agg_quantile(0.5) * 1e3, 3
                ),
                "wait_p99_ms": round(
                    _WAIT_SECONDS.agg_quantile(0.99) * 1e3, 3
                ),
                "lock_wait_p99_ms": round(
                    _LOCK_WAIT.quantile(0.99) * 1e3, 3
                ),
            }


# -- metadata staging-cost estimation (r13 satellite) ------------------------


def estimate_staging_bytes(table, columns=None) -> int:
    """A query's predicted HBM staging footprint from table METADATA:
    row count × encoded column widths, no data read.

    Width per column prefers the table's OBSERVED staged bytes-per-row
    (parallel/staging.OBSERVED_BPR, recorded at every staging insert —
    it reflects narrowing, f32 sketch staging, and int-dict codes);
    before any staging exists it falls back to the relation's raw host
    widths plus the 1-byte validity mask — deliberately conservative,
    since the check exists to refuse DOOMED cold stages."""
    from pixie_tpu.parallel.staging import OBSERVED_BPR
    from pixie_tpu.types import DataType

    stats = table.stats()
    rows = max(int(stats.num_rows), 0)
    if rows == 0:
        return 0
    bpr = OBSERVED_BPR.get(table.name)
    if bpr is None:
        widths = {
            DataType.BOOLEAN: 1,
            DataType.INT64: 8,
            DataType.FLOAT64: 8,
            DataType.STRING: 4,  # dictionary codes
            DataType.TIME64NS: 8,
            DataType.UINT128: 16,
        }
        names = set(columns) if columns else None
        bpr = 1.0  # validity mask
        for c in table.relation:
            if names is not None and c.name not in names:
                continue
            bpr += widths.get(c.data_type, 8)
    return int(rows * bpr)


def make_store_estimator(table_store):
    """table_name -> estimated staging bytes, over a TableStore — the
    callable QueryBroker(staging_estimator=...) wants. Unknown tables
    estimate 0 (never reject what we cannot see)."""

    def estimate(table_name: str) -> int:
        table = table_store.get_table(table_name)
        if table is None:
            return 0
        try:
            return estimate_staging_bytes(table)
        except Exception:
            return 0

    return estimate
