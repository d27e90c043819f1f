"""Lightweight distributed query tracing (r11).

Ref posture: Dapper (Sigelman et al., 2010) — per-query trace trees of
spans with (trace_id, span_id, parent_id) propagated across process
boundaries — exported in the OpenTelemetry data model, and dogfooded the
way the reference lands `stirling_error`/`probe_status` into its own
TableStore: finished spans are buffered here and periodically drained
into the node's `query_spans` table (ingest/self_telemetry.py) so PxL
scripts can query the engine about itself.

Design contract (mirrors utils/faults.py):

- **Near-zero cost when disabled.** Call sites gate on the module-level
  ``ACTIVE`` bool::

      if trace.ACTIVE:
          with trace.span("compile"): ...

  or call ``span()``/``record()`` directly — every entry point re-checks
  ``ACTIVE`` and returns a no-op immediately. The microbench
  (tools/microbench_fault_overhead.py ``trace_overhead`` key) holds the
  disabled path to <1% of the warm agg path and the transport RTT.

- **The query_id IS the trace_id.** The broker roots each query's trace
  at its query_id, so spans, inline degradation events, and the final
  ``degraded`` annotation are joinable on one key.

- **Propagation is explicit across processes, ambient within a
  thread.** A thread-local context stack makes nested ``span()`` calls
  parent automatically; crossing a boundary (broker → agent message,
  transport frame) carries ``{"trace_id", "span_id"}`` explicitly and
  the far side re-enters the context with ``context(trace_id, span_id)``.

- **One clock with the device.** Every with-block ``span()`` is also a
  ``jax.profiler.TraceAnnotation`` of the span's own name, entered
  whether or not tracing is on (~0.4 us a span with no profiler
  running), so a profiler trace shows the program's spans on the
  device's clock beside the device's operations.

- **Finished spans are data.** ``Span.to_dict()`` is wire-encodable
  (str/int/dict only); agents ship their spans back on ``fragment_done``
  and the broker merges by span_id (in-process clusters share this
  module's buffer, so dedup-by-id keeps the merge exact).
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
import uuid
from typing import Any, Optional

from jax.profiler import TraceAnnotation

from pixie_tpu.utils.config import define_flag, flags

define_flag(
    "query_tracing",
    True,
    help_="Distributed query tracing: every query gets a Dapper-style "
    "span tree covering broker, each participating agent, each exec "
    "node, and the device offload's phases, assembled in "
    "QueryResult.profile and landed in the node's own query_spans table "
    "(utils/trace.py). Off = spans are never recorded (<1% residual "
    "overhead, gated by tools/microbench_fault_overhead.py); with-block "
    "spans stay profiler annotations.",
)
define_flag(
    "trace_buffer_cap",
    8192,
    help_="Finished-span ring buffer capacity per process; the oldest "
    "spans are evicted when self-telemetry ingestion falls behind.",
)
define_flag(
    "trace_otel_export",
    False,
    help_="Export each query's finished spans as an OTLP resourceSpans "
    "payload through the engine's pluggable OTel exporter (the "
    "exec/otel_sink_node.py path) in addition to the query_spans table.",
)
define_flag(
    "resource_attribution",
    True,
    help_="Continuous resource attribution (r15): threads executing a "
    "query carry an ambient (query_id, tenant, phase) label, so host "
    "profiler stack samples, device dispatch records "
    "(parallel/profiler.py), and HBM usage snapshots attribute CPU, "
    "device time, and bytes to the query/tenant that caused them. "
    "Off = attribution contexts and recorders are never entered (<1% "
    "residual cost, gated by tools/microbench_fault_overhead.py "
    "``profiler_overhead``).",
)

# Fast gate read by every call site (one attribute load + branch when
# tracing is off). Synced with the ``query_tracing`` flag at import and by
# set_enabled()/refresh().
ACTIVE = False
# Resource-attribution gate (r15, flag ``resource_attribution``):
# identical posture to ACTIVE — every attribution entry point re-checks
# it and becomes a no-op immediately when off.
ATTR_ACTIVE = False

_BUF_LOCK = threading.Lock()
_FINISHED: "collections.deque[Span]" = collections.deque(
    maxlen=flags.trace_buffer_cap
)
_tls = threading.local()


def set_enabled(on: bool) -> None:
    """Flip tracing at runtime (also updates the ``query_tracing`` flag
    so flag introspection stays truthful)."""
    global ACTIVE
    ACTIVE = bool(on)
    flags.set("query_tracing", bool(on))


def set_attribution_enabled(on: bool) -> None:
    """Flip resource attribution at runtime (also updates the
    ``resource_attribution`` flag, and the parallel/profiler.py
    recorders' gate syncs from the same flag on their next refresh)."""
    global ATTR_ACTIVE
    ATTR_ACTIVE = bool(on)
    flags.set("resource_attribution", bool(on))


def refresh() -> None:
    """Re-read the ``query_tracing``/``resource_attribution`` flags into
    the ACTIVE/ATTR_ACTIVE gates."""
    global ACTIVE, ATTR_ACTIVE
    ACTIVE = bool(flags.query_tracing)
    ATTR_ACTIVE = bool(flags.resource_attribution)


def new_id() -> str:
    return uuid.uuid4().hex[:16]


@dataclasses.dataclass
class Span:
    """One finished (or in-flight) operation in a trace tree."""

    trace_id: str
    span_id: str
    parent_id: str  # "" at the root
    name: str
    start_unix_ns: int
    duration_ns: int = 0
    status: str = "ok"
    instance: str = ""
    attrs: dict = dataclasses.field(default_factory=dict)
    _start_pc_ns: int = 0  # perf_counter origin (not serialized)
    _finished: bool = False

    def to_dict(self) -> dict:
        """Wire-encodable form (plain str/int values + a str->scalar
        attrs map) — rides bus messages and transport frames as-is."""
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start_unix_ns": self.start_unix_ns,
            "duration_ns": self.duration_ns,
            "status": self.status,
            "instance": self.instance,
            "attrs": dict(self.attrs),
        }

    @staticmethod
    def from_dict(d: dict) -> "Span":
        return Span(
            trace_id=str(d.get("trace_id", "")),
            span_id=str(d.get("span_id", "")),
            parent_id=str(d.get("parent_id", "")),
            name=str(d.get("name", "")),
            start_unix_ns=int(d.get("start_unix_ns", 0)),
            duration_ns=int(d.get("duration_ns", 0)),
            status=str(d.get("status", "ok")),
            instance=str(d.get("instance", "")),
            attrs=dict(d.get("attrs") or {}),
        )


# -- thread-local context ----------------------------------------------------
def current() -> Optional[tuple[str, str]]:
    """(trace_id, span_id) of the innermost active span on this thread,
    or None outside any trace."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


def _push(ctx: tuple[str, str]) -> None:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    stack.append(ctx)


def _pop() -> None:
    stack = getattr(_tls, "stack", None)
    if stack:
        stack.pop()


class context:
    """Adopt an externally-propagated span context on this thread (the
    agent re-enters the broker's root span; a worker thread re-enters
    its query's fragment span). No-op with a None/empty context."""

    def __init__(self, trace_id: Optional[str], span_id: str = ""):
        self._ctx = (trace_id, span_id) if trace_id else None

    def __enter__(self):
        if self._ctx is not None:
            _push(self._ctx)
        return self

    def __exit__(self, *exc):
        if self._ctx is not None:
            _pop()
        return False


def context_of(span: "Optional[Span]") -> context:
    if span is None:
        return context(None)
    return context(span.trace_id, span.span_id)


# -- resource attribution (r15) ----------------------------------------------
# Thread ident -> (query_id, tenant, phase) for every thread currently
# doing work on a query's behalf. Unlike the span context stack (which is
# thread-LOCAL, invisible to other threads), this registry is readable
# ACROSS threads: the host profiler samples ``sys._current_frames()``,
# which is keyed by thread ident, and labels each sampled stack with the
# attribution the owning thread declared. Plain-dict assignment/removal
# is GIL-atomic, so readers take consistent snapshots without a lock.
_THREAD_ATTR: dict[int, tuple[str, str, str]] = {}


class attribution:
    """Declare that work on this thread — until exit — runs on behalf of
    ``(query_id, tenant, phase)``. Nested scopes restore the outer
    attribution on exit (a broker thread executing a local telemetry
    query inside an SLO evaluation re-attributes just that inner span of
    work). No-op when ``resource_attribution`` is off or query_id is
    empty."""

    __slots__ = ("_ctx", "_ident", "_prev")

    def __init__(self, query_id: Optional[str], tenant: str = "default",
                 phase: str = ""):
        self._ctx = (
            (str(query_id), str(tenant or "default"), str(phase))
            if ATTR_ACTIVE and query_id
            else None
        )

    def __enter__(self):
        if self._ctx is not None:
            self._ident = threading.get_ident()
            self._prev = _THREAD_ATTR.get(self._ident)
            _THREAD_ATTR[self._ident] = self._ctx
        return self

    def __exit__(self, *exc):
        if self._ctx is not None:
            if self._prev is None:
                _THREAD_ATTR.pop(self._ident, None)
            else:
                _THREAD_ATTR[self._ident] = self._prev
        return False


def current_attribution() -> Optional[tuple[str, str, str]]:
    """(query_id, tenant, phase) this thread is working for, or None."""
    if not ATTR_ACTIVE:
        return None
    return _THREAD_ATTR.get(threading.get_ident())


def thread_attributions() -> dict[int, tuple[str, str, str]]:
    """Snapshot of every attributed thread: ident -> (query_id, tenant,
    phase). The host profiler joins this against sys._current_frames()."""
    if not ATTR_ACTIVE:
        return {}
    return dict(_THREAD_ATTR)


def attributed(fn, phase: Optional[str] = None):
    """Wrap ``fn`` for submission to a worker thread/pool so the worker
    runs under the SUBMITTING thread's span context and resource
    attribution — the explicit cross-thread propagation rule (r11) now
    covering attribution too: pack/encode/compile workers doing a
    query's work show up in stack samples labeled with that query.
    ``phase`` overrides the attribution phase for the worker ("pack",
    "compile"). Returns ``fn`` unchanged when there is nothing to
    propagate."""
    if not (ACTIVE or ATTR_ACTIVE):
        return fn
    tctx = current()
    attr = current_attribution()
    if tctx is None and attr is None:
        return fn

    def run(*args, **kwargs):
        if tctx is not None:
            _push(tctx)
        scope = None
        if attr is not None:
            scope = attribution(
                attr[0], attr[1], attr[2] if phase is None else phase
            )
            scope.__enter__()
        try:
            return fn(*args, **kwargs)
        finally:
            if scope is not None:
                scope.__exit__(None, None, None)
            if tctx is not None:
                _pop()

    return run


# -- span lifecycle ----------------------------------------------------------
def begin(
    name: str,
    trace_id: Optional[str] = None,
    parent_id: Optional[str] = None,
    instance: str = "",
    attrs: Optional[dict] = None,
) -> Optional[Span]:
    """Start a span WITHOUT making it ambient (explicit-parent style for
    long scopes where a with-block is awkward, e.g. the broker's root
    span). Returns None when tracing is off; pair with ``finish()``."""
    if not ACTIVE:
        return None
    cur = current()
    if trace_id is None:
        trace_id = cur[0] if cur else new_id()
    if parent_id is None:
        parent_id = cur[1] if cur else ""
    s = Span(
        trace_id=trace_id,
        span_id=new_id(),
        parent_id=parent_id,
        name=name,
        start_unix_ns=time.time_ns(),
        instance=instance,
        attrs=dict(attrs or {}),
    )
    s._start_pc_ns = time.perf_counter_ns()
    return s


def finish(
    span: Optional[Span],
    status: Optional[str] = None,
    attrs: Optional[dict] = None,
) -> None:
    """Stamp the duration and buffer a span started with ``begin()``.
    Idempotent; None-safe (the disabled path passes None through)."""
    if span is None or span._finished:
        return
    span._finished = True
    span.duration_ns = time.perf_counter_ns() - span._start_pc_ns
    if status is not None:
        span.status = status
    if attrs:
        span.attrs.update(attrs)
    _record(span)


class span:
    """``with trace.span("compile"): ...`` — an ambient child span: nested
    spans on this thread parent to it automatically. ``.set(k=v)`` adds
    attributes; an exception propagating out marks status=error. The
    block is a profiler annotation of the same name even with tracing
    off."""

    def __init__(
        self,
        name: str,
        trace_id: Optional[str] = None,
        parent_id: Optional[str] = None,
        instance: str = "",
        attrs: Optional[dict] = None,
    ):
        self._name = name
        self._trace_id = trace_id
        self._parent_id = parent_id
        self._instance = instance
        self._attrs = attrs
        self.span: Optional[Span] = None
        self._annotation = TraceAnnotation(name)

    def __enter__(self):
        self._annotation.__enter__()
        self.span = begin(
            self._name,
            trace_id=self._trace_id,
            parent_id=self._parent_id,
            instance=self._instance,
            attrs=self._attrs,
        )
        if self.span is not None:
            _push((self.span.trace_id, self.span.span_id))
        return self

    def set(self, **attrs) -> None:
        if self.span is not None:
            self.span.attrs.update(attrs)

    def __exit__(self, exc_type, exc, tb):
        if self.span is not None:
            _pop()
            finish(self.span, status="error" if exc_type else None)
        self._annotation.__exit__(exc_type, exc, tb)
        return False


def record(
    name: str,
    duration_ns: int,
    trace_id: Optional[str] = None,
    parent_id: Optional[str] = None,
    start_unix_ns: Optional[int] = None,
    status: str = "ok",
    instance: str = "",
    attrs: Optional[dict] = None,
) -> Optional[Span]:
    """Buffer an already-measured span (exec-node stats, transport ack
    latencies). Inherits the ambient context for
    missing trace/parent ids; drops the span when tracing is off OR no
    trace context is resolvable (orphan phases outside any query)."""
    if not ACTIVE:
        return None
    cur = current()
    if trace_id is None:
        if cur is None:
            return None
        trace_id = cur[0]
    if parent_id is None:
        parent_id = cur[1] if cur else ""
    if start_unix_ns is None:
        start_unix_ns = time.time_ns() - int(duration_ns)
    s = Span(
        trace_id=trace_id,
        span_id=new_id(),
        parent_id=parent_id,
        name=name,
        start_unix_ns=start_unix_ns,
        duration_ns=int(duration_ns),
        status=status,
        instance=instance,
        attrs=dict(attrs or {}),
    )
    s._finished = True
    _record(s)
    return s


def _record(s: Span) -> None:
    with _BUF_LOCK:
        _FINISHED.append(s)


# -- buffer access -----------------------------------------------------------
def drain() -> list[Span]:
    """Remove and return every buffered finished span (the self-telemetry
    connector's consumption path — single consumer per process)."""
    with _BUF_LOCK:
        out = list(_FINISHED)
        _FINISHED.clear()
    return out


def spans_for(trace_id: str) -> list[Span]:
    """Copies of the buffered spans belonging to one trace (the buffer
    keeps them for self-telemetry ingestion)."""
    with _BUF_LOCK:
        return [s for s in _FINISHED if s.trace_id == trace_id]


def buffered_count() -> int:
    with _BUF_LOCK:
        return len(_FINISHED)


def clear() -> None:
    """Drop all buffered spans (tests)."""
    with _BUF_LOCK:
        _FINISHED.clear()


# -- profile assembly --------------------------------------------------------
def build_tree(spans: "list[dict | Span]") -> list[dict]:
    """Assemble span dicts into a parent->children forest, children sorted
    by start time. Unknown parents (dropped/evicted spans) root their
    subtree so a degraded trace still renders."""
    nodes: dict[str, dict] = {}
    ordered = []
    for s in spans:
        d = dict(s.to_dict() if isinstance(s, Span) else s)
        d["children"] = []
        prev = nodes.get(d["span_id"])
        if prev is None:
            nodes[d["span_id"]] = d
            ordered.append(d)
    roots = []
    for d in ordered:
        parent = nodes.get(d["parent_id"]) if d["parent_id"] else None
        if parent is None or parent is d:
            roots.append(d)
        else:
            parent["children"].append(d)
    for d in ordered:
        d["children"].sort(key=lambda c: c["start_unix_ns"])
    roots.sort(key=lambda c: c["start_unix_ns"])
    return roots


def spans_to_otel(spans: "list[dict | Span]", service: str = "pixie_tpu"):
    """OTLP/JSON resourceSpans payload for a span list — same data model
    the exec/otel_sink_node.py sink emits, so any exporter accepting its
    payloads accepts these."""
    from pixie_tpu.exec.otel_sink_node import _attr_list

    out = []
    for s in spans:
        d = s.to_dict() if isinstance(s, Span) else s
        out.append(
            {
                "name": d["name"],
                "traceId": d["trace_id"],
                "spanId": d["span_id"],
                "parentSpanId": d["parent_id"],
                "startTimeUnixNano": str(int(d["start_unix_ns"])),
                "endTimeUnixNano": str(
                    int(d["start_unix_ns"]) + int(d["duration_ns"])
                ),
                "attributes": _attr_list(
                    list(dict(d.get("attrs") or {}).items())
                    + [("status", d.get("status", "ok")),
                       ("instance", d.get("instance", ""))]
                ),
            }
        )
    return {
        "resourceSpans": [
            {
                "resource": {
                    "attributes": _attr_list([("service.name", service)])
                },
                "scopeSpans": [{"spans": out}],
            }
        ]
    }


refresh()
