"""Distributed query tracing + self-telemetry (r11).

Covers the Dapper-style span tree end to end: context propagation across
threads and a real TCP transport reconnect (one trace_id, no duplicate
spans under replay/dedup), per-exec-node spans with row counts,
degraded-query span trees, the query_spans table round-trip through a
PxL query (the engine observing itself with its own engine), and the
disabled-path cost contract (no spans, no buffer growth).
"""

import json
import time
import urllib.request

import numpy as np
import pytest

from pixie_tpu.engine import Carnot
from pixie_tpu.exec.router import BridgeRouter
from pixie_tpu.table.row_batch import RowBatch
from pixie_tpu.table.table_store import TableStore
from pixie_tpu.types import DataType, Relation
from pixie_tpu.utils import faults, flags, metrics_registry, trace
from pixie_tpu.vizier import Agent, MessageBus, QueryBroker
from pixie_tpu.vizier import agent as agent_mod
from pixie_tpu.vizier.transport import (
    BusTransportServer,
    RemoteBus,
    RemoteRouter,
)

F, S, T = DataType.FLOAT64, DataType.STRING, DataType.TIME64NS
REL = Relation.of(("time_", T), ("service", S), ("latency", F))
TABLES = {"http_events": REL}
N_ROWS = 1000

AGG_QUERY = (
    "df = px.DataFrame(table='http_events')\n"
    "stats = df.groupby(['service']).agg(\n"
    "    total=('latency', px.sum), n=('latency', px.count))\n"
    "px.display(stats, 'out')\n"
)


@pytest.fixture(autouse=True)
def _clean_state():
    faults.reset()
    trace.set_enabled(True)
    trace.clear()
    yield
    faults.reset()
    trace.set_enabled(True)
    trace.clear()


@pytest.fixture
def flagset():
    saved = {}

    def set_(name, value):
        if name not in saved:
            saved[name] = flags.get(name)
        flags.set(name, value)

    yield set_
    for name, value in saved.items():
        flags.set(name, value)


def _make_store(seed_offset, n=N_ROWS):
    rng = np.random.default_rng(5 + seed_offset)
    ts = TableStore()
    t = ts.create_table("http_events", REL)
    t.write_pydict(
        {
            "time_": np.arange(n) + seed_offset,
            "service": rng.choice(["a", "b", "c"], n).astype(object),
            "latency": rng.integers(1, 100, n).astype(np.float64),
        }
    )
    t.stop()
    return ts


def _rows(res, name="out"):
    batches = [b for b in res.tables.get(name, []) if b.num_rows]
    if not batches:
        return {}
    return RowBatch.concat(batches).to_pydict()


def _wait(pred, timeout=15.0, msg="condition never held"):
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, msg
        time.sleep(0.02)


def _local_engine(n=N_ROWS):
    c = Carnot()
    rng = np.random.default_rng(5)
    t = c.table_store.create_table("http_events", REL)
    t.write_pydict(
        {
            "time_": np.arange(n),
            "service": rng.choice(["a", "b", "c"], n).astype(object),
            "latency": rng.integers(1, 100, n).astype(np.float64),
        }
    )
    t.compact()
    t.stop()
    return c


# -- span primitives ---------------------------------------------------------


def test_span_nesting_and_context():
    with trace.span("outer", trace_id="t1") as outer:
        assert trace.current() == ("t1", outer.span.span_id)
        with trace.span("inner") as inner:
            assert inner.span.trace_id == "t1"
            assert inner.span.parent_id == outer.span.span_id
    assert trace.current() is None
    spans = trace.drain()
    by_name = {s.name: s for s in spans}
    assert by_name["inner"].parent_id == by_name["outer"].span_id
    assert by_name["outer"].duration_ns >= by_name["inner"].duration_ns


def test_context_adoption_across_threads():
    import threading

    root = trace.begin("root", trace_id="tx")
    seen = []

    def worker():
        with trace.context_of(root):
            with trace.span("child"):
                seen.append(trace.current()[0])

    th = threading.Thread(target=worker)
    th.start()
    th.join()
    trace.finish(root)
    assert seen == ["tx"]
    child = [s for s in trace.drain() if s.name == "child"][0]
    assert child.parent_id == root.span_id


def test_build_tree_orphans_root_their_subtree():
    spans = [
        {"span_id": "a", "parent_id": "", "name": "root",
         "start_unix_ns": 1, "trace_id": "t"},
        {"span_id": "b", "parent_id": "a", "name": "child",
         "start_unix_ns": 2, "trace_id": "t"},
        {"span_id": "c", "parent_id": "missing", "name": "orphan",
         "start_unix_ns": 3, "trace_id": "t"},
    ]
    roots = trace.build_tree(spans)
    assert [r["name"] for r in roots] == ["root", "orphan"]
    assert roots[0]["children"][0]["name"] == "child"


def test_error_status_on_exception():
    with pytest.raises(ValueError):
        with trace.span("boom", trace_id="te"):
            raise ValueError("x")
    s = trace.drain()[0]
    assert s.status == "error"


# -- local engine: per-node exec spans + profile -----------------------------


def test_local_query_profile_and_exec_node_rows():
    c = _local_engine()
    res = c.execute_query(AGG_QUERY)
    assert res.trace_spans, "tracing on -> spans collected"
    names = {s["name"] for s in res.trace_spans}
    assert {"query", "compile", "fragment"} <= names
    # One trace, unique span ids.
    assert {s["trace_id"] for s in res.trace_spans} == {res.query_id}
    ids = [s["span_id"] for s in res.trace_spans]
    assert len(ids) == len(set(ids))
    # Per-exec-node spans carry the row counts the node actually saw.
    src = [s for s in res.trace_spans if s["name"].startswith("exec:MemorySource")]
    assert src and src[0]["attrs"]["rows_out"] == N_ROWS
    agg = [s for s in res.trace_spans if s["name"].startswith("exec:Agg")]
    assert agg and agg[0]["attrs"]["rows_in"] == N_ROWS
    assert agg[0]["attrs"]["rows_out"] == 3  # three services
    # Assembled profile: the root is the query span, fragment under it,
    # exec nodes under the fragment.
    prof = res.profile
    assert prof["trace_id"] == res.query_id
    assert [r["name"] for r in prof["roots"]] == ["query"]
    children = {c["name"] for c in prof["roots"][0]["children"]}
    assert "compile" in children and "fragment" in children
    frag = [c for c in prof["roots"][0]["children"] if c["name"] == "fragment"][0]
    assert any(c["name"].startswith("exec:") for c in frag["children"])


def test_tracing_disabled_no_spans_no_buffer():
    trace.set_enabled(False)
    c = _local_engine()
    res = c.execute_query(AGG_QUERY)
    assert res.trace_spans is None
    assert res.profile is None
    assert trace.buffered_count() == 0
    assert sum(_rows(res)["n"]) == N_ROWS  # query itself unaffected


# -- query_spans round-trip: the engine observes itself ----------------------


def test_query_spans_table_roundtrip_via_pxl():
    c = _local_engine()
    res = c.execute_query(AGG_QUERY)
    qid = res.query_id
    res2 = c.execute_query(
        "df = px.DataFrame(table='query_spans')\n"
        f"df = df[df.trace_id == '{qid}']\n"
        "df = df[['trace_id', 'name', 'duration_ns', 'status']]\n"
        "px.display(df, 'spans')\n"
    )
    d = res2.table("spans")
    assert set(d["trace_id"]) == {qid}
    assert "query" in d["name"] and "fragment" in d["name"]
    assert any(n.startswith("exec:") for n in d["name"])
    assert all(v >= 0 for v in d["duration_ns"])


def test_bundled_query_profile_script():
    c = _local_engine()
    res = c.execute_query(AGG_QUERY)
    from pixie_tpu.scripts.library import ScriptLibrary

    lib = ScriptLibrary()
    assert "px/query_profile" in lib.names()
    out = lib.run(c, "px/query_profile", {"trace_id": res.query_id})
    spans = _rows(out, "spans")
    assert set(spans["trace_id"]) == {res.query_id}
    phases = _rows(out, "phases")
    # The phase breakdown aggregates per span name: the root query span
    # dominates total time.
    by_name = dict(zip(phases["name"], phases["total_ns"]))
    assert by_name["query"] >= by_name["compile"]
    assert all(n >= 1 for n in phases["spans"])


def test_engine_metrics_table_roundtrip():
    c = _local_engine()
    # Touch a transport counter so the registry has a *_total sample even
    # in a process that never opened a transport connection.
    metrics_registry().counter("transport_dedup_dropped_total").inc(0)
    c.execute_query(AGG_QUERY)
    res = c.execute_query(
        "df = px.DataFrame(table='engine_metrics')\n"
        "df = df[['name', 'value', 'kind']]\n"
        "px.display(df, 'm')\n"
    )
    d = res.table("m")
    assert len(d["name"]) > 0
    # Registry counters are visible as rows (satellite: ad-hoc totals
    # ride the shared registry).
    assert any("_total" in n for n in d["name"])


def test_self_telemetry_connector_drains_periodically():
    from pixie_tpu.ingest import IngestCore, SelfTelemetrySourceConnector

    with trace.span("seed-span", trace_id="tconn"):
        pass
    core = IngestCore()
    store = TableStore()
    src = SelfTelemetrySourceConnector(interval_s=0.02)
    core.register_source(src)
    core.wire_to_table_store(store)
    core.run_as_thread()
    try:
        _wait(
            lambda: (store.get_table("query_spans").end_row_id() > 0),
            msg="spans never ingested",
        )
        _wait(
            lambda: (store.get_table("engine_metrics").end_row_id() > 0),
            msg="metrics never ingested",
        )
    finally:
        core.stop()
    cur = store.get_table("query_spans").cursor()
    rows = []
    while True:
        b = cur.next_batch()
        if b is None or cur.done():
            if b is not None:
                rows.append(b)
            break
        rows.append(b)
    got = RowBatch.concat([b for b in rows if b.num_rows]).to_pydict()
    assert "seed-span" in got["name"]


# -- broker path: cross-agent trace assembly ---------------------------------


@pytest.fixture
def bus_cluster(monkeypatch):
    monkeypatch.setattr(agent_mod, "HEARTBEAT_INTERVAL_S", 0.05)
    bus = MessageBus()
    router = BridgeRouter()
    broker = QueryBroker(bus, router, table_relations=TABLES)
    agents = [
        Agent("pem1", bus, router, table_store=_make_store(0)),
        Agent("pem2", bus, router, table_store=_make_store(10**6)),
        Agent("kelvin", bus, router, is_kelvin=True),
    ]
    for a in agents:
        a.start()
    _wait(
        lambda: len(broker.tracker.distributed_state().agents) >= 3,
        msg="agents never registered",
    )
    yield broker, agents
    broker.stop()
    for a in agents:
        a.stop()


def test_broker_trace_covers_every_agent(bus_cluster):
    """Acceptance: a single query produces ONE trace whose span tree
    covers broker, every participating agent, each exec node, and the
    degraded annotation joins on the same trace_id."""
    broker, _ = bus_cluster
    res = broker.execute_script(AGG_QUERY, timeout_s=30)
    assert res.degraded is None
    assert sum(_rows(res)["n"]) == 2 * N_ROWS
    spans = res.trace_spans
    assert spans
    assert {s["trace_id"] for s in spans} == {res.query_id}
    ids = [s["span_id"] for s in spans]
    assert len(ids) == len(set(ids)), "in-process merge must dedup"
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    assert "query" in by_name and by_name["query"][0]["instance"] == "broker"
    # Every participating agent contributed an execute span parented to
    # the broker's root.
    execs = {s["instance"]: s for s in by_name.get("agent.execute", [])}
    assert {"pem1", "pem2", "kelvin"} <= set(execs)
    root = by_name["query"][0]
    assert all(s["parent_id"] == root["span_id"] for s in execs.values())
    # Exec-node spans from the PEM fragments carry their shard's rows.
    src_rows = [
        s["attrs"]["rows_out"]
        for s in spans
        if s["name"].startswith("exec:MemorySource")
        and s["instance"] in ("pem1", "pem2")
    ]
    assert sorted(src_rows) == [N_ROWS, N_ROWS]
    prof = res.profile
    assert sorted(prof["agents"]) == ["kelvin", "pem1", "pem2"]
    assert prof["roots"][0]["name"] == "query"


def test_degraded_query_span_tree(bus_cluster):
    """An agent erroring mid-query still yields a coherent (partial)
    span tree: the dead agent's execute span arrives with status=error,
    the annotation and events carry the trace_id."""
    broker, _ = bus_cluster
    faults.arm("agent.execute@pem2", count=1)
    events = []
    res = broker.execute_script(
        AGG_QUERY, timeout_s=30, on_event=lambda qid, ev: events.append(ev)
    )
    assert res.degraded is not None
    assert res.degraded["trace_id"] == res.query_id
    assert all(ev["trace_id"] == res.query_id for ev in events)
    spans = res.trace_spans
    execs = {
        s["instance"]: s for s in spans if s["name"] == "agent.execute"
    }
    assert execs["pem2"]["status"] == "error"
    assert execs["pem1"]["status"] == "ok"
    root = [s for s in spans if s["name"] == "query"][0]
    assert root["status"] == "degraded"
    prof = res.profile
    assert prof["degraded"]["error_agents"] == ["pem2"]


def test_otel_export_of_query_trace(bus_cluster, flagset):
    broker, _ = bus_cluster
    flagset("trace_otel_export", True)
    payloads = []
    broker.otel_exporter = payloads.append
    res = broker.execute_script(AGG_QUERY, timeout_s=30)
    assert res.degraded is None
    assert len(payloads) == 1
    scope_spans = payloads[0]["resourceSpans"][0]["scopeSpans"][0]["spans"]
    assert {s["traceId"] for s in scope_spans} == {res.query_id}
    assert any(s["name"] == "agent.execute" for s in scope_spans)
    for s in scope_spans:
        assert int(s["endTimeUnixNano"]) >= int(s["startTimeUnixNano"])


# -- transport: reconnect/replay keeps one trace, no duplicate spans ---------


@pytest.fixture
def tcp_cluster(flagset, monkeypatch):
    """Broker + kelvin on a local bus; one PEM over real TCP (spans from
    the PEM cross the wire on fragment_done)."""
    flagset("agent_backoff_initial_s", 0.01)
    flagset("agent_backoff_max_s", 0.1)
    monkeypatch.setattr(agent_mod, "HEARTBEAT_INTERVAL_S", 0.05)
    bus = MessageBus()
    router = BridgeRouter()
    server = BusTransportServer(bus, router)
    broker = QueryBroker(bus, router, table_relations=TABLES)
    kelvin = Agent("kelvin", bus, router, is_kelvin=True)
    kelvin.start()
    rbus = RemoteBus(server.address)
    rrouter = RemoteRouter(rbus)
    pem = Agent("pem1", rbus, rrouter, table_store=_make_store(0))
    pem.start()
    _wait(
        lambda: len(broker.tracker.distributed_state().agents) >= 2,
        msg="agents never registered",
    )
    yield broker, rbus
    broker.stop()
    pem.stop()
    kelvin.stop()
    rbus.close()
    server.stop()


def _ack_spans(spans):
    return [s for s in spans if s.name == "transport.ack"]


def test_trace_survives_transport_reconnect_exactly_once(tcp_cluster):
    """Span-context propagation across a transport reconnect: the query
    keeps ONE trace_id, no span is duplicated under replay/dedup, and
    each windowed frame yields at most one ack-latency span."""
    broker, rbus = tcp_cluster
    # Kill the data-plane socket before a frame hits the wire: the send
    # path redials, replays the window, and the server dedups.
    faults.arm("transport.send_data", count=1)
    res = broker.execute_script(AGG_QUERY, timeout_s=30)
    assert res.degraded is None
    assert sum(_rows(res)["n"]) == N_ROWS
    spans = res.trace_spans
    assert {s["trace_id"] for s in spans} == {res.query_id}
    ids = [s["span_id"] for s in spans]
    assert len(ids) == len(set(ids)), "replayed frames must not dup spans"
    assert any(
        s["name"] == "agent.execute" and s["instance"] == "pem1"
        for s in spans
    ), "the remote agent's spans crossed the wire"
    # Ack spans: at most one per (plane, seq) even across the reconnect
    # replay (watermark-trimmed and re-acked entries release once). Wait
    # for the data window to fully drain so every entry has released.
    _wait(
        lambda: rbus.window_depths()["data"][0] == 0,
        msg="data window never drained",
    )
    acks = _ack_spans(trace.drain())
    assert acks, "no ack-latency spans emitted"
    keys = [(s.attrs["plane"], s.attrs["seq"]) for s in acks]
    assert len(keys) == len(set(keys)), "duplicate ack spans under replay"


def test_replay_dup_does_not_duplicate_ack_spans(tcp_cluster):
    """Even when the reconnect replay re-sends frames the server already
    applied (transport.replay_dup), each window entry releases exactly
    once: ack spans stay unique per (plane, seq)."""
    broker, rbus = tcp_cluster
    faults.arm("transport.send_data", count=1)
    faults.arm("transport.replay_dup", count=1)
    res = broker.execute_script(AGG_QUERY, timeout_s=30)
    assert res.degraded is None
    assert sum(_rows(res)["n"]) == N_ROWS
    _wait(
        lambda: rbus.window_depths()["data"][0] == 0,
        msg="data window never drained",
    )
    acks = _ack_spans(trace.drain())
    assert acks
    keys = [(s.attrs["plane"], s.attrs["seq"]) for s in acks]
    assert len(keys) == len(set(keys))


def test_ack_latency_histogram_populates(tcp_cluster):
    broker, _ = tcp_cluster
    h = metrics_registry().histogram("transport_ack_latency_seconds")
    before = h.value(plane="data")
    res = broker.execute_script(AGG_QUERY, timeout_s=30)
    assert res.degraded is None
    _wait(
        lambda: h.value(plane="data") > before,
        msg="no data-plane ack latency observed",
    )
    assert h.quantile(0.5, plane="data") >= 0.0


def test_device_phase_spans_in_trace():
    """Acceptance: a query offloaded to the device mesh contributes a
    device.execute span plus per-phase staging children (COLD_PROFILE
    keys folded into spans) under the same trace."""
    import jax
    from jax.sharding import Mesh

    from pixie_tpu.parallel import MeshExecutor

    mesh = Mesh(np.array(jax.devices("cpu")), ("d",))
    c = Carnot(device_executor=MeshExecutor(mesh=mesh, block_rows=256))
    rng = np.random.default_rng(5)
    t = c.table_store.create_table("http_events", REL)
    t.write_pydict(
        {
            "time_": np.arange(N_ROWS),
            "service": rng.choice(["a", "b", "c"], N_ROWS).astype(object),
            "latency": rng.integers(1, 100, N_ROWS).astype(np.float64),
        }
    )
    t.compact()
    t.stop()
    res = c.execute_query(AGG_QUERY)
    assert sum(_rows(res)["n"]) == N_ROWS
    names = {s["name"] for s in res.trace_spans}
    assert "device.execute" in names, names
    assert any(n.startswith("device.") and n != "device.execute"
               for n in names), names
    dev = [s for s in res.trace_spans if s["name"] == "device.execute"][0]
    assert dev["trace_id"] == res.query_id
    assert "program_key" in dev["attrs"]
    # The executor recorded this shape's fold latency for the health plane.
    assert c.device_executor.fold_latency_snapshot()


# -- health plane: fold-latency percentiles ----------------------------------


def test_fold_latency_snapshot_percentiles():
    import jax
    from jax.sharding import Mesh

    from pixie_tpu.parallel import MeshExecutor

    mesh = Mesh(np.array(jax.devices("cpu")), ("d",))
    dev = MeshExecutor(mesh=mesh, block_rows=1024)
    for ms in range(1, 101):
        dev._record_fold_latency("key_a", float(ms))
    snap = dev.fold_latency_snapshot()
    assert snap["key_a"]["n"] == 100
    assert 45 <= snap["key_a"]["p50_ms"] <= 55
    assert snap["key_a"]["p99_ms"] >= 95
    health = dev.health_snapshot()
    assert health["fold_latency"]["key_a"]["n"] == 100


def test_tracker_fold_latency_view_and_statusz(bus_cluster, monkeypatch):
    """Heartbeat-carried fold-latency percentiles aggregate in the
    tracker and surface on /statusz."""
    broker, agents = bus_cluster

    class DevStub:
        def try_execute_fragment(self, *a, **k):
            return None

        def health_snapshot(self):
            return {
                "breaker": {},
                "breaker_open": [],
                "staging_depth": 0,
                "last_fold_ms": 2.0,
                "fold_latency": {"shape_x": {"p50_ms": 2.0,
                                             "p99_ms": 5.0, "n": 42}},
            }

    agents[0].carnot.device_executor = DevStub()
    # One query, so that the broker's latency histogram has a series.
    assert broker.execute_script(AGG_QUERY, timeout_s=30).degraded is None
    _wait(
        lambda: "shape_x" in broker.tracker.fold_latency_view(),
        msg="fold latency never reached the tracker",
    )
    view = broker.tracker.fold_latency_view()
    assert view["shape_x"]["pem1"]["p99_ms"] == 5.0
    srv = broker.start_health_server()
    host, port = srv.address[:2]
    try:
        status = json.load(
            urllib.request.urlopen(f"http://{host}:{port}/statusz")
        )
        assert status["status"]["fold_latency"]["shape_x"]["pem1"]["n"] == 42
        # /metrics carries the registry (histograms included).
        text = (
            urllib.request.urlopen(f"http://{host}:{port}/metrics")
            .read()
            .decode()
        )
        assert "broker_queries_total" in text
        assert "broker_query_seconds_bucket" in text
    finally:
        srv.stop()
