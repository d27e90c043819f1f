"""The program's spans on the profiler's clock: every with-block span is
a jax.profiler annotation too, the offload's phases parent to its
device.execute span, the span count of a query does not grow with the
table's pushes or the stream's windows, and background compiles are
visible through a public count."""

import collections
import concurrent.futures
import threading
import time

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from pixie_tpu.engine import Carnot
from pixie_tpu.parallel import MeshExecutor, pipeline
from pixie_tpu.parallel.staging import reset_cold_profile
from pixie_tpu.types import DataType, Relation
from pixie_tpu.utils import flags, metrics_registry, trace
from pixie_tpu.utils.metrics import Gauge

REL = Relation.of(
    ("time_", DataType.TIME64NS),
    ("service", DataType.STRING),
    ("latency", DataType.FLOAT64),
)
PUSH_ROWS = 20
# svc_let's shape: a px.bin window key beside the service, over a table
# of uncompacted pushes.
QUERY = (
    "df = px.DataFrame(table='http_events')\n"
    "df.timestamp = px.bin(df.time_, 100)\n"
    "df = df[df.service != '']\n"
    "df = df.groupby(['service', 'timestamp']).agg(\n"
    "    n=('latency', px.count), total=('latency', px.sum))\n"
    "px.display(df, 'out')\n"
)


@pytest.fixture(autouse=True)
def _clean_state():
    saved = flags.get("streaming_window_rows")
    trace.set_enabled(True)
    trace.clear()
    reset_cold_profile()
    yield
    flags.set("streaming_window_rows", saved)
    trace.set_enabled(True)
    trace.clear()
    reset_cold_profile()


def _engine(pushes: int) -> Carnot:
    mesh = Mesh(np.array(jax.devices("cpu")), ("d",))
    c = Carnot(device_executor=MeshExecutor(mesh=mesh, block_rows=256))
    t = c.table_store.create_table("http_events", REL)
    rng = np.random.default_rng(pushes)
    for k in range(pushes):
        lo = k * PUSH_ROWS
        t.write_pydict(
            {
                "time_": np.arange(lo, lo + PUSH_ROWS),
                "service": rng.choice(["a", "b", "c"], PUSH_ROWS).astype(object),
                "latency": rng.integers(1, 100, PUSH_ROWS).astype(np.float64),
            }
        )
    return c


def _offloaded(c: Carnot, res) -> None:
    ex = c.device_executor
    assert not ex.fallback_errors and not ex.stream_fallback_errors
    n = sum(int(np.sum(b.to_pydict()["n"])) for b in res.tables["out"])
    assert n == c.table_store.get_table("http_events").end_row_id()


def test_span_count_does_not_grow_with_pushes_or_windows():
    counts = {}
    for pushes in (30, 300):
        # 300 pushes stream as several windows, 30 as one.
        flags.set("streaming_window_rows", 1024)
        c = _engine(pushes)
        res = c.execute_query(QUERY)
        _offloaded(c, res)
        assert reset_cold_profile()["read_batches"] >= 2 * pushes
        counts[pushes] = collections.Counter(s["name"] for s in res.trace_spans)
        plan = [s for s in res.trace_spans if s["name"] == "device.plan_keys"]
        # Every push is walked, each read resuming at its own segment;
        # the few rows are evaluated in one chunk.
        assert plan[0]["attrs"] == {
            "batches": pushes, "visits": pushes, "evals": 1, "cached": False
        }
    assert counts[30] == counts[300]
    for name in ("query", "compile", "fragment", "device.execute",
                 "device.plan_keys", "device.stage", "device.finalize", "exec"):
        assert counts[30][name] == 1, (name, counts[30])


def test_device_phases_parent_to_device_execute():
    c = _engine(30)
    res = c.execute_query(QUERY)
    _offloaded(c, res)
    spans = {s["span_id"]: s for s in res.trace_spans}
    (ex,) = [s for s in spans.values() if s["name"] == "device.execute"]
    assert ex["attrs"]["offloaded"] is True and "program_key" in ex["attrs"]
    assert spans[ex["parent_id"]]["name"] == "fragment"
    phases = [
        s for s in spans.values()
        if s["name"].startswith("device.") and s is not ex
    ]
    assert {s["name"] for s in phases} >= {
        "device.plan_keys", "device.read_columns", "device.stage",
        "device.finalize",
    }
    for s in phases:
        # Each phase lies under the offload, directly or in another phase.
        p = spans[s["parent_id"]]
        while p is not ex:
            assert p["name"].startswith("device."), (s["name"], p["name"])
            p = spans[p["parent_id"]]
    top = {s["name"] for s in phases if s["parent_id"] == ex["span_id"]}
    assert {"device.plan_keys", "device.stage", "device.finalize"} <= top
    (exec_,) = [s for s in spans.values() if s["name"] == "exec"]
    assert exec_["parent_id"] == ex["parent_id"]


def test_tracing_off_keeps_annotations_and_cold_profile(tmp_path):
    from benchmark import xtrace

    c = _engine(30)
    c.execute_query(QUERY)  # warm: compiles outside the profile
    trace.set_enabled(False)
    trace.clear()
    reset_cold_profile()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(xtrace.WINDOW):
            # A new table version: the refresh plans keys and stages anew.
            c.table_store.get_table("http_events").write_pydict(
                {"time_": [10**6], "service": ["a"], "latency": [1.0]}
            )
            with jax.profiler.TraceAnnotation(xtrace.QUERY):
                res = c.execute_query(QUERY)
    finally:
        jax.profiler.stop_trace()
    _offloaded(c, res)
    assert trace.buffered_count() == 0 and res.trace_spans is None
    prof = reset_cold_profile()
    assert prof["plan_keys"] > 0 and prof["read_batches"] >= 31
    s = xtrace.load(str(tmp_path))
    (q,) = [h for h in s.host if h[0] == xtrace.QUERY]
    inside = {
        h[0] for h in s.host if h[3] == q[3] and q[1] <= h[1] and h[2] <= q[2]
    }
    assert {"query", "compile", "fragment", "device.execute",
            "device.plan_keys", "device.finalize", "exec"} <= inside


def test_pending_compiles_is_public():
    mesh = Mesh(np.array(jax.devices("cpu")), ("d",))
    ex = MeshExecutor(mesh=mesh, block_rows=256)
    fut = concurrent.futures.Future()
    ex._aot_futures["sig"] = fut
    assert ex.pending_compiles() == 1
    assert ex.health_snapshot()["staging_depth"] == 1
    fut.set_result(None)
    assert ex.pending_compiles() == 0
    assert ex.health_snapshot()["staging_depth"] == 0


def test_aot_pending_gauge_counts_running_compiles(monkeypatch):
    gauge = Gauge("device_aot_pending", "a fresh series for this test")
    monkeypatch.setattr(pipeline, "_AOT_PENDING", gauge)
    mesh = Mesh(np.array(jax.devices("cpu")), ("d",))
    ex = MeshExecutor(mesh=mesh, block_rows=256)
    release = threading.Event()
    monkeypatch.setattr(ex, "_aot_lower_compile", lambda p, a: release.wait(60) and None)
    fut = ex._aot_compile_async("sig", None, ())
    assert gauge.value() == 1 and ex.pending_compiles() == 1
    release.set()
    fut.result(timeout=60)
    deadline = time.monotonic() + 10
    while gauge.value() and time.monotonic() < deadline:
        time.sleep(0.01)  # the done-callback runs just after the result
    assert gauge.value() == 0 and ex.pending_compiles() == 0
    assert "device_aot_pending" in metrics_registry().render_text()
