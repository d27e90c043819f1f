"""Each cell's path at a tiny size on the CPU, answers compared with the
numpy reference; the control and planted faults must come out not
correct."""

from __future__ import annotations

import math

import numpy as np
import pytest
from bench_tiny import cpu_run, make_root, tiny_root  # noqa: F401

CELLS = ("http_node.history", "http_node.live")


@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearsal_is_correct(tiny_root, workload):
    # Live: past a 2 s window boundary of event time, so that refreshes move.
    out = cpu_run(tiny_root, workload, seconds=3.2 if workload.endswith(".live") else 1.0)
    res = out.result
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["attempted"] == len(out.records) > 0
    assert res["checked_answers"] > 0
    assert list(res)[-1] == "checks"
    assert "setup_s" in res["metrics"]
    for name, c in res["checks"].items():
        assert c["value"] <= c["limit"], name
    if workload.endswith(".live"):
        # Every refresh covers a later window as the writer appends.
        his = [r.hi for r in out.records]
        assert his == sorted(his) and his[-1] > his[0]
        assert "refresh_p50_ms" in res["metrics"]
    else:
        assert "scan_rows_per_s" in res["metrics"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails(tiny_root, workload):
    """The reference one precision step down, in the program's place:
    float32 latency and error rates."""
    from benchmark import harness

    out = cpu_run(tiny_root, workload, seconds=0.2)
    cell = harness.load_cell(workload, tiny_root)
    numbers, _ = harness.check(cell, out.timeline, out.records, 7, "low")
    ok, checks = harness.verdict(cell, numbers, 0)
    assert not ok, checks


def _perturb_one(monkeypatch, column):
    from pixie_tpu.engine import Carnot

    real = Carnot.execute_query

    def altered(self, *a, **k):
        res = real(self, *a, **k)
        for batches in res.tables.values():
            for b in batches:
                if b.num_rows and column in b.relation.col_names():
                    i = b.relation.col_idx(column)
                    col = np.array(b.columns[i])
                    col[0] = col[0] * 1.03 + 1e-9
                    b.columns[i] = col
                    return res
        return res

    monkeypatch.setattr(Carnot, "execute_query", altered)


@pytest.mark.parametrize(
    "workload,column",
    [
        ("http_node.history", "request_throughput"),
        ("http_node.history", "bytes_throughput"),
        ("http_node.live", "latency_p90"),
        ("http_node.live", "error_rate"),
    ],
)
def test_answer_altered_is_caught(tiny_root, monkeypatch, workload, column):
    _perturb_one(monkeypatch, column)
    res = cpu_run(tiny_root, workload, seconds=0.2).result
    assert not res["correct"]


@pytest.mark.parametrize("workload", CELLS)
def test_half_the_rows_left_out_is_caught(tiny_root, monkeypatch, workload):
    """The program's scan passes on only the first half of each batch."""
    from pixie_tpu.table.table import Cursor

    real = Cursor.next_batch

    def half(self, *a, **k):
        b = real(self, *a, **k)
        return b if b is None else b.slice(0, b.num_rows // 2)

    monkeypatch.setattr(Cursor, "next_batch", half)
    res = cpu_run(tiny_root, workload, seconds=0.2).result
    assert res["failed"] == 0
    assert not res["correct"]


@pytest.mark.parametrize(
    "counter",
    ["device_offload_fallback_total", "device_offload_fallback_breaker_trips_total"],
)
def test_a_query_off_the_device_is_failed(tiny_root, monkeypatch, counter):
    """A query that answers but fell to the host or tripped the breaker
    counts as failed: the run is not correct, and its rows count in no
    metric."""
    from pixie_tpu.engine import Carnot
    from pixie_tpu.utils import metrics_registry

    real = Carnot.execute_query
    calls = []

    def off_device(self, *a, **k):
        res = real(self, *a, **k)
        calls.append(1)
        if len(calls) > 3:  # past warm-up: in the window
            metrics_registry().counter(counter).inc()
        return res

    monkeypatch.setattr(Carnot, "execute_query", off_device)
    out = cpu_run(tiny_root, "http_node.history", seconds=0.5)
    res = out.result
    assert not res["correct"]
    assert res["failed"] == res["checks"]["queries_failed"]["value"] > 0
    done = [r for r in out.records if r.failed is None]
    assert len(done) < len(out.records)
    rows_per_s = res["metrics"].get("scan_rows_per_s", {}).get("value")
    if done:
        assert rows_per_s == pytest.approx(
            sum(r.hi - r.lo for r in done) / out.window_s
        )
    else:
        assert rows_per_s is None


def test_metrics_read_only_device_answers(tiny_root):
    from benchmark import harness

    cell = harness.load_cell("http_node.history", tiny_root)
    ok = harness.Record(0.0, 0.0, 1.0, 0, 100, {"k8s": []})
    off = harness.Record(1.0, 1.0, 2.0, 0, 100, {"k8s": []}, failed="not offloaded")
    view = harness.RunView(cell, [ok, off], 2.0, 1.0, {})
    assert view.done == [ok]
    assert cell.metric_reader("scan_rows_per_s")(view) == 50.0


def test_compile_in_window_is_not_correct(tiny_root):
    from benchmark import harness

    cell = harness.load_cell("http_node.history", tiny_root)
    ok, checks = harness.verdict(cell, {k: 0 for k in cell.config["limits"]}, 0)
    assert ok
    ok, checks = harness.verdict(
        cell, {k: 0 for k in cell.config["limits"]}, 0, compiled=1
    )
    assert not ok and checks["programs_compiled_in_window"]["value"] == 1


def test_stale_refresh_is_caught(tiny_root, monkeypatch):
    """Live refreshes that return the first answer unchanged."""
    from pixie_tpu.engine import Carnot

    real = Carnot.execute_query
    first = []

    def stale(self, *a, **k):
        if not first:
            first.append(real(self, *a, **k))
        return first[0]

    monkeypatch.setattr(Carnot, "execute_query", stale)
    res = cpu_run(tiny_root, "http_node.live", seconds=1.0).result
    assert not res["correct"]


def test_verdict_prints_no_infinity(tiny_root):
    from benchmark import harness

    cell = harness.load_cell("http_node.history", tiny_root)
    ok, checks = harness.verdict(cell, {}, 0)
    assert not ok
    assert all(checks[k]["value"] == "inf" for k in cell.config["limits"])


def test_timeline_rows_and_times(tmp_path):
    from benchmark import harness

    root = make_root(str(tmp_path), http_node={"rows": 1000})
    cell = harness.load_cell("http_node.live", root)
    tl = harness.Timeline(cell, 3, 500)
    t = tl.time_of(range(1500))
    assert (t[1:] > t[:-1]).all()
    for i in (0, 1, 999, 1000, 1499):
        assert tl.first_at_or_after(int(t[i])) == i
        assert tl.first_at_or_after(int(t[i]) + 1) == i + 1
    rows = tl.rows(990, 1010)
    assert len(rows["latency"]) == 20
    assert rows["latency"][10] == tl.ingest["latency"][0]
    assert math.isclose(tl.rate, 3500)
