"""The conn_node configuration: its query is the vendored
px/net_flow_graph body under the listed substitutions only, its tiny
run on the CPU is correct and reads the cell's metrics, and the float32
control fails its limits."""

from __future__ import annotations

import json
import os
import re
import textwrap

import pytest
from bench_tiny import REPO, cpu_run, make_root
from test_bench_trace import DEVICE, HOST, _summary

SCRIPT = os.path.join(
    REPO, "pixie_tpu", "scripts", "px", "net_flow_graph", "net_flow_graph.pxl"
)
CELL = "conn_node.history"
METRICS = (
    "device_aggs.conn_node",
    "fold_roofline.conn_node",
    "suffix_ms.conn_node",
    "device_idle_pct.conn_node",
)


def _cfg(root=REPO):
    with open(os.path.join(root, "benchmark", "configs", "conn_node.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def conn_root(tmp_path_factory):
    """A checkout whose conn_node holds two reports of every aggregate
    and part of a third: 14,080 client connections in the queried
    namespace, as at full size."""
    from benchmark.datasets import conn_stats

    root = make_root(str(tmp_path_factory.mktemp("conn")))
    cfg = _cfg(root)
    cfg.update(rows=2 * conn_stats.aggregates(cfg) + 999, block_rows=1 << 14)
    with open(os.path.join(root, "benchmark", "configs", "conn_node.json"), "w") as f:
        json.dump(cfg, f)
    return root


def _script_body(ns, start_ns, end_ns, out):
    """The vendored function's body with the substitutions the
    configuration lists, and no others."""
    with open(SCRIPT) as f:
        text = f.read()
    body = text.split("throughput_filter: float):\n", 1)[1]
    body = textwrap.dedent(body)
    subs = [
        ("df.ctx['namespace']", "df.namespace"),
        ("df.ctx['pod']", "df.pod"),
        ("start_time=start_time", f"start_time={start_ns}, end_time={end_ns}"),
        ("from_entity_filter", "''"),
        ("to_entity_filter", "''"),
        ("throughput_filter", "0.0"),
        ("return df\n", f"px.display(df, '{out}')\n"),
    ]
    for old, new in subs:
        assert old in body, old
        body = body.replace(old, new)
    body, n = re.subn(r"== ns\]", f"== '{ns}']", body)
    assert n == 1
    return body


def test_query_is_the_vendored_script():
    from benchmark.datasets import conn_stats

    cfg = _cfg()
    got = conn_stats.query(cfg, 17, 2**61 + 3)
    assert got == _script_body(cfg["namespaces"][0], 17, 2**61 + 3, conn_stats.OUT)


def test_lower_bound_bits():
    """4 namespaces: 2 bits; 2 trace roles: 1; 110 pods: 7; 220 upids: 8;
    4,096 remote addresses: 12; 2^24 rows at 56,320/s span 297.9 s in ns:
    39; each counter over [2^32, 2^40) plus 298 reports of growth below
    2^16: 40."""
    from benchmark.datasets import conn_stats

    assert conn_stats.lower_bound_bits(_cfg()) == 2 + 1 + 7 + 8 + 12 + 39 + 40 + 40


def test_tiny_run_is_correct_and_reads_the_cells_metrics(conn_root):
    from benchmark import harness

    out = cpu_run(conn_root, CELL, seconds=1.0, trace=True)
    res = out.result
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["checked_answers"] > 0
    assert res["checks"]["bytes_gap"]["value"] == 0
    assert all(r.profile.get("device_aggs") == 2.0 for r in out.records)
    assert res["metrics"]["device_aggs.conn_node"]["value"] == 2.0
    assert res["metrics"]["suffix_ms.conn_node"]["value"] > 0
    # The CPU's trace has no device plane: the device readers read the
    # same records over a hand-made trace of a device.
    cell = harness.load_cell(CELL, conn_root)
    assert [m["name"] for m in cell.per_layer] == list(METRICS)
    view = harness.RunView(
        cell, out.records, out.window_s, 0.0, {"hbm_bytes_per_s": 819e9},
        _summary(DEVICE + HOST),
    )
    got = harness.read_metrics(view, cell.per_layer)
    assert set(got) == {"device_aggs.conn_node", "fold_roofline.conn_node",
                        "device_idle_pct.conn_node"}
    assert got["device_idle_pct.conn_node"]["value"] == pytest.approx(55.0)
    bits = cell.dataset.lower_bound_bits(cell.config)
    rows = sum(r.hi - r.lo for r in view.done)
    # The hand-made trace's queries hold 4000 ns of device time.
    assert got["fold_roofline.conn_node"]["value"] == pytest.approx(
        100 * rows * bits / 8 / 819e9 / 4e-6
    )
    # The float32 control, in the program's place, fails the limits.
    numbers, _ = harness.check(cell, out.timeline, out.records, 7, "low")
    ok, checks = harness.verdict(cell, numbers, 0)
    assert not ok and numbers["bytes_gap"] > 0, checks
