"""Fan-out fragments on the device: one source chain forking into several
aggregations is staged once and every branch is folded over it.

On seeded conn_stats data whose counters lie above 2**32 and whose
per-connection group-by has 14,080 groups (above the matmul lane's
8,192), each fan-out must equal the host engine and a plain numpy
reference exactly; shapes outside the rule must stay on the host whole.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from benchmark.datasets import conn_stats as ds
from pixie_tpu.engine import Carnot
from pixie_tpu.parallel import MeshExecutor
from pixie_tpu.parallel.pipeline import match_fanout
from pixie_tpu.parallel.staging import reset_cold_profile
from pixie_tpu.table import TableStore
from pixie_tpu.utils import metrics_registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "benchmark", "configs", "conn_node.json")) as f:
    CFG = json.load(f)
# Two whole reports of every aggregate and part of a third.
CFG["rows"] = 2 * ds.aggregates(CFG) + 999
COUNTERS = (
    "device_offload_total",
    "device_offload_unmatched_total",
    "device_offload_fallback_total",
)


@pytest.fixture(scope="module")
def data():
    n = CFG["rows"]
    cols = ds.generate(CFG, n, np.random.default_rng(2**33 + 7))
    cols["time_"] = CFG["time_base_ns"] + np.arange(n, dtype=np.int64) * (
        10**9
    ) // CFG["events_per_s"]
    store = TableStore()
    table = store.create_table(ds.TABLE, ds.relation(), size_limit=1 << 40)
    ds.identity_codes(table, CFG)
    table.write_pydict(ds.pydict(table, cols, 0, n, cols["time_"]))
    table.compact()
    return store, cols


def _src(cols):
    return (
        f"df = px.DataFrame('{ds.TABLE}', start_time={int(cols['time_'][0])},"
        f" end_time={int(cols['time_'][-1])})\n"
    )


NAMES = {
    "pod": ds.pod_names(CFG),
    "upid": ds.upid_names(CFG),
    "remote_addr": ds.addr_names(CFG),
    "namespace": ds.namespace_names(CFG),
}
CONN = ["pod", "upid", "remote_addr"]


def _group(cols, keep, keys, aggs):
    """{decoded key tuple: tuple of aggregates} by plain numpy: aggs is a
    list of (values array over all rows, 'min' | 'max' | 'sum' |
    'count')."""
    codes = np.stack([cols[k][keep] for k in keys], axis=1) if keys else (
        np.zeros((int(keep.sum()), 1), np.int64)
    )
    uniq, inv = np.unique(codes, axis=0, return_inverse=True)
    inv = inv.reshape(-1)
    outs = []
    for vals, how in aggs:
        v = vals[keep]
        if how == "count":
            outs.append(np.bincount(inv, minlength=len(uniq)))
            continue
        if how == "sum":
            acc = np.zeros(len(uniq), np.int64)
            np.add.at(acc, inv, v)
        elif how == "min":
            acc = np.full(len(uniq), np.iinfo(np.int64).max)
            np.minimum.at(acc, inv, v)
        else:
            acc = np.full(len(uniq), np.iinfo(np.int64).min)
            np.maximum.at(acc, inv, v)
        outs.append(acc)
    out = {}
    for i, u in enumerate(uniq):
        key = tuple(NAMES[k][c] for k, c in zip(keys, u)) if keys else ()
        out[key] = tuple(int(o[i]) for o in outs)
    return out


def _keyed(rows, keys):
    """A result table as {key tuple: tuple of the other columns}."""
    vals = [c for c in rows if c not in keys]
    n = len(rows[vals[0]])
    return {
        tuple(rows[k][i] for k in keys): tuple(int(rows[c][i]) for c in vals)
        for i in range(n)
    }


def _net_flow(cols):
    return ds.query(CFG, int(cols["time_"][0]), int(cols["time_"][-1]))


def _shared_filter(cols):
    return _src(cols) + (
        "df = df[df.trace_role == 1]\n"
        "a = df.groupby(['pod', 'upid', 'remote_addr']).agg(\n"
        "    lo=('bytes_sent', px.min), hi=('bytes_sent', px.max))\n"
        "b = df.groupby(['namespace']).agg(\n"
        "    n=('bytes_recv', px.count), s=('bytes_recv', px.sum))\n"
        "px.display(a, 'a')\n"
        "px.display(b, 'b')\n"
    )


def _three(cols):
    return _src(cols) + (
        "df = df[df.namespace == 'app']\n"
        "a = df.groupby(['pod', 'upid', 'remote_addr']).agg(\n"
        "    mx=('bytes_recv', px.max))\n"
        "b = df.groupby(['pod']).agg(n=('time_', px.count))\n"
        "c = df.agg(t0=('time_', px.min), t1=('time_', px.max))\n"
        "px.display(a, 'a')\n"
        "px.display(b, 'b')\n"
        "px.display(c, 'c')\n"
    )


def _own_filters(cols):
    return _src(cols) + (
        "df = df[df.namespace == 'app']\n"
        "cl = df[df.trace_role == 1]\n"
        "cl = cl.groupby(['pod', 'upid', 'remote_addr']).agg(\n"
        "    s=('bytes_sent', px.max))\n"
        "sv = df[df.trace_role == 2]\n"
        "sv.total = sv.bytes_sent + sv.bytes_recv\n"
        "sv = sv.groupby(['pod']).agg(t=('total', px.sum))\n"
        "px.display(cl, 'a')\n"
        "px.display(sv, 'b')\n"
    )


def _want(case, cols):
    """{output table: (key columns, {key: values})} of the plain
    reference, or the net_flow_graph reference."""
    app = cols["namespace"] == 0
    client = cols["trace_role"] == 1
    if case == "shared_filter":
        return {
            "a": (CONN, _group(cols, client, CONN, [(cols["bytes_sent"], "min"), (cols["bytes_sent"], "max")])),
            "b": (["namespace"], _group(cols, client, ["namespace"], [(cols["bytes_recv"], "count"), (cols["bytes_recv"], "sum")])),
        }
    if case == "three":
        return {
            "a": (CONN, _group(cols, app, CONN, [(cols["bytes_recv"], "max")])),
            "b": (["pod"], _group(cols, app, ["pod"], [(cols["time_"], "count")])),
            "c": ([], _group(cols, app, [], [(cols["time_"], "min"), (cols["time_"], "max")])),
        }
    total = cols["bytes_sent"] + cols["bytes_recv"]
    return {
        "a": (CONN, _group(cols, app & client, CONN, [(cols["bytes_sent"], "max")])),
        "b": (["pod"], _group(cols, app & ~client, ["pod"], [(total, "sum")])),
    }


CASES = {
    "net_flow_graph": (_net_flow, 2),
    "shared_filter": (_shared_filter, 2),
    "three": (_three, 3),
    "own_filters": (_own_filters, 2),
}


def _run(carnot, q):
    reg = metrics_registry()
    before = [reg.counter(c).value() for c in COUNTERS]
    reset_cold_profile()
    res = carnot.execute_query(q)
    profile = reset_cold_profile()
    delta = {
        c: reg.counter(c).value() - b for c, b in zip(COUNTERS, before)
    }
    return res, delta, profile


def _rows(res):
    return {
        name: sorted(
            zip(*[np.asarray(v).tolist() for _, v in sorted(res.table(name).items())])
        )
        for name in res.tables
    }


@pytest.mark.parametrize("case", sorted(CASES))
def test_fanout_equals_host_and_reference(data, case):
    store, cols = data
    make, n_aggs = CASES[case]
    q = make(cols)
    ex = MeshExecutor(block_rows=1 << 14)
    res, delta, profile = _run(Carnot(table_store=store, device_executor=ex), q)
    assert delta == {
        "device_offload_total": 1,
        "device_offload_unmatched_total": 0,
        "device_offload_fallback_total": 0,
    }, ex.fallback_errors
    assert profile["device_aggs"] == n_aggs
    host = Carnot(table_store=store).execute_query(q)
    assert _rows(res) == _rows(host)
    if case == "net_flow_graph":
        got = ds.as_reference(res.table(ds.OUT), CFG)
        gaps = ds.compare(got, ds.reference(CFG, cols))
        assert gaps == {"bytes_gap": 0, "rows_gap": 0, "time_delta_gap": 0}
        assert len(got) > 8192
        return
    for name, (keys, want) in _want(case, cols).items():
        assert _keyed(res.table(name), keys) == want, name
    assert max(len(w) for _, w in _want(case, cols).values()) > 8192


def test_fanout_stages_once(data):
    """One offload answers both branches, and the first (staging) query
    walks the table as often as one branch alone does."""
    store, cols = data
    q = _shared_filter(cols)
    one = q.replace("px.display(b, 'b')\n", "")
    _, _, alone = _run(
        Carnot(table_store=store, device_executor=MeshExecutor(block_rows=1 << 14)),
        one,
    )
    ex = MeshExecutor(block_rows=1 << 14)
    carnot = Carnot(table_store=store, device_executor=ex)
    _, delta, profile = _run(carnot, q)
    assert delta["device_offload_total"] == 1
    assert profile["device_aggs"] == 2 and alone["device_aggs"] == 1
    assert profile["read_batches"] == alone["read_batches"] > 0
    assert len(ex._staged_cache) == 1
    # Warm: the staged entry serves both branches.
    _, delta, profile = _run(carnot, q)
    assert delta["device_offload_total"] == 1 and profile["device_aggs"] == 2
    assert len(ex._staged_cache) == 1
    assert "stage" not in profile and "read_batches" not in profile


HOST_SHAPES = {
    # A branch that displays the filtered rows themselves.
    "raw_rows": (
        "df = df[df.namespace == 'kube-system']\n"
        "a = df.groupby(['pod']).agg(n=('time_', px.count))\n"
        "b = df.agg(t0=('time_', px.min))\n"
        "px.display(a, 'a')\n"
        "px.display(b, 'b')\n"
        "px.display(df, 'raw')\n"
    ),
    # The shared filter feeds a join besides the aggregations.
    "shared_join": (
        "df = df[df.namespace == 'kube-system']\n"
        "a = df.groupby(['pod']).agg(n=('time_', px.count))\n"
        "b = df.agg(t0=('time_', px.min))\n"
        "j = df.merge(a, how='inner', left_on='pod', right_on='pod')\n"
        "px.display(b, 'b')\n"
        "px.display(j, 'j')\n"
    ),
    # Two aggregations over a streaming source (planned, not run: a
    # streaming query waits for rows that never come).
    "streaming": (
        "df = df[df.trace_role == 1].stream()\n"
        "a = df.groupby(['pod']).agg(n=('time_', px.count))\n"
        "b = df.agg(t0=('time_', px.min))\n"
        "px.display(a, 'a')\n"
        "px.display(b, 'b')\n"
    ),
}


@pytest.mark.parametrize("shape", sorted(HOST_SHAPES))
def test_other_shapes_stay_on_the_host_whole(data, shape):
    from pixie_tpu.compiler import Compiler
    from pixie_tpu.udf.registry import default_registry

    store, cols = data
    q = _src(cols) + HOST_SHAPES[shape]
    reg = default_registry()
    (frag,) = Compiler(reg).compile(q, store.relation_map()).fragments
    rels = frag.resolve_relations(
        reg, lambda op: store.get_relation(op.table_name)
    )
    assert match_fanout(frag, rels) is None
    offloads = metrics_registry().counter("device_offload_total")
    before = offloads.value()
    reset_cold_profile()
    assert MeshExecutor(block_rows=1 << 14).try_execute_fragment(
        frag, store, reg
    ) is None
    assert offloads.value() == before
    assert "device_aggs" not in reset_cold_profile()
    if shape == "streaming":
        return
    ex = MeshExecutor(block_rows=1 << 14)
    res, delta, profile = _run(Carnot(table_store=store, device_executor=ex), q)
    assert delta["device_offload_total"] == 0
    assert "device_aggs" not in profile
    assert _rows(res) == _rows(Carnot(table_store=store).execute_query(q))


@pytest.mark.parametrize("state", [None, "with_state"])
def test_nslookup_without_metadata_returns_the_address(state):
    from pixie_tpu.exec.exec_state import FunctionContext
    from pixie_tpu.metadata.state import MetadataState
    from pixie_tpu.types import DataType
    from pixie_tpu.udf.registry import default_registry

    udf = default_registry().lookup_scalar("nslookup", [DataType.STRING])
    md = None if state is None else MetadataState(dns={"10.0.0.1": "db.svc"})
    ips = np.array(["10.0.0.1", "10.0.0.2"], dtype=object)
    got = list(udf.fn(FunctionContext(metadata_state=md), ips))
    assert got == (["10.0.0.1", "10.0.0.2"] if md is None else ["db.svc", "10.0.0.2"])
