"""Key planning over fixed-size chunks: the generic host key plan
evaluates and densifies the group keys over chunks of exactly
DEFAULT_COMPACTED_ROWS rows (the last one padded), not once per cursor
batch. Over a table with a compacted prefix and a few hundred small
pushes, read with time ranges that cut a batch, the plan partitions rows
exactly as a per-batch plan does, evaluates ceil(rows / C) chunks of C
rows each, compiles nothing for a span of another length, and still
counts the cursor batches it walks."""

import math

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from pixie_tpu.engine import Carnot
from pixie_tpu.exec.expression_evaluator import ExpressionEvaluator
from pixie_tpu.exec.group_encoder import GroupEncoder
from pixie_tpu.parallel import MeshExecutor, pipeline
from pixie_tpu.parallel.staging import reset_cold_profile
from pixie_tpu.plan.expressions import ColumnRef
from pixie_tpu.plan.operators import MapOp
from pixie_tpu.table.column import DictColumn, StringDictionary
from pixie_tpu.types import DataType, Relation
from pixie_tpu.utils import trace

REL = Relation.of(
    ("time_", DataType.TIME64NS),
    ("service", DataType.STRING),
    ("latency", DataType.FLOAT64),
)
COMPACTED = 256  # the prefix's cold batch size
PREFIX_ROWS = 4 * COMPACTED
PUSHES, PUSH_ROWS = 300, 7
ROWS = PREFIX_ROWS + PUSHES * PUSH_ROWS
STEP_NS = 10  # row i has time_ i * STEP_NS

# svc_let's two keys: the service beside a numeric px.bin window.
BIN_KEYS = "df.timestamp = px.bin(df.time_, 1000)\n", "['service', 'timestamp']"
# A computed string key: a fresh dictionary per evaluation, re-encoded
# through one stable dictionary across chunks.
STRING_KEYS = (
    "df.timestamp = px.bin(df.time_, 1000)\ndf.svc = px.toUpper(df.service)\n",
    "['svc', 'timestamp']",
)


def _query(keys, first_row=None, last_row=None):
    maps, groups = keys
    span = ""
    if first_row is not None:
        span = f", start_time={first_row * STEP_NS}, end_time={last_row * STEP_NS}"
    return (
        f"df = px.DataFrame(table='http_events'{span})\n"
        + maps
        + f"df = df.groupby({groups}).agg(n=('latency', px.count))\n"
        "px.display(df, 'out')\n"
    )


@pytest.fixture(autouse=True)
def _clean_state():
    trace.set_enabled(True)
    trace.clear()
    reset_cold_profile()
    yield
    trace.clear()
    reset_cold_profile()


def _engine() -> Carnot:
    mesh = Mesh(np.array(jax.devices("cpu")), ("d",))
    c = Carnot(device_executor=MeshExecutor(mesh=mesh, block_rows=256))
    t = c.table_store.create_table(
        "http_events", REL, compacted_rows=COMPACTED
    )
    rng = np.random.default_rng(7)
    services = rng.choice(["a", "b", "c"], ROWS).astype(object)
    latency = rng.integers(1, 100, ROWS).astype(np.float64)

    def write(lo, hi):
        t.write_pydict(
            {
                "time_": np.arange(lo, hi) * STEP_NS,
                "service": services[lo:hi],
                "latency": latency[lo:hi],
            }
        )

    write(0, PREFIX_ROWS)
    t.compact()
    for lo in range(PREFIX_ROWS, ROWS, PUSH_ROWS):
        write(lo, lo + PUSH_ROWS)
    return c


def _spy_plans(monkeypatch):
    """Records (match, table, registry, func_ctx, key plan) per call."""
    calls = []
    plan = pipeline.MeshExecutor._plan_keys

    def spy(self, m, table, registry, func_ctx, base_cols, sp=None):
        kp = plan(self, m, table, registry, func_ctx, base_cols, sp)
        calls.append((m, table, registry, func_ctx, kp))
        return kp

    monkeypatch.setattr(pipeline.MeshExecutor, "_plan_keys", spy)
    return calls


def _run(c, query):
    res = c.execute_query(query)
    ex = c.device_executor
    assert not ex.fallback_errors and not ex.stream_fallback_errors
    return res


def _walked(table, start, stop):
    """Cursor batches holding rows over the span."""
    cur, n = table.cursor(start, stop), 0
    while not cur.done():
        b = cur.next_batch()
        if b is None:
            break
        n += bool(b.num_rows)
    return n


def _per_batch_plan(m, table, registry, func_ctx):
    """The plan as one evaluation and encode per cursor batch gives it:
    (gids, num_groups, key columns in gid order)."""
    groups = m.agg_op.groups
    ev = ExpressionEvaluator(
        [(g, m.col_exprs[g]) for g in groups], m.source_relation,
        registry, func_ctx,
    )
    out_rel = MapOp(
        tuple((g, m.col_exprs[g]) for g in groups)
    ).output_relation([m.source_relation], registry)
    enc, parts, dicts = GroupEncoder(), [], {}
    cur = table.cursor(m.source_op.start_time, m.source_op.stop_time)
    while not cur.done():
        b = cur.next_batch()
        if b is None:
            break
        if not b.num_rows:
            continue
        cols = []
        for g, col in zip(groups, ev.evaluate(b, out_rel).columns):
            if isinstance(col, DictColumn):
                if not isinstance(m.col_exprs[g], ColumnRef):
                    d = dicts.setdefault(g, StringDictionary())
                    col = DictColumn(d.encode(col.decode()), d)
                dicts[g] = col.dictionary
            cols.append(col)
        parts.append(enc.encode(cols))
    keys = [
        DictColumn(a.astype(np.int32), dicts[g]) if g in dicts else a
        for g, a in zip(groups, enc.key_arrays())
    ]
    return np.concatenate(parts), enc.num_groups, keys


def _key(columns, gid):
    return tuple(
        c.dictionary.decode(c.codes[gid:gid + 1])[0]
        if isinstance(c, DictColumn) else c[gid].item()
        for c in columns
    )


@pytest.mark.parametrize("chunk", [64, 1000, None], ids=["c64", "c1000", "default"])
@pytest.mark.parametrize("keys", [BIN_KEYS, STRING_KEYS], ids=["bin", "computed_string"])
def test_chunked_plan_partitions_as_per_batch(monkeypatch, keys, chunk):
    if chunk is not None:
        monkeypatch.setattr(pipeline, "DEFAULT_COMPACTED_ROWS", chunk)
    calls = _spy_plans(monkeypatch)
    c = _engine()
    # Both ends cut a batch: the first inside a cold batch, the last
    # inside a push.
    res = _run(c, _query(keys, 37, ROWS - 3))
    (m, table, registry, func_ctx, kp), = calls
    ref_gids, ref_groups, ref_keys = _per_batch_plan(m, table, registry, func_ctx)
    assert len(kp.host_gids) == len(ref_gids) == ROWS - 3 - 37 + 1
    assert kp.num_groups == ref_groups
    # A bijection between the per-batch gids and the chunked gids ...
    pairs = set(zip(ref_gids.tolist(), kp.host_gids.tolist()))
    assert len(pairs) == ref_groups
    assert len({o for o, _ in pairs}) == len({n for _, n in pairs}) == ref_groups
    # ... that maps each group to the same key values.
    for old, new in pairs:
        assert _key(ref_keys, old) == _key(kp.key_columns, new)
    assert sum(res.table("out")["n"]) == ROWS - 3 - 37 + 1


@pytest.mark.parametrize("chunk", [64, 1000])
def test_key_evals_are_whole_chunks(monkeypatch, chunk):
    monkeypatch.setattr(pipeline, "DEFAULT_COMPACTED_ROWS", chunk)
    sizes = []
    evaluate = ExpressionEvaluator.evaluate

    def spy(self, batch, output_relation):
        if output_relation.col_names() == ["service", "timestamp"]:
            sizes.append(batch.num_rows)
        return evaluate(self, batch, output_relation)

    monkeypatch.setattr(ExpressionEvaluator, "evaluate", spy)
    c = _engine()
    first, last = 37, ROWS - 3
    res = _run(c, _query(BIN_KEYS, first, last))
    rows = last - first + 1
    want = math.ceil(rows / chunk)
    assert sizes == [chunk] * want
    assert reset_cold_profile()["key_evals"] == want
    (plan,) = [s for s in res.trace_spans if s["name"] == "device.plan_keys"]
    assert plan["attrs"]["evals"] == want


def test_second_span_of_another_length_compiles_nothing():
    compiled = []

    def listen(event, duration, fun_name="", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(fun_name)

    c = _engine()
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        # The same windows, services and staged shape: 1,500 rows, then
        # 1,450 with a cut first batch.
        _run(c, _query(BIN_KEYS, 0, 1499))
        assert compiled
        del compiled[:]
        res = _run(c, _query(BIN_KEYS, 50, 1499))
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert sum(res.table("out")["n"]) == 1450
    assert compiled == []


def test_read_batches_counts_cursor_batches():
    c = _engine()
    table = c.table_store.get_table("http_events")
    first, last = 37, ROWS - 3
    walked = _walked(table, first * STEP_NS, last * STEP_NS)
    assert walked == 4 + PUSHES  # every cold batch and every push
    res = _run(c, _query(BIN_KEYS, first, last))
    prof = reset_cold_profile()
    # Key planning and read_columns each walk the span once, and each
    # batch's cursor call resumes at its own segment: one visit a batch.
    assert prof["read_batches"] == 2 * walked
    assert prof["read_visits"] == 2 * walked
    assert prof["key_evals"] == 1
    (plan,) = [s for s in res.trace_spans if s["name"] == "device.plan_keys"]
    assert plan["attrs"] == {
        "batches": walked, "visits": walked, "evals": 1, "cached": False
    }
