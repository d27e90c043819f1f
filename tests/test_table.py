"""Table store tests (ref model: src/table_store/table/table_test.cc)."""

import functools

import numpy as np
import pytest

from pixie_tpu.table import DictColumn, RowBatch, StringDictionary, Table, TableStore
from pixie_tpu.table.table import DEFAULT_COMPACTED_ROWS
from pixie_tpu.types import DataType, Relation

REL = Relation.of(
    ("time_", DataType.TIME64NS),
    ("latency", DataType.FLOAT64),
    ("service", DataType.STRING),
)


def make_batch(times, lats, svcs, dicts=None, **flags):
    return RowBatch.from_pydict(
        REL,
        {"time_": times, "latency": lats, "service": svcs},
        dictionaries=dicts,
        **flags,
    )


def test_string_dictionary():
    d = StringDictionary()
    codes = d.encode(["a", "b", "a", "c"])
    assert codes.dtype == np.int32
    assert codes[0] == codes[2]
    assert len(d) == 3
    assert list(d.decode(codes)) == ["a", "b", "a", "c"]
    assert d.lookup("b") == codes[1]
    assert d.lookup("zz") == -1


def test_row_batch_basics():
    rb = make_batch([1, 2, 3], [0.1, 0.2, 0.3], ["x", "y", "x"])
    assert rb.num_rows == 3
    assert isinstance(rb.col("service"), DictColumn)
    sel = rb.select(["latency"])
    assert sel.relation.col_names() == ["latency"]
    taken = rb.take(np.array([2, 0]))
    assert taken.to_pydict()["service"] == ["x", "x"]
    cat = RowBatch.concat([rb, rb.slice(0, 1)])
    assert cat.num_rows == 4


def test_row_batch_wire_roundtrip():
    rb = make_batch([1, 2], [0.5, 1.5], ["svc-a", "svc-b"], **{"eos": True})
    rt = RowBatch.from_bytes(rb.to_bytes())
    assert rt.eos and not rt.eow
    assert rt.to_pydict() == rb.to_pydict()


def test_table_write_read():
    t = Table(REL, name="http_events")
    t.write_pydict({"time_": [1, 2], "latency": [1.0, 2.0], "service": ["a", "b"]})
    t.write_pydict({"time_": [3, 4], "latency": [3.0, 4.0], "service": ["a", "c"]})
    cur = t.cursor()
    out = []
    while not cur.done():
        b = cur.next_batch()
        if b is None:
            break
        out.append(b)
    merged = RowBatch.concat(out)
    assert merged.num_rows == 4
    assert merged.to_pydict()["service"] == ["a", "b", "a", "c"]
    # codes are table-consistent across batches
    svc = merged.col("service")
    assert svc.codes[0] == svc.codes[2]


def test_table_time_bounds():
    t = Table(REL)
    t.write_pydict(
        {"time_": [10, 20, 30, 40], "latency": [1, 2, 3, 4], "service": list("abcd")}
    )
    cur = t.cursor(start_time=20, stop_time=30)
    b = cur.next_batch()
    assert b.to_pydict()["time_"] == [20, 30]


def test_table_compaction_preserves_cursor():
    t = Table(REL, compacted_rows=4)
    for i in range(3):
        t.write_pydict(
            {
                "time_": [i * 10 + 1, i * 10 + 2],
                "latency": [1.0, 2.0],
                "service": ["a", "b"],
            }
        )
    cur = t.cursor()
    first = cur.next_batch(max_rows=2)
    assert first.num_rows == 2
    assert t.compact() == 1  # 6 hot rows -> one 4-row cold batch + 2-row hot tail
    rest = []
    while True:
        b = cur.next_batch(max_rows=100)
        if b is None:
            break
        rest.append(b)
    assert sum(b.num_rows for b in rest) == 4  # no duplicates, no loss


def test_table_ring_expiry():
    t = Table(REL, size_limit=1)  # absurdly small: keep only newest segment
    for i in range(5):
        t.write_pydict({"time_": [i], "latency": [float(i)], "service": ["s"]})
    st = t.stats()
    assert st.batches_expired >= 3
    cur = t.cursor()
    batches = []
    while not cur.done():
        b = cur.next_batch()
        if b is None:
            break
        batches.append(b)
    assert sum(b.num_rows for b in batches) < 5


def test_table_store():
    ts = TableStore()
    t = ts.create_table("http_events", REL)
    assert ts.get_table("http_events") is t
    assert ts.get_relation("http_events") == REL
    assert ts.table_names() == ["http_events"]
    assert ts.relation_map()["http_events"].has_column("latency")


def test_streaming_cursor():
    t = Table(REL)
    cur = t.cursor(streaming=True)
    assert not cur.done()
    assert cur.next_batch() is None
    t.write_pydict({"time_": [1], "latency": [1.0], "service": ["a"]})
    assert cur.next_batch().num_rows == 1
    t.stop()
    assert cur.next_batch() is None
    assert cur.done()


# -- cursor resumes: the bisected read against a linear-scan reference ----


def _linear_read_from(table, row_id, max_rows, start_time, stop_time):
    """Table._read_from as a scan from the first segment on every call:
    the reference a bisected start must answer exactly as."""
    with table._lock:
        visited = 0
        for seg in table._segments:
            visited += 1
            if seg.end_row_id <= row_id:
                continue
            if start_time is not None and seg.max_time < start_time:
                row_id = seg.end_row_id
                continue
            if stop_time is not None and seg.min_time > stop_time:
                return None, row_id, visited
            lo = max(0, row_id - seg.first_row_id)
            hi = min(seg.num_rows, lo + max_rows)
            chunk = seg.batch.slice(lo, hi)
            next_id = seg.first_row_id + hi
            if start_time is not None or stop_time is not None:
                t = np.asarray(chunk.columns[table._time_idx])
                mask = np.ones(len(t), dtype=bool)
                if start_time is not None:
                    mask &= t >= start_time
                if stop_time is not None:
                    mask &= t <= stop_time
                if not mask.all():
                    chunk = chunk.take(np.nonzero(mask)[0])
            return chunk, next_id, visited
        return None, max(row_id, table._next_row_id), visited


PUSH = 5  # rows a push; row i has time_ 10 * i


def _push(t, k, eow=False):
    """Write push k: rows [k * PUSH, (k + 1) * PUSH)."""
    ids = np.arange(k * PUSH, (k + 1) * PUSH)
    t.write_pydict(
        {
            "time_": ids * 10,
            "latency": ids.astype(np.float64),
            "service": ["s%d" % (i % 3) for i in ids],
        },
        eow=eow,
    )


def _pushes(t, lo, hi, eow_every=0):
    for k in range(lo, hi):
        _push(t, k, eow=bool(eow_every) and k % eow_every == eow_every - 1)


def _cold_prefix(t):
    """40 pushes compacted into 8-row cold segments (25 of them), then 20
    more pushes: the range [45, 270] rows starts in the cold prefix."""
    _pushes(t, 0, 40)
    t.compact()
    _pushes(t, 40, 60)


def _eow_markers(t):
    """Pushes closing a window every third push, and zero-row eow pushes
    between some of them."""
    for k in range(30):
        _push(t, k, eow=k % 3 == 2)
        if k % 7 == 6:
            t.write_pydict({"time_": [], "latency": [], "service": []}, eow=True)


def _expire_leading(t, step):
    if step in (3, 8):  # each drops the oldest pushes from the ring
        _pushes(t, 30 + 10 * step, 40 + 10 * step)


def _stream(t, step):
    if step % 2 and step < 40:
        _push(t, 100 + step, eow=step % 5 == 0)
    if step == 45:
        t.stop()


WALKS = {
    # name: (table kwargs, fill, cursor kwargs, max_rows, between calls)
    "unbounded": ({}, lambda t: _pushes(t, 0, 40, 4), {}, 3, None),
    "time_bounded": (
        {}, lambda t: _pushes(t, 0, 40, 4),
        {"start_time": 333, "stop_time": 1566}, 4, None,
    ),
    "cold_prefix_into_pushes": (
        {"compacted_rows": 8}, _cold_prefix,
        {"start_time": 450, "stop_time": 2700}, 3, None,
    ),
    "eow_markers": ({}, _eow_markers, {"start_time": 120}, 4, None),
    "compact_between_calls": (
        {"compacted_rows": 8}, lambda t: _pushes(t, 0, 30, 3),
        {"start_time": 75, "stop_time": 1400}, 3,
        lambda t, step: step == 4 and t.compact(),
    ),
    "expire_between_calls": (
        {"size_limit": 20 * PUSH * 20}, lambda t: _pushes(t, 0, 20, 2),
        {}, 4, _expire_leading,
    ),
    "streaming_appends": (
        {}, lambda t: _pushes(t, 0, 6, 2), {"streaming": True}, 3, _stream,
    ),
}


def _walk(t, cursor_kw, max_rows, between):
    """Every next_batch answer (rows, eow, eos, or None), then the
    cursor's skipped rows and done state."""
    cur = t.cursor(**cursor_kw)
    out = []
    for step in range(200):
        if between is not None:
            between(t, step)
        if cur.done():
            break
        b = cur.next_batch(max_rows)
        out.append(None if b is None else (b.to_pydict(), b.eow, b.eos))
    out.append((cur.rows_skipped, cur.done()))
    return out, cur


@pytest.mark.parametrize("case", list(WALKS))
def test_cursor_walk_matches_linear_scan(case):
    table_kw, fill, cursor_kw, max_rows, between = WALKS[case]
    bisected, linear = Table(REL, **table_kw), Table(REL, **table_kw)
    linear._read_from = functools.partial(_linear_read_from, linear)
    for t in (bisected, linear):
        fill(t)
    got, cur = _walk(bisected, cursor_kw, max_rows, between)
    want, ref = _walk(linear, cursor_kw, max_rows, between)
    assert got == want
    assert sum(b is not None and len(b[0]["time_"]) for b in got[:-1]) > 0
    assert cur.segments_visited <= ref.segments_visited
    if case == "expire_between_calls":
        assert bisected.stats().batches_expired and cur.rows_skipped


@pytest.mark.parametrize("max_rows", [DEFAULT_COMPACTED_ROWS, 2])
def test_cursor_visits_grow_with_batches_not_segments(max_rows):
    """2,000 pushes behind 500 cold segments: a time-bounded walk
    visits the segments before its range once, then one a batch."""
    cold, pushes, rows = 500, 2000, 3
    t = Table(REL, compacted_rows=8)
    ids = np.arange(cold * 8)
    t.write_pydict(
        {"time_": ids, "latency": ids * 1.0, "service": ["a"] * len(ids)}
    )
    assert t.compact() == cold
    for k in range(pushes):
        ids = cold * 8 + k * rows + np.arange(rows)
        t.write_pydict(
            {"time_": ids, "latency": ids * 1.0, "service": ["b"] * rows}
        )
    cur = t.cursor(start_time=cold * 8, stop_time=None)
    batches = n = 0
    while not cur.done():
        b = cur.next_batch(max_rows)
        if b is None:
            break
        batches += 1
        n += b.num_rows
    assert n == pushes * rows
    assert batches == pushes * -(-rows // min(rows, max_rows))
    assert cur.segments_visited <= cold + batches + 2
