"""The sorted 64-bit segment-sum lane (ops/segment.py: sorted_segment_sum).

Above MATMUL_MAX_SEGMENTS a TPU seg_sum over int64 or float64 sorts
(segment id, value) in 2^17-row tiles, scans the sorted values with a
restart at every run, and reads each run's sum at its last row, found
from the tile's int32 segment counts, instead of the s64/f64 scalar
scatter. On the CPU the lane is called directly or forced with
``segment.set_sorted_strategy(True)``:
- int64 sums are bit-identical with jax.ops.segment_sum and numpy,
  wrapping modulo 2^64;
- float64 sums keep each segment's rounding its own: within n*eps of
  its sum of |v|, even a tiny segment sorted after a 1e15 one;
- the lane's jaxpr holds no 64-bit scatter over the rows;
- the gate: TPU-class platform, above 8,192 segments, 64-bit dtype, at
  least SORTED_MIN_ROWS rows, ``sorted_compact`` on, and the tiles'
  segment tables no larger than the rows;
- end to end, a svc_let-shaped group-by and a device join-aggregation
  over more than 8,192 keys match the host engine with the lane forced.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from pixie_tpu.engine import Carnot
from pixie_tpu.ops import segment
from pixie_tpu.parallel import MeshExecutor
from pixie_tpu.types import DataType, Relation, SemanticType
from pixie_tpu.utils import flags, trace

F, I, S, T = (
    DataType.FLOAT64,
    DataType.INT64,
    DataType.STRING,
    DataType.TIME64NS,
)

I64_MIN, I64_MAX = np.iinfo(np.int64).min, np.iinfo(np.int64).max


@pytest.fixture
def forced():
    segment.set_sorted_strategy(True)
    yield
    segment.set_sorted_strategy(None)


# (n, num_segments, share of rows kept, id layout)
CASES = [
    (5000, 9001, 0.8, "uniform"),  # non-pow2 nseg, ragged mask
    (3000, 16384, 0.5, "uniform"),  # most segments empty
    (2000, 9000, 1.0, "one"),  # every row in one segment
    (300, 20000, 0.7, "uniform"),  # n < nseg
    (4096, 37, 0.3, "uniform"),  # few segments, sparse mask
    (1, 5, 1.0, "uniform"),
    (2 * (1 << 17) + 3, 9001, 0.8, "uniform"),  # three tiles, one padded
]


def _ids(rng, n, nseg, layout):
    if layout == "one":
        return np.full(n, nseg // 3, np.int32)
    return rng.integers(0, nseg, n).astype(np.int32)


@pytest.mark.parametrize("n,nseg,keep,layout", CASES)
def test_int64_bit_identical(rng, n, nseg, keep, layout):
    ids = _ids(rng, n, nseg, layout)
    mask = rng.random(n) < keep
    # Values near both ends of int64, so that partial sums wrap.
    vals = np.where(
        rng.random(n) < 0.5,
        I64_MAX - rng.integers(0, 1 << 20, n),
        I64_MIN + rng.integers(0, 1 << 20, n),
    ).astype(np.int64)
    want = np.zeros(nseg, np.int64)
    with np.errstate(over="ignore"):
        np.add.at(want, ids[mask], vals[mask])
    jv, ji, jm = jnp.asarray(vals), jnp.asarray(ids), jnp.asarray(mask)
    got = np.asarray(segment.sorted_segment_sum(jv, ji, nseg, jm))
    ref = np.asarray(
        jax.ops.segment_sum(jnp.where(jm, jv, 0), ji, num_segments=nseg)
    )
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, ref)
    unmasked = np.asarray(segment.sorted_segment_sum(jv, ji, nseg))
    want_all = np.zeros(nseg, np.int64)
    with np.errstate(over="ignore"):
        np.add.at(want_all, ids, vals)
    np.testing.assert_array_equal(unmasked, want_all)


def _reduceat_truth(vals, ids, nseg):
    """Per-segment numpy sums (np.add.reduceat over the rows sorted by
    segment) and each segment's sum of |v|."""
    order = np.argsort(ids, kind="stable")
    v, s = vals[order], ids[order]
    sums, mags = np.zeros(nseg), np.zeros(nseg)
    if len(v):
        starts = np.flatnonzero(np.r_[True, s[1:] != s[:-1]])
        sums[s[starts]] = np.add.reduceat(v, starts)
        mags[s[starts]] = np.add.reduceat(np.abs(v), starts)
    return sums, mags


@pytest.mark.parametrize("n,nseg,keep,layout", CASES)
def test_float64_within_segment_rounding(rng, n, nseg, keep, layout):
    ids = _ids(rng, n, nseg, layout)
    mask = rng.random(n) < keep
    vals = rng.normal(size=n) * 10.0 ** rng.integers(-3, 15, n)
    got = np.asarray(
        segment.sorted_segment_sum(
            jnp.asarray(vals), jnp.asarray(ids), nseg, jnp.asarray(mask)
        )
    )
    want, mags = _reduceat_truth(vals[mask], ids[mask], nseg)
    eps = np.finfo(np.float64).eps
    assert (np.abs(got - want) <= n * eps * mags).all()
    assert (got[mags == 0] == 0).all()


def test_float64_small_segment_after_huge_one():
    """A 1e-3-scale segment sorted after a 1e15-scale one keeps its own
    digits: a difference of global prefix sums would lose them all."""
    big = np.full(1000, 1e15) + np.arange(1000)
    tiny = np.full(1000, 1e-3) * (1 + np.arange(1000) / 1000)
    vals = np.concatenate([big, tiny])
    ids = np.concatenate([np.zeros(1000), np.ones(1000)]).astype(np.int32)
    got = np.asarray(
        segment.sorted_segment_sum(jnp.asarray(vals), jnp.asarray(ids), 9000)
    )
    want, mags = _reduceat_truth(vals, ids, 9000)
    eps = np.finfo(np.float64).eps
    assert abs(got[1] - want[1]) <= len(vals) * eps * mags[1]
    assert abs(got[0] - want[0]) <= len(vals) * eps * mags[0]
    assert (got[2:] == 0).all()


def test_empty_input():
    for dt in (jnp.int64, jnp.float64):
        got = segment.sorted_segment_sum(
            jnp.zeros(0, dt), jnp.zeros(0, jnp.int32), 9000
        )
        assert got.shape == (9000,) and got.dtype == dt
        assert not np.asarray(got).any()


def _wide_scatters(fn, n, *args):
    """(primitive, dtype) of every scatter in fn's jaxpr, sub-jaxprs
    included, that has an operand of length n and a 64-bit dtype."""
    found = []

    def walk(jx):
        for eqn in jx.eqns:
            if "scatter" in eqn.primitive.name:
                for v in eqn.invars:
                    aval = getattr(v, "aval", None)
                    shape = getattr(aval, "shape", ())
                    if shape and shape[0] == n and aval.dtype.itemsize == 8:
                        found.append((eqn.primitive.name, aval.dtype))
            for val in eqn.params.values():
                sub = getattr(val, "jaxpr", None)
                if sub is not None:
                    walk(getattr(sub, "jaxpr", sub))

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("dtype", [jnp.int64, jnp.float64])
def test_lane_has_no_64bit_row_scatter(dtype):
    n, nseg = 4096, 9000
    v = jnp.zeros(n, dtype)
    g = jnp.zeros(n, jnp.int32)
    m = jnp.ones(n, jnp.bool_)
    lane = lambda v, g, m: segment.sorted_segment_sum(v, g, nseg, m)
    assert _wide_scatters(lane, n, v, g, m) == []
    # The scatter it replaces does hold one: the check can see it.
    old = lambda v, g, m: jax.ops.segment_sum(
        jnp.where(m, v, 0), g, num_segments=nseg
    )
    assert _wide_scatters(old, n, v, g, m)


# (platform, num_segments, dtype, rows, lane engaged)
SELECTION = [
    ("tpu", 16384, jnp.int64, "min", True),
    ("tpu", 16384, jnp.float64, "min", True),
    ("tpu", 8193, jnp.int64, "min", True),
    ("tpu", 8192, jnp.int64, "min", False),  # the MXU lane's
    ("tpu", 8192, jnp.float64, "min", False),
    ("tpu", 16384, jnp.int32, "min", False),
    ("tpu", 16384, jnp.float32, "min", False),
    ("tpu", 16384, jnp.int64, "below", False),
    # SORTED_MIN_ROWS rows make 8 tiles: 8 x 131,072 segment tables
    # hold as many entries as there are rows.
    ("tpu", 131071, jnp.float64, "min", True),
    ("tpu", 131072, jnp.float64, "min", False),
    ("cpu", 16384, jnp.int64, "min", False),
    ("cpu", 16384, jnp.float64, "min", False),
]


@pytest.mark.parametrize("platform,nseg,dtype,rows,engaged", SELECTION)
def test_lane_selection(platform, nseg, dtype, rows, engaged):
    n = segment.SORTED_MIN_ROWS - (rows == "below")
    with segment.platform_hint(platform):
        assert segment.sum_sorted_strategy(n, nseg, dtype) == engaged
        segment.reduce_lanes(reset=True)
        jax.eval_shape(
            lambda v, g, m: segment.seg_sum(v, g, nseg, m),
            jax.ShapeDtypeStruct((n,), dtype),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.bool_),
        )
        lanes = segment.reduce_lanes(reset=True)
    assert (lanes.get("sum_sorted", 0) >= 1) == engaged, lanes


def test_lane_selection_flag_and_force():
    n, nseg = segment.SORTED_MIN_ROWS, 16384
    flags.set("sorted_compact", False)
    try:
        with segment.platform_hint("tpu"):
            assert not segment.sum_sorted_strategy(n, nseg, jnp.int64)
    finally:
        flags.reset("sorted_compact")
    segment.set_sorted_strategy(True)
    try:
        with segment.platform_hint("cpu"):
            assert segment.sum_sorted_strategy(8, nseg, jnp.float64)
            # Forcing never moves a sum off the MXU lane's range or
            # widens it to 32-bit dtypes.
            assert not segment.sum_sorted_strategy(8, 8192, jnp.float64)
            assert not segment.sum_sorted_strategy(8, nseg, jnp.int32)
    finally:
        segment.set_sorted_strategy(None)
    segment.set_sorted_strategy(False)
    try:
        with segment.platform_hint("tpu"):
            assert not segment.sum_sorted_strategy(n, nseg, jnp.int64)
    finally:
        segment.set_sorted_strategy(None)


# -- end to end --------------------------------------------------------------

N_SERVICES, N_WINDOWS = 16, 640  # 10,240 (service, window) groups

SVC_LET = (
    "df = px.DataFrame(table='http_events')\n"
    "df.failure = df.resp_status >= 400\n"
    "df.timestamp = px.bin(df.time_, px.seconds(1))\n"
    "s = df.groupby(['service', 'timestamp']).agg(\n"
    "    n=('latency', px.count),\n"
    "    bytes=('resp_body_size', px.sum),\n"
    "    err=('failure', px.mean),\n"
    "    q=('latency', px.quantiles),\n"
    ")\n"
    "px.display(s, 'out')\n"
)


def _http_events(carnot, n=40_960, seed=11):
    rel = Relation.of(
        ("time_", T, SemanticType.ST_TIME_NS),
        ("service", S),
        ("resp_status", I),
        ("resp_body_size", I),
        ("latency", F),
    )
    t = carnot.table_store.create_table("http_events", rel)
    rng = np.random.default_rng(seed)
    data = {
        "time_": np.sort(rng.integers(0, N_WINDOWS * 10**9, n)),
        "service": rng.choice(
            [f"svc{i}" for i in range(N_SERVICES)], n
        ).astype(object),
        "resp_status": rng.choice([200, 404, 500], n, p=[0.8, 0.1, 0.1]),
        "resp_body_size": rng.integers(0, 1 << 40, n),
        "latency": rng.exponential(3e7, n),
    }
    for off in range(0, n, 4096):
        t.write_pydict({k: v[off : off + 4096] for k, v in data.items()})
    t.compact()
    t.stop()


def _by_key(rows, keys):
    return {tuple(k): i for i, k in enumerate(zip(*[rows[c] for c in keys]))}


def test_svc_let_shape_matches_host_engine(forced):
    """count, byte sum, f64 mean and quantiles over more than 8,192
    (service, window) groups, on a one-device mesh (the group states
    are 10,240 x 1,024 histogram bins), with the lane forced: the
    device answers as the host engine does, and the device.program span
    names the lane."""
    mesh = Mesh(np.array(jax.devices("cpu")[:1]), ("d",))
    ex = MeshExecutor(mesh=mesh, block_rows=4096)
    c_dev = Carnot(device_executor=ex)
    _http_events(c_dev)
    segment.reduce_lanes(reset=True)
    rows_d = c_dev.execute_query(SVC_LET).table("out")
    assert not ex.fallback_errors, ex.fallback_errors
    assert segment.reduce_lanes(reset=True).get("sum_sorted", 0) >= 1
    # The cold query folds while it stages; the warm one, a staged-cache
    # hit, runs the fold program under device.program.
    trace.clear()
    c_dev.execute_query(SVC_LET)
    programs = [s for s in trace.drain() if s.name == "device.program"]
    assert programs and all(
        "sum_sorted" in s.attrs.get("lanes", "").split(",") for s in programs
    ), [s.attrs for s in programs]
    c_host = Carnot(device_executor=None)
    _http_events(c_host)
    rows_h = c_host.execute_query(SVC_LET).table("out")
    keys = ("service", "timestamp")
    dd, hh = _by_key(rows_d, keys), _by_key(rows_h, keys)
    assert len(dd) > segment.MATMUL_MAX_SEGMENTS
    assert set(dd) == set(hh)
    for k, i in dd.items():
        j = hh[k]
        assert rows_d["n"][i] == rows_h["n"][j], k
        assert rows_d["bytes"][i] == rows_h["bytes"][j], k
        assert rows_d["err"][i] == pytest.approx(rows_h["err"][j], rel=1e-12)
        assert json.loads(rows_d["q"][i]) == json.loads(rows_h["q"][j]), k


def test_join_agg_over_many_keys_matches_host_engine(forced):
    """A device join-aggregation whose right side reduces per join key
    (f64 seg_sum over 10,000 keys) takes the lane when forced and
    matches the host join + group-by."""
    mesh = Mesh(np.array(jax.devices("cpu")), ("d",))
    rng = np.random.default_rng(3)
    nl, nr, nkeys = 12_000, 20_000, 10_000
    left = {
        "time_": np.arange(nl) * 10,
        "svc": rng.choice(["a", "b", "c"], nl).astype(object),
        "ep": rng.integers(0, nkeys, nl),
        "lat": rng.normal(100, 10, nl),
    }
    right = {
        "time_": np.arange(nr) * 10,
        "endpoint": rng.integers(0, nkeys, nr),
        "cost": rng.normal(5, 1, nr),
    }

    def build(executor):
        c = Carnot(device_executor=executor)
        tl = c.table_store.create_table(
            "reqs",
            Relation.of(
                ("time_", T, SemanticType.ST_TIME_NS),
                ("svc", S),
                ("ep", I),
                ("lat", F),
            ),
        )
        tl.write_pydict(left)
        tl.compact()
        tl.stop()
        tr = c.table_store.create_table(
            "costs",
            Relation.of(
                ("time_", T, SemanticType.ST_TIME_NS),
                ("endpoint", I),
                ("cost", F),
            ),
        )
        tr.write_pydict(right)
        tr.compact()
        tr.stop()
        return c

    q = (
        "l = px.DataFrame(table='reqs')\n"
        "r = px.DataFrame(table='costs')\n"
        "j = l.merge(r, how='inner', left_on=['ep'], right_on=['endpoint'],"
        " suffixes=['', '_r'])\n"
        "s = j.groupby(['svc']).agg(\n"
        "    n=('time_', px.count),\n"
        "    cost_total=('cost', px.sum),\n"
        "    cost_avg=('cost', px.mean),\n"
        ")\n"
        "px.display(s, 'out')\n"
    )
    ex = MeshExecutor(mesh=mesh, block_rows=512)
    cd = build(ex)
    segment.reduce_lanes(reset=True)
    rows_d = cd.execute_query(q).table("out")
    assert not ex.fallback_errors, ex.fallback_errors
    assert any(s.startswith("joinL|") for s in ex._program_cache)
    assert segment.reduce_lanes(reset=True).get("sum_sorted", 0) >= 1
    rows_h = build(None).execute_query(q).table("out")
    dd, hh = _by_key(rows_d, ("svc",)), _by_key(rows_h, ("svc",))
    assert set(dd) == set(hh) == {("a",), ("b",), ("c",)}
    for k, i in dd.items():
        j = hh[k]
        assert rows_d["n"][i] == rows_h["n"][j]
        for col in ("cost_total", "cost_avg"):
            assert rows_d[col][i] == pytest.approx(rows_h[col][j], rel=1e-9)
