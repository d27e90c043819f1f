"""Staging codec (r13) + device-resident incremental ingest.

The codec's contract is LOSSLESSNESS: with ``staging_codec`` on, the
device-decoded blocks — and therefore every query result — must be
BIT-identical to the passthrough transfer. These tests pin that at
three levels: per-encoder round trips (including NaN floats, empty and
singleton columns, all-equal runs, and non-monotone "monotone" guesses
falling back to passthrough), full-query codec-on vs codec-off
bit-equality across agg/sketch shapes, and a fuzz sweep over random
dtype/cardinality mixes.

Resident ingest's contract is weaker by design: ring hits change the
stream WINDOWING (the documented r6 float re-association), so counts
and int sums stay exact while float sums carry the usual 1e-9 rel
tolerance — and the wire must go quiet (wire_bytes ≪ stage_bytes,
resident hits > 0) for the in-window span.
"""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from pixie_tpu.engine import Carnot
from pixie_tpu.ops import codec
from pixie_tpu.parallel import MeshExecutor
from pixie_tpu.parallel.staging import reset_cold_profile
from pixie_tpu.types import DataType, Relation, SemanticType
from pixie_tpu.utils import flags

F, I, S, T = (
    DataType.FLOAT64,
    DataType.INT64,
    DataType.STRING,
    DataType.TIME64NS,
)

D, NBLK, B = 8, 2, 256
TOTAL = D * NBLK * B


@pytest.fixture(scope="module")
def mesh():
    devs = np.array(jax.devices("cpu"))
    assert devs.size == 8, "conftest must provide 8 virtual devices"
    return Mesh(devs, ("d",))


def _bits(a):
    return a.view(np.uint8)


def _roundtrip(mesh, flat, rows, min_ratio=1.1):
    """(plan, decoded) — decoded is None when the planner passed."""
    plan = codec.plan_codec_local(flat, D, NBLK, B, rows, min_ratio)
    if plan is None:
        return None, None
    payload = codec.encode_window(flat, plan, rows)
    args = codec.put_payload(mesh, payload)
    out = np.asarray(codec.decoder(mesh, plan, NBLK, B)(*args))
    return plan, out


def _padded(vals, rows, dtype):
    flat = np.zeros(TOTAL, dtype=dtype)
    flat[:rows] = vals[:rows]
    return flat


# -- per-encoder round trips -------------------------------------------------


def test_delta_roundtrip_timestamps(mesh):
    rows = TOTAL - 137
    flat = _padded(
        np.arange(rows, dtype=np.int64) * 1000 + (5 << 40), rows, np.int64
    )
    plan, out = _roundtrip(mesh, flat, rows)
    assert plan is not None and plan.kind == "delta"
    assert np.array_equal(out.reshape(-1), flat)


def test_rle_roundtrip_runs(mesh):
    rng = np.random.default_rng(3)
    rows = TOTAL - 5
    vals = np.repeat(rng.integers(0, 4, rows // 64 + 1), 64)[:rows]
    flat = _padded(vals.astype(np.int64), rows, np.int64)
    plan, out = _roundtrip(mesh, flat, rows)
    assert plan is not None and plan.kind == "rle"
    assert np.array_equal(out.reshape(-1), flat)


def test_rle_nan_floats_bit_exact(mesh):
    # NaN != NaN under value compare; the codec compares BIT PATTERNS,
    # so NaN runs (and distinct NaN payloads) survive exactly.
    rows = TOTAL - 9
    vals = np.repeat(
        np.random.default_rng(4).standard_normal(rows // 128 + 1), 128
    )[:rows].copy()
    vals[::5] = np.nan
    vals[7] = np.float64(np.frombuffer(
        np.uint64(0x7FF80000DEADBEEF).tobytes(), np.float64
    )[0])  # non-default NaN payload
    flat = _padded(vals, rows, np.float64)
    plan, out = _roundtrip(mesh, flat, rows)
    assert plan is not None and plan.kind == "rle"
    assert np.array_equal(_bits(out.reshape(-1)), _bits(flat))


def test_all_equal_column(mesh):
    flat = _padded(np.full(TOTAL, 42, np.int64), TOTAL, np.int64)
    plan, out = _roundtrip(mesh, flat, TOTAL)
    assert plan is not None
    assert np.array_equal(out.reshape(-1), flat)


def test_empty_and_singleton(mesh):
    flat = np.zeros(TOTAL, np.int64)
    plan, out = _roundtrip(mesh, flat, 0)
    if plan is not None:
        assert np.array_equal(out.reshape(-1), flat)
    flat = _padded(np.array([99], np.int64), 1, np.int64)
    plan, out = _roundtrip(mesh, flat, 1)
    assert plan is not None
    assert np.array_equal(out.reshape(-1), flat)


def test_non_monotone_guess_falls_back_to_passthrough(mesh):
    # Wide-delta, high-churn ints: neither encoder pays — the planner
    # must pass rather than ship a bloated encoding.
    rng = np.random.default_rng(5)
    flat = _padded(rng.integers(0, 1 << 40, TOTAL), TOTAL, np.int64)
    plan, _ = _roundtrip(mesh, flat, TOTAL, min_ratio=1.4)
    assert plan is None


def test_random_floats_pass_through(mesh):
    flat = _padded(
        np.random.default_rng(6).standard_normal(TOTAL), TOTAL, np.float64
    )
    plan, _ = _roundtrip(mesh, flat, TOTAL, min_ratio=1.4)
    assert plan is None


def test_encode_overflow_raises_and_pack_ships_raw(mesh):
    # A plan whose guess a later window defeats must raise
    # CodecOverflow from encode — and pack_stream_window must catch it
    # and ship that window raw (correctness never rides the guess).
    bad = codec.CodecPlan(
        kind="delta",
        dtype=np.dtype(np.int64).str,
        d=D,
        shard_len=NBLK * B,
        delta_dtype=np.dtype(np.uint8).str,
        delta_off=0,
    )
    hostile = _padded(
        np.random.default_rng(7).integers(0, 1 << 30, TOTAL),
        TOTAL,
        np.int64,
    )
    with pytest.raises(codec.CodecOverflow):
        codec.encode_window(hostile, bad, TOTAL)

    from pixie_tpu.parallel import staging

    plan = staging.plan_stream(
        mesh,
        {"x": hostile[:TOTAL]},
        TOTAL,
        TOTAL,
        block_rows=B,
    )
    plan.codecs["x"] = bad  # poison the recipe
    rows, packed, _g, nbytes = staging.pack_stream_window(
        plan, {"x": hostile[:TOTAL]}, None, 0
    )
    assert isinstance(packed["x"], np.ndarray)  # raw fallback, not payload


def test_rle_overflow_guard(mesh):
    bad = codec.CodecPlan(
        kind="rle",
        dtype=np.dtype(np.int64).str,
        d=D,
        shard_len=NBLK * B,
        runs_cap=2,
    )
    hostile = _padded(np.arange(TOTAL, dtype=np.int64), TOTAL, np.int64)
    with pytest.raises(codec.CodecOverflow):
        codec.encode_window(hostile, bad, TOTAL)


def test_fuzz_roundtrip_dtype_cardinality_mixes(mesh):
    rng = np.random.default_rng(11)
    for trial in range(40):
        dtype = rng.choice(
            [np.int64, np.int32, np.uint16, np.uint8, np.float64,
             np.float32]
        )
        rows = int(rng.integers(0, TOTAL + 1))
        kind = rng.integers(0, 4)
        if np.dtype(dtype).kind == "f":
            vals = rng.standard_normal(max(rows, 1)).astype(dtype)
            if kind == 1:
                vals = np.repeat(vals, 32)[: max(rows, 1)]
            if kind == 2:
                vals[rng.random(vals.shape) < 0.3] = np.nan
        else:
            card = int(rng.choice([1, 2, 100, 100_000]))
            vals = rng.integers(0, card, max(rows, 1)).astype(dtype)
            if kind == 1:
                vals = np.sort(vals)
            elif kind == 2:
                vals = np.cumsum(
                    rng.integers(0, 3, max(rows, 1))
                ).astype(dtype)
        flat = _padded(vals, rows, dtype)
        plan, out = _roundtrip(mesh, flat, rows)
        if plan is None:
            continue
        assert np.array_equal(_bits(out.reshape(-1)), _bits(flat)), (
            trial, dtype, rows, plan,
        )


# -- query-level: codec on == codec off, streamed == monolithic --------------

AGG_PXL = (
    "df = px.DataFrame(table='http_events')\n"
    "df.failure = df.resp_status >= 400\n"
    "stats = df.groupby(['service']).agg(\n"
    "    n=('time_', px.count),\n"
    "    total=('latency', px.sum),\n"
    "    hi=('latency', px.max),\n"
    "    err=('failure', px.mean),\n"
    "    q=('latency', px.quantiles),\n"
    "    u=('resp_status', px.approx_count_distinct),\n"
    ")\n"
    "px.display(stats, 'out')\n"
)


def _seed_engine(mesh, n=12_000, seed=7, window_rows=2048):
    c = Carnot(
        device_executor=MeshExecutor(mesh=mesh, block_rows=256)
    )
    rel = Relation.of(
        ("time_", T, SemanticType.ST_TIME_NS),
        ("service", S),
        ("resp_status", I),
        ("latency", F),
    )
    t = c.table_store.create_table("http_events", rel)
    rng = np.random.default_rng(seed)
    data = {
        "time_": np.arange(n) * 10**6,
        "service": rng.choice(["a", "b", "c"], n).astype(object),
        "resp_status": rng.choice([200, 400, 500], n, p=[0.8, 0.1, 0.1]),
        "latency": rng.exponential(30.0, n),
    }
    for off in range(0, n, 2048):
        t.write_pydict({k: v[off : off + 2048] for k, v in data.items()})
    t.compact()
    t.stop()
    return c, t


def _cols(result, table="out"):
    tb = result.table(table)
    return {k: np.asarray(tb[k]) for k in tb}


def _assert_bit_identical(a: dict, b: dict):
    assert set(a) == set(b)
    for k in a:
        x, y = a[k], b[k]
        if x.dtype.kind == "f":
            assert np.array_equal(
                x.view(np.uint64), y.view(np.uint64)
            ), k
        else:
            assert np.array_equal(x, y), k


def test_query_codec_on_equals_off_bitwise(mesh):
    flags.set("streaming_window_rows", 2048)
    try:
        flags.set("staging_codec", True)
        c1, _ = _seed_engine(mesh)
        r1 = c1.execute_query(AGG_PXL)
        prof_on = reset_cold_profile()
        flags.set("staging_codec", False)
        c2, _ = _seed_engine(mesh)
        r2 = c2.execute_query(AGG_PXL)
        _assert_bit_identical(_cols(r1), _cols(r2))
        # time_ never stages (count reads no args) and latency/status
        # are incompressible here — but the profile keys must exist and
        # wire can never exceed stage.
        assert prof_on.get("wire_bytes", 0) <= prof_on.get(
            "stage_bytes", 0
        )
    finally:
        flags.reset("staging_codec")
        flags.reset("streaming_window_rows")


def test_streamed_equals_monolithic_with_codec(mesh):
    # Delta-compressible column consumed by an exact SUM: wire must
    # shrink AND the streamed fold must equal the monolithic one bit
    # for bit (int sums are order-exact).
    flags.set("staging_codec", True)
    try:
        rel = Relation.of(
            ("time_", T, SemanticType.ST_TIME_NS),
            ("service", S),
            ("seq", I),
        )
        n = 12_000

        def build(streaming):
            flags.set("streaming_stage", streaming)
            flags.set("streaming_window_rows", 2048)
            c = Carnot(
                device_executor=MeshExecutor(mesh=mesh, block_rows=256)
            )
            t = c.table_store.create_table("events", rel)
            rng = np.random.default_rng(9)
            for off in range(0, n, 3000):
                m = min(3000, n - off)
                t.write_pydict(
                    {
                        "time_": np.arange(off, off + m) * 10**6,
                        "service": rng.choice(["a", "b"], m).astype(
                            object
                        ),
                        "seq": np.arange(off, off + m) * 7 + (1 << 33),
                    }
                )
            t.compact()
            t.stop()
            reset_cold_profile()
            r = c.execute_query(
                "df = px.DataFrame(table='events')\n"
                "s = df.groupby(['service']).agg(\n"
                "    n=('time_', px.count), total=('seq', px.sum))\n"
                "px.display(s, 'out')\n"
            )
            return _cols(r), reset_cold_profile()

        streamed, prof_s = build(True)
        mono, prof_m = build(False)
        _assert_bit_identical(streamed, mono)
        # seq is delta-compressible (stride 7): the wire must carry
        # materially less than the decoded blocks on both paths.
        for prof in (prof_s, prof_m):
            assert prof["wire_bytes"] < prof["stage_bytes"] * 0.75, prof
    finally:
        flags.reset("staging_codec")
        flags.reset("streaming_stage")
        flags.reset("streaming_window_rows")


def test_query_fuzz_codec_vs_plain(mesh):
    # Random dtype/cardinality mixes at the QUERY level: every mix must
    # be bit-identical codec-on vs codec-off.
    rel = Relation.of(
        ("time_", T, SemanticType.ST_TIME_NS),
        ("k", S),
        ("a", I),
        ("b", F),
    )
    n = 9_000
    for seed in (21, 22, 23):
        rng = np.random.default_rng(seed)
        card = int(rng.choice([1, 3, 64]))
        data = {
            "time_": np.cumsum(rng.integers(1, 90, n)).astype(np.int64),
            "k": rng.choice(
                [f"k{i}" for i in range(card)], n
            ).astype(object),
            "a": rng.integers(0, int(rng.choice([2, 1 << 9, 1 << 35])), n),
            "b": np.where(
                rng.random(n) < 0.2,
                np.nan,
                np.repeat(rng.standard_normal(n // 16 + 1), 16)[:n],
            ),
        }
        outs = []
        for codec_on in (True, False):
            flags.set("staging_codec", codec_on)
            flags.set("streaming_window_rows", 2048)
            try:
                c = Carnot(
                    device_executor=MeshExecutor(
                        mesh=mesh, block_rows=256
                    )
                )
                t = c.table_store.create_table("fz", rel)
                for off in range(0, n, 2500):
                    t.write_pydict(
                        {k: v[off : off + 2500] for k, v in data.items()}
                    )
                t.compact()
                t.stop()
                r = c.execute_query(
                    "df = px.DataFrame(table='fz')\n"
                    "s = df.groupby(['k']).agg(\n"
                    "    n=('time_', px.count), sa=('a', px.sum),\n"
                    "    mx=('b', px.max), u=('a', "
                    "px.approx_count_distinct))\n"
                    "px.display(s, 'out')\n"
                )
                outs.append(_cols(r))
            finally:
                flags.reset("staging_codec")
                flags.reset("streaming_window_rows")
        _assert_bit_identical(outs[0], outs[1])


# -- device-resident incremental ingest --------------------------------------


def _resident_engine(mesh, n=20_000, window_rows=4096, seed=7):
    flags.set("resident_ingest", True)
    flags.set("resident_window_rows", window_rows)
    c = Carnot(device_executor=MeshExecutor(mesh=mesh, block_rows=512))
    rel = Relation.of(
        ("time_", T, SemanticType.ST_TIME_NS),
        ("service", S),
        ("resp_status", I),
        ("latency", F),
    )
    t = c.table_store.create_table("http_events", rel)
    rng = np.random.default_rng(seed)
    data = {
        "time_": np.arange(n) * 10**6,
        "service": rng.choice(["a", "b", "c"], n).astype(object),
        "resp_status": rng.choice([200, 400, 500], n, p=[0.8, 0.1, 0.1]),
        "latency": rng.exponential(30.0, n),
    }
    for off in range(0, n, 2048):
        t.write_pydict({k: v[off : off + 2048] for k, v in data.items()})
    t.compact()
    t.stop()
    return c, t, data


def test_resident_ingest_hot_table_stages_only_tail(mesh):
    try:
        c, t, data = _resident_engine(mesh)
        ex = c.device_executor
        snap = ex._resident.snapshot()["http_events"]
        assert snap["windows"] == 4  # 20000 rows / 4096 → 4 full windows
        assert snap["valid"]
        # Pool accounting: ring bytes are pinned (unevictable).
        pool = ex._staged_cache.snapshot()
        assert pool["resident_windows"] == 4
        assert pool["resident_bytes"] > 0
        assert pool["pinned_bytes"] >= pool["resident_bytes"]

        reset_cold_profile()
        r = c.execute_query(AGG_PXL)
        prof = reset_cold_profile()
        # 4 of 5 stream windows came from HBM: the wire went quiet for
        # the in-window span (only the tail + gids traveled).
        assert prof.get("stage_resident_hits") == 4.0, prof
        assert prof["wire_bytes"] < prof["stage_bytes"] / 3.0, prof

        # Exactness: counts/int outputs exact vs a plain engine; float
        # sums re-associate across the ring windowing (r6 tolerance).
        flags.set("resident_ingest", False)
        c2 = Carnot(
            device_executor=MeshExecutor(mesh=mesh, block_rows=512)
        )
        rel = t.relation
        t2 = c2.table_store.create_table("http_events", rel)
        n = len(data["time_"])
        for off in range(0, n, 2048):
            t2.write_pydict(
                {k: v[off : off + 2048] for k, v in data.items()}
            )
        t2.compact()
        t2.stop()
        r2 = c2.execute_query(AGG_PXL)
        a, b = _cols(r), _cols(r2)
        assert np.array_equal(a["service"], b["service"])
        assert np.array_equal(a["n"], b["n"])
        assert np.array_equal(a["u"], b["u"])
        np.testing.assert_allclose(a["total"], b["total"], rtol=1e-9)
        np.testing.assert_allclose(a["err"], b["err"], rtol=1e-9)
    finally:
        flags.reset("resident_ingest")
        flags.reset("resident_window_rows")


def test_resident_scan_row_set_and_warm_cache(mesh):
    try:
        c, t, data = _resident_engine(mesh)
        scan = (
            "df = px.DataFrame(table='http_events')\n"
            "df = df[df.resp_status >= 400]\n"
            "df = df[['time_', 'service', 'latency']]\n"
            "df = df.head(100000)\n"
            "px.display(df, 'out')\n"
        )
        reset_cold_profile()
        r = c.execute_query(scan)
        prof = reset_cold_profile()
        assert prof.get("stage_resident_hits", 0) >= 4.0, prof
        assert prof["wire_bytes"] < prof["stage_bytes"] / 3.0, prof
        got = sorted(np.asarray(r.table("out")["time_"]).tolist())
        want = sorted(
            data["time_"][data["resp_status"] >= 400].tolist()
        )
        assert got == want
        # Warm: the assembled entry serves the repeat query from cache.
        reset_cold_profile()
        r2 = c.execute_query(scan)
        prof2 = reset_cold_profile()
        assert prof2.get("wire_bytes", 0.0) == 0.0, prof2
        assert sorted(np.asarray(r2.table("out")["time_"]).tolist()) == want
    finally:
        flags.reset("resident_ingest")
        flags.reset("resident_window_rows")


def test_resident_ring_rolls_and_releases_accounting(mesh):
    try:
        flags.set("resident_max_windows", 2)
        c, t, _ = _resident_engine(mesh)
        ex = c.device_executor
        snap = ex._resident.snapshot()["http_events"]
        assert snap["windows"] == 2  # rolled 4 → 2
        pool = ex._staged_cache.snapshot()
        assert pool["resident_windows"] == 2
        ring = ex._resident.ring_for("http_events")
        ring.release_all()
        pool = ex._staged_cache.snapshot()
        assert pool["resident_windows"] == 0
        assert pool["resident_bytes"] == 0
    finally:
        flags.reset("resident_ingest")
        flags.reset("resident_window_rows")
        flags.reset("resident_max_windows")


def test_resident_ring_invalidates_on_row_gap(mesh):
    try:
        c, t, _ = _resident_engine(mesh)
        ex = c.device_executor
        ring = ex._resident.ring_for("http_events")
        # Simulate a listener that missed rows: the ring must disable
        # itself (and free its windows), never serve stale windows.
        ring.on_append(ring._next_row + 5, _FakeBatch())
        assert not ring._valid
        assert ex._staged_cache.snapshot()["resident_windows"] == 0
        # Queries still work (staging path).
        r = c.execute_query(AGG_PXL)
        assert len(_cols(r)["n"]) == 3
    finally:
        flags.reset("resident_ingest")
        flags.reset("resident_window_rows")


class _FakeBatch:
    num_rows = 5


def test_time_bounded_query_skips_resident(mesh):
    try:
        c, t, data = _resident_engine(mesh)
        reset_cold_profile()
        r = c.execute_query(
            "df = px.DataFrame(table='http_events', start_time=0, "
            f"end_time={int(data['time_'][5000])})\n"
            "s = df.groupby(['service']).agg(n=('time_', px.count))\n"
            "px.display(s, 'out')\n"
        )
        prof = reset_cold_profile()
        assert prof.get("stage_resident_hits", 0.0) == 0.0
        assert int(np.asarray(r.table("out")["n"]).sum()) == 5001
    finally:
        flags.reset("resident_ingest")
        flags.reset("resident_window_rows")


# -- admission staging-bytes estimate (r13 satellite) ------------------------


def test_estimate_staging_bytes_metadata_and_observed(mesh):
    from pixie_tpu.parallel import staging
    from pixie_tpu.serving.admission import estimate_staging_bytes

    rel = Relation.of(("time_", T), ("v", F), ("s", S))
    from pixie_tpu.table.table import Table

    t = Table(rel, name="est_t")
    t.write_pydict(
        {
            "time_": np.arange(1000, dtype=np.int64),
            "v": np.zeros(1000),
            "s": np.array(["x"] * 1000, dtype=object),
        }
    )
    # No staging observed yet: conservative raw widths + mask.
    est = estimate_staging_bytes(t)
    assert est == 1000 * (8 + 8 + 4 + 1)
    # Observed bytes-per-row takes over once a staging records it.
    staging.record_observed_bpr("est_t", 5_000, 1000)
    assert estimate_staging_bytes(t) == 5_000
    staging.OBSERVED_BPR.pop("est_t", None)


def test_admission_rejects_doomed_stage_before_it_starts():
    from pixie_tpu.serving.admission import (
        AdmissionController,
        AdmissionRejected,
    )

    snap = {"budget_bytes": 1000, "pinned_bytes": 300}
    ctl = AdmissionController(
        max_concurrent=4, max_queue=4, timeout_s=1.0,
        budget_fn=lambda: snap,
    )
    # Fits: 300 pinned + 600 estimated <= 1000.
    ctl.acquire("t", estimated_bytes=600).release()
    # Doomed: even evicting every unpinned byte leaves 300 + 800 > 1000.
    with pytest.raises(AdmissionRejected) as ei:
        ctl.acquire("t", estimated_bytes=800)
    assert ei.value.reason == "hbm_budget"
    assert "estimated" in ei.value.detail
    # Without an estimate the old behavior holds (admit until pinned
    # exceeds budget).
    ctl.acquire("t").release()
    snap["pinned_bytes"] = 1000
    with pytest.raises(AdmissionRejected):
        ctl.acquire("t")


def test_broker_estimates_from_script_tables(mesh):
    from pixie_tpu.serving.admission import make_store_estimator
    from pixie_tpu.table.table_store import TableStore

    rel = Relation.of(("time_", T), ("v", F))
    store = TableStore()
    t = store.create_table("tiny", rel)
    t.write_pydict(
        {"time_": np.arange(100, dtype=np.int64), "v": np.zeros(100)}
    )
    est = make_store_estimator(store)
    assert est("tiny") == 100 * (8 + 8 + 1)
    assert est("missing") == 0

    from pixie_tpu.exec import BridgeRouter
    from pixie_tpu.vizier import MessageBus, QueryBroker

    broker = QueryBroker(
        MessageBus(), BridgeRouter(), table_relations={"tiny": rel},
        staging_estimator=est,
    )
    q = "df = px.DataFrame(table='tiny')\npx.display(df, 'o')\n"
    assert broker._estimate_staging(q) == est("tiny")
    assert broker._estimate_staging("no tables here") == 0
    broker.stop()


# -- u4 nibble deltas + gid-stream codec (r16) -------------------------------


def test_delta_nibble_picked_and_roundtrips(mesh):
    """A fixed-cadence timestamp column (delta range 0) plans the
    nibble encoding and round-trips bit-exact; wire bytes are ~half of
    the u8 delta encoding."""
    rows = TOTAL - 31
    flat = _padded(
        np.arange(rows, dtype=np.int64) * 8 + (3 << 41), rows, np.int64
    )
    plan, out = _roundtrip(mesh, flat, rows)
    assert plan is not None and plan.kind == "delta"
    assert plan.delta_dtype == "nib"
    assert np.array_equal(out.reshape(-1), flat)
    u8 = codec.CodecPlan(
        kind="delta", dtype=plan.dtype, d=plan.d,
        shard_len=plan.shard_len,
        delta_dtype=np.dtype(np.uint8).str, delta_off=plan.delta_off,
    )
    assert plan.wire_nbytes() < 0.6 * u8.wire_nbytes()


def test_delta_nibble_fuzz_bit_exact(mesh):
    """Random small-delta columns (range <= 15 around arbitrary — incl.
    negative — frame offsets, random row counts incl. odd lengths) stay
    bit-exact through the nibble pack."""
    rng = np.random.default_rng(23)
    for trial in range(25):
        rows = int(rng.integers(1, TOTAL + 1))
        lo = int(rng.integers(-1000, 1000))
        width = int(rng.integers(0, 16))
        deltas = rng.integers(lo, lo + width + 1, rows)
        base = int(rng.integers(-(1 << 40), 1 << 40))
        vals = base + np.concatenate(
            [[0], np.cumsum(deltas[1:])]
        ).astype(np.int64)
        flat = _padded(vals, rows, np.int64)
        plan, out = _roundtrip(mesh, flat, rows, min_ratio=1.01)
        if plan is None or plan.kind != "delta":
            continue  # RLE/passthrough may win; exactness covered above
        assert plan.delta_dtype == "nib", (trial, plan)
        assert np.array_equal(out.reshape(-1), flat), (trial, rows, lo)


def test_delta_nibble_overflow_raises(mesh):
    bad = codec.CodecPlan(
        kind="delta",
        dtype=np.dtype(np.int64).str,
        d=D,
        shard_len=NBLK * B,
        delta_dtype="nib",
        delta_off=0,
    )
    hostile = _padded(
        np.cumsum(np.full(TOTAL, 200, np.int64)), TOTAL, np.int64
    )
    with pytest.raises(codec.CodecOverflow):
        codec.encode_window(hostile, bad, TOTAL)


def test_gid_stream_plans_and_roundtrips(mesh):
    """Sorted group keys -> run-heavy gids -> the stream plan encodes
    the gids lane, and the decoded device gids are bit-identical to the
    raw put."""
    from pixie_tpu.parallel import staging

    rows = TOTAL
    # 4 groups, sorted: gids RLE to ~nothing.
    gids = np.sort(
        np.random.default_rng(31).integers(0, 4, rows)
    ).astype(np.int32)
    cols = {"v": np.arange(rows, dtype=np.int64)}
    plan = staging.plan_stream(
        mesh, cols, rows, rows, block_rows=B,
        num_groups=4, has_gids=True, gids=gids,
    )
    assert plan.gid_codec is not None, "gid lane did not plan a codec"
    _rows, _packed, pgids, _nbytes = staging.pack_stream_window(
        plan, cols, gids, 0
    )
    assert isinstance(pgids, codec.CodecPayload)
    assert pgids.nbytes < 0.2 * staging.staged_gid_nbytes(pgids)
    dev = staging.put_window_gids(mesh, pgids, plan.nblk, plan.b)
    raw = np.zeros(TOTAL, plan.gid_dtype)
    raw[:rows] = gids.astype(plan.gid_dtype)
    assert np.array_equal(
        np.asarray(dev).reshape(-1), raw
    )


def test_gid_stream_random_gids_pass_through(mesh):
    """High-churn gids defeat both encoders: the plan passes and pack
    ships the raw blocks (no bloated encodings, no payload)."""
    from pixie_tpu.parallel import staging

    rows = TOTAL
    gids = np.random.default_rng(37).integers(0, 50_000, rows).astype(
        np.int32
    )
    cols = {"v": np.arange(rows, dtype=np.int64)}
    plan = staging.plan_stream(
        mesh, cols, rows, rows, block_rows=B,
        num_groups=50_000, has_gids=True, gids=gids,
    )
    assert plan.gid_codec is None
    _rows, _packed, pgids, _n = staging.pack_stream_window(
        plan, cols, gids, 0
    )
    assert isinstance(pgids, np.ndarray)


def _seed_sorted_engine(mesh, n=12_000, seed=7):
    """An engine with a table SORTED by service, so host gids are
    run-heavy and the gid codec engages."""
    c = Carnot(device_executor=MeshExecutor(mesh=mesh, block_rows=256))
    rel = Relation.of(
        ("time_", T, SemanticType.ST_TIME_NS),
        ("service", S),
        ("resp_status", I),
        ("latency", F),
    )
    rng = np.random.default_rng(seed)
    data = {
        "time_": np.arange(n) * 10**6,
        "service": rng.choice(["a", "b", "c"], n).astype(object),
        "resp_status": rng.choice([200, 400, 500], n),
        "latency": rng.exponential(30.0, n),
    }
    order = np.argsort(data["service"].astype(str), kind="stable")
    t = c.table_store.create_table("http_sorted", rel)
    t.write_pydict({k: np.asarray(v)[order] for k, v in data.items()})
    t.compact()
    t.stop()
    return c


def test_query_with_sorted_keys_gid_codec_bit_identical(mesh):
    """Host-gids group-by over a key-sorted table: results with the gid
    codec riding are bit-identical to codec-off execution."""
    # A computed string key forces the host-gids path (device
    # dictionary codes can't carry svc2).
    q = (
        "df = px.DataFrame(table='http_sorted')\n"
        "df.svc2 = df.service + df.service\n"
        "s = df.groupby(['svc2']).agg(\n"
        "    n=('time_', px.count),\n"
        "    total=('latency', px.sum),\n"
        ")\n"
        "px.display(s, 'out')\n"
    )
    flags.set("staging_codec", True)
    try:
        on = _seed_sorted_engine(mesh).execute_query(q).table("out")
    finally:
        flags.reset("staging_codec")
    flags.set("staging_codec", False)
    try:
        off = _seed_sorted_engine(mesh).execute_query(q).table("out")
    finally:
        flags.reset("staging_codec")
    assert set(on) == set(off)
    for col in on:
        a, b = np.asarray(on[col]), np.asarray(off[col])
        assert a.dtype == b.dtype and np.array_equal(a, b), col


_RESTAGE_PXL = (
    "df = px.DataFrame(table='restaged')\n"
    "s = df.groupby(['service']).agg(\n"
    "    hi=('ts', px.max),\n"
    "    lat=('latency', px.mean),\n"
    ")\n"
    "px.display(s, 'out')\n"
)


@pytest.mark.parametrize(
    "column, kind", [("ts", "delta"), ("latency", None)],
    ids=["compressible", "incompressible"],
)
def test_codec_plan_same_on_first_and_twentieth_restaging(
    mesh, monkeypatch, column, kind
):
    """The codec bar is ``staging_codec_min_ratio``: a table restaged
    twenty times (a new version each time) plans each column's encoder
    the same way the twentieth time as the first."""
    from pixie_tpu.parallel import staging

    plans = []
    plan_stream = staging.plan_stream

    def spy(*a, **k):
        plan = plan_stream(*a, **k)
        plans.append({n: cp.kind for n, cp in plan.codecs.items()})
        return plan

    monkeypatch.setattr(staging, "plan_stream", spy)
    flags.set("staging_codec", True)
    flags.set("streaming_window_rows", 1024)
    try:
        c = Carnot(device_executor=MeshExecutor(mesh=mesh, block_rows=256))
        rel = Relation.of(
            ("time_", T, SemanticType.ST_TIME_NS),
            ("service", S),
            ("ts", I),
            ("latency", F),
        )
        t = c.table_store.create_table("restaged", rel)
        rng = np.random.default_rng(29)
        n = 0
        for _ in range(20):
            m = 512
            t.write_pydict(
                {
                    "time_": np.arange(n, n + m) * 10**6,
                    "service": rng.choice(["a", "b", "c"], m).astype(object),
                    "ts": 10**15 + np.arange(n, n + m) * 1000,
                    "latency": rng.exponential(30.0, m),
                }
            )
            n += m
            c.execute_query(_RESTAGE_PXL)
            assert not c.device_executor.fallback_errors
    finally:
        flags.reset("staging_codec")
        flags.reset("streaming_window_rows")
    assert len(plans) >= 20
    assert plans[0].get(column) == kind
    assert plans[-1].get(column) == plans[0].get(column)
