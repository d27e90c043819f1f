"""The trace reduction, on a hand-made trace with hand-worked numbers and
on a small trace recorded on a v5e."""

from __future__ import annotations

import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "v5e_sample.xplane.pb")


def _events(spans):
    """(metadata id, start ns, end ns) -> text-proto events on a line at 0."""
    return "".join(
        f"events {{ metadata_id: {m} offset_ps: {a * 1000} "
        f"duration_ps: {(b - a) * 1000} }}\n"
        for m, a, b in spans
    )


def _meta(names):
    return "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
        for i, n in enumerate(names, 1)
    )


def _device(plane_id, name, spans, names):
    return (
        f'planes {{ id: {plane_id} name: "{name}"\n'
        f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0\n{_events(spans)}}}\n'
        f"{_meta(names)}}}\n"
    )


HOST = (
    'planes { id: 9 name: "/host:CPU"\n'
    'lines { id: 1 name: "python" timestamp_ns: 0\n'
    + _events(
        [
            (1, 1000, 11000),  # bench.window
            (2, 1000, 5000),  # bench.query
            (2, 6000, 9000),  # bench.query
            (3, 9000, 9500),  # bench.materialize
            (4, 9100, 9400),  # PjitFunction(fin), under it
        ]
    )
    + "}\n"
    'lines { id: 2 name: "bench-writer" timestamp_ns: 0\n'
    + _events([(5, 5200, 5800)])  # bench.push
    + "}\n"
    + _meta(
        [
            "bench.window",
            "bench.query",
            "bench.materialize",
            "PjitFunction(fin)",
            "bench.push",
        ]
    )
    + "}\n"
)
# fusion.2 runs inside while.1's loop; copy.3 runs past the window's end.
DEVICE = _device(
    1,
    "/device:TPU:0",
    [(1, 1000, 4000), (2, 2000, 3000), (2, 7000, 8000), (3, 10500, 12000)],
    ["%while.1 = (s32[]) while(s32[] %x)", "fusion.2", "copy.3"],
)


def _summary(text):
    from jax.profiler import ProfileData

    from benchmark import xtrace

    return xtrace.summarize(ProfileData.from_text_proto(text))


def test_hand_worked_trace():
    s = _summary(DEVICE + HOST)
    # Busy: [1000, 4000] + [7000, 8000] + [10500, 11000] = 4500 ns of 10000.
    assert s.window_s == pytest.approx(1e-5)
    assert s.busy_s == pytest.approx(4.5e-6)
    # Inside the two queries: 3000 + 1000 ns.
    assert s.device_s_in(s.queries) == pytest.approx(4e-6)
    b = s.breakdown()
    # Self time: the loop's 3000 ns less its body's 1000.
    assert b["device_ops"] == [
        ["%while.1", pytest.approx(2e-6)],
        ["fusion.2", pytest.approx(2e-6)],
        ["copy.3", pytest.approx(5e-7)],
    ]
    assert b["idle_gaps"] == [
        ["bench.push", pytest.approx(3e-6)],
        ["bench.materialize > PjitFunction(fin)", pytest.approx(2.5e-6)],
    ]


def test_busy_is_averaged_over_devices():
    second = _device(2, "/device:TPU:1", [(1, 1000, 1500)], ["fusion.1"])
    s = _summary(DEVICE + second + HOST)
    assert s.busy_s == pytest.approx((4500 + 500) / 2 / 1e9)


def test_idle_metric_reader_and_no_trace():
    from benchmark import xtrace

    class Run:
        trace = _summary(DEVICE + HOST)

    assert xtrace.idle_pct(Run) == pytest.approx(55.0)
    Run.trace = None
    assert xtrace.idle_pct(Run) is None


def test_trace_without_window_is_refused():
    with pytest.raises(ValueError):
        _summary(DEVICE)


def test_recorded_v5e_trace():
    """Three small programs, each under a bench.query span, recorded on
    one v5e (calibrate.py trace-sample)."""
    from jax.profiler import ProfileData

    from benchmark import xtrace

    s = xtrace.summarize(ProfileData.from_file(RECORDED))
    assert len(s.busy) == 1
    assert len(s.queries) == 3
    assert 0 < s.busy_s < s.window_s
    # The device's clock sits ~1.7 ms before the host's in this trace
    # (its ops start before the host dispatches them), so these 24 us
    # ops fall outside their 1 ms query spans; over the window they count.
    assert s.device_s_in([s.window]) == pytest.approx(s.busy_s)
    assert s.device_s_in(s.queries) <= s.busy_s + 1e-12
    b = s.breakdown()
    assert b["device_ops"] and len(b["device_ops"]) <= 10
    assert b["idle_gaps"] and len(b["idle_gaps"]) <= 10
