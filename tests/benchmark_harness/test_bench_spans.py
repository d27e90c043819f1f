"""The per-layer metrics that read the program's spans: on a hand-made
trace with hand-worked numbers, on a trace of a program without the
spans, and on traced tiny runs of both cells on the CPU."""

from __future__ import annotations

import importlib.util
import math
import os

import pytest
from bench_tiny import cpu_run, tiny_root  # noqa: F401
from test_bench_trace import DEVICE, _events, _meta, _summary

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NEW = {
    "http_node.live": ("keys_ms.live", "read_batches.live", "untraced_ms.live"),
    "http_node.history": ("finalize_ms.history", "suffix_ms.history"),
}

NAMES = [
    "bench.window", "bench.query", "query", "compile", "fragment",
    "device.execute", "device.plan_keys", "device.stage", "device.finalize",
    "exec", "device.windowize",
]
ID = {n: i for i, n in enumerate(NAMES, 1)}


def _host(query_line, other_line):
    return (
        'planes { id: 9 name: "/host:CPU"\n'
        'lines { id: 1 name: "python" timestamp_ns: 0\n'
        + _events([(ID[n], a, b) for n, a, b in query_line])
        + "}\n"
        'lines { id: 2 name: "bench-writer" timestamp_ns: 0\n'
        + _events([(ID[n], a, b) for n, a, b in other_line])
        + "}\n"
        + _meta(NAMES)
        + "}\n"
    )


# Two refreshes of 4000 and 3000 ns. The first: compile 100, the offload
# 2600 (plan_keys 1000 of it, stage 800, finalize 400, 400 in no phase),
# exec 500; 1200 ns in no leaf span. The second: plan_keys 600 from
# before bench.query (clipped to 400), windowize 300, exec 1000; 1300 ns
# in no leaf span. A plan_keys span on the writer's thread counts nowhere.
PROGRAM = _host(
    [
        ("bench.window", 0, 20000),
        ("bench.query", 1000, 5000),
        ("query", 1050, 4950),
        ("compile", 1100, 1200),
        ("fragment", 1300, 4800),
        ("device.execute", 1300, 3900),
        ("device.plan_keys", 1300, 2300),
        ("device.stage", 2400, 3200),
        ("device.finalize", 3400, 3800),
        ("exec", 4000, 4500),
        ("device.plan_keys", 5600, 6200),
        ("bench.query", 5800, 8800),
        ("device.windowize", 6300, 6600),
        ("exec", 7000, 8000),
    ],
    [("device.plan_keys", 1000, 5000)],
)
BARE = _host([("bench.window", 0, 20000), ("bench.query", 1000, 5000)], [])


def _reader(name):
    path = os.path.join(REPO, "benchmark", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_t_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Run:
    def __init__(self, trace, done=()):
        self.trace, self.done = trace, list(done)


def test_hand_worked_span_metrics():
    from benchmark import spans

    run = Run(_summary(DEVICE + PROGRAM))
    # (1000 + 400 + 300) / 2 queries, in ms.
    assert _reader("keys_ms.live")(run) == pytest.approx(850 / 1e6)
    assert _reader("untraced_ms.live")(run) == pytest.approx(1250 / 1e6)
    assert _reader("finalize_ms.history")(run) == pytest.approx(200 / 1e6)
    assert _reader("suffix_ms.history")(run) == pytest.approx(750 / 1e6)
    selfs = spans.self_ms(run.trace)
    assert sum(selfs.values()) == pytest.approx(3500 / 1e6)
    # The offload's 400 ns outside its phases, over two queries.
    assert selfs["device.execute"] == pytest.approx(200 / 1e6)
    assert selfs["bench.query"] == pytest.approx((100 + 1300) / 2 / 1e6)


def test_span_readers_without_program_spans_read_nothing():
    from benchmark import spans

    run = Run(_summary(DEVICE + BARE))
    for names in NEW.values():
        for name in names:
            assert _reader(name)(run) is None, name
    assert spans.self_ms(run.trace) is None
    assert spans.self_ms(None) is None


@pytest.mark.parametrize("workload", sorted(NEW))
def test_traced_tiny_run_reads_program_spans(tiny_root, monkeypatch, workload):
    from benchmark import xtrace

    loaded = []
    load = xtrace.load
    monkeypatch.setattr(xtrace, "load", lambda d: loaded.append(load(d)) or loaded[-1])
    live = workload.endswith(".live")
    res = cpu_run(tiny_root, workload, seconds=3.2 if live else 1.0, trace=True).result
    assert res["correct"], res["checks"]
    for name in NEW[workload]:
        assert math.isfinite(res["metrics"][name]["value"]), name
    (s,) = loaded
    want = {"query", "compile", "fragment", "device.execute", "device.finalize", "exec"}
    if live:
        want.add("device.plan_keys")
    for _, qa, qb, line in [h for h in s.host if h[0] == xtrace.QUERY]:
        inside = {h[0] for h in s.host if h[3] == line and qa <= h[1] and h[2] <= qb}
        assert want <= inside, want - inside
