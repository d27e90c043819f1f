"""read_visits.live: the table segments the cursors examined per
refresh, read from a traced tiny live run on the CPU, and nothing from a
program without the counter."""

from __future__ import annotations

import importlib.util
import os
import types

import pytest
from bench_tiny import cpu_run, tiny_root  # noqa: F401

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _reader():
    path = os.path.join(REPO, "benchmark", "metrics", "read_visits.live.py")
    spec = importlib.util.spec_from_file_location("_t_read_visits", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize(
    "profiles, want",
    [
        ([{"read_batches": 6.0, "read_visits": 40.0}] * 2, 40.0),
        ([{"read_visits": 30.0}, {"read_visits": 0.0}], 15.0),
        ([{"read_batches": 6.0}] * 2, None),  # a program without the counter
        ([], None),  # no refresh answered
    ],
    ids=["per_refresh", "mean", "no_counter", "no_refresh"],
)
def test_read_visits_reader(profiles, want):
    run = types.SimpleNamespace(
        done=[types.SimpleNamespace(profile=p) for p in profiles]
    )
    assert _reader()(run) == want


def test_traced_tiny_live_run_reads_read_visits(tiny_root):
    out = cpu_run(tiny_root, "http_node.live", seconds=3.2, trace=True)
    res = out.result
    assert res["correct"], res["checks"]
    # Each refresh's walks visit a segment per batch they read, plus the
    # segments before the range once a walk.
    visits = [r.profile.get("read_visits", 0.0) for r in out.records]
    batches = [r.profile.get("read_batches", 0.0) for r in out.records]
    assert all(v >= b for v, b in zip(visits, batches)) and max(visits) > 0
    assert res["metrics"]["read_visits.live"]["value"] == sum(visits) / len(visits)
