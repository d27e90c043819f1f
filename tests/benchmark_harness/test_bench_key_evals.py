"""key_evals.live: the key plan's evaluations per refresh, read from a
traced tiny live run on the CPU, and nothing from a program without the
counter."""

from __future__ import annotations

import importlib.util
import os
import types

import pytest
from bench_tiny import cpu_run, tiny_root  # noqa: F401

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _reader():
    path = os.path.join(REPO, "benchmark", "metrics", "key_evals.live.py")
    spec = importlib.util.spec_from_file_location("_t_key_evals", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize(
    "profiles, want",
    [
        ([{"read_batches": 6.0, "key_evals": 9.0}] * 2, 9.0),
        ([{"read_batches": 6.0, "key_evals": 2.0}, {"key_evals": 0.0}], 1.0),
        ([{"read_batches": 6.0}] * 2, None),  # a program without the counter
        ([], None),  # no refresh answered
    ],
    ids=["per_refresh", "mean", "no_counter", "no_refresh"],
)
def test_key_evals_reader(profiles, want):
    run = types.SimpleNamespace(
        done=[types.SimpleNamespace(profile=p) for p in profiles]
    )
    assert _reader()(run) == want


def test_traced_tiny_live_run_reads_key_evals(tiny_root):
    out = cpu_run(tiny_root, "http_node.live", seconds=3.2, trace=True)
    res = out.result
    assert res["correct"], res["checks"]
    # A tiny refresh's 14,000 rows fit one 131,072-row chunk, whatever
    # the number of pushes they came in; a refresh of a table version
    # already planned hits the key-plan cache and evaluates nothing.
    evals = [r.profile.get("key_evals", 0.0) for r in out.records]
    assert set(evals) <= {0.0, 1.0} and 1.0 in evals
    assert res["metrics"]["key_evals.live"]["value"] == sum(evals) / len(evals)
    assert res["metrics"]["read_batches.live"]["value"] > 2.0
