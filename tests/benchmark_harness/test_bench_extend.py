"""A configuration, a traffic mix and a metric added as new files: the
harness finds each by name, and no file that was there changes."""

from __future__ import annotations

import hashlib
import json
import os

from bench_tiny import cpu_run, tiny_root  # noqa: F401


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[p] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_cell_from_new_files_only(tiny_root):  # noqa: F811
    bench = os.path.join(tiny_root, "benchmark")
    spec_path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    before = _digests(bench)

    with open(os.path.join(bench, "configs", "http_node.json")) as f:
        cfg = json.load(f)
    cfg.update(name="http_wide", services=48)
    with open(os.path.join(bench, "configs", "http_wide.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "trickle.json"), "w") as f:
        json.dump(
            {
                "why": "slow refreshes under a light writer",
                "loop": "open",
                "rate_per_s": 4.0,
                "span_s": 4,
                "align_ns": 2 * 10**9,
                "hot_s": 6.5,
                "ingest_events_per_s": 1000,
                "push_period_s": 0.05,
            },
            f,
        )
    with open(os.path.join(bench, "metrics", "queries_per_s.py"), "w") as f:
        f.write(
            "def read(run):\n"
            "    return len(run.records) / run.window_s\n"
        )
    # The spec is the one file a later PR adds entries to.
    spec["configs"].append(
        {
            "name": "http_wide",
            "source": "https://example.org/wide",
            "file": "benchmark/configs/http_wide.json",
            "reduced": ["rows"],
            "why": "more services",
        }
    )
    spec["workloads"].append(
        {
            "name": "http_wide.trickle",
            "config": "http_wide",
            "traffic": "trickle",
            "chips": 1,
            "why": "test cell",
        }
    )
    spec["end_to_end"].append(
        {
            "name": "queries_per_s",
            "unit": "1/s",
            "better": "higher",
            "bound": 0.05,
            "source": "host_clock",
            "workloads": ["http_wide.trickle"],
        }
    )
    with open(spec_path, "w") as f:
        json.dump(spec, f)

    res = cpu_run(tiny_root, "http_wide.trickle").result
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"queries_per_s", "setup_s"}
    after = _digests(bench)
    assert {p: after[p] for p in before} == before
