"""Fixtures for the benchmark's CPU rehearsals: a copy of the benchmark's
files in a temporary checkout, with every configuration shrunk to a size
a test run can hold. The program runs on the CPU here (tests/conftest.py);
the harness's look for a TPU is skipped by calling ``harness.run`` with
the CPU's devices and a stand-in peak."""

from __future__ import annotations

import json
import os
import shutil
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# 2 s windows of 7,000 rows, so that the tiny table's 10 s hold five whole ones.
TINY = {"http_node": {"rows": 35000, "block_rows": 1 << 14, "window_ns": 2 * 10**9}}
# Two whole 2 s windows a refresh, as the live mix holds thirty of 10 s,
# and refreshes often enough that a short run holds several.
TINY_TRAFFIC = {
    "live": {"span_s": 4, "align_ns": 2 * 10**9, "hot_s": 6.5, "rate_per_s": 1.6}
}
CPU_PEAKS = {"hbm_bytes_per_s": 1e9}  # a stand-in: nothing here is a device number


def make_root(path: str, **overrides) -> str:
    """A checkout holding BENCHMARK.json and the benchmark's files."""
    shutil.copytree(
        os.path.join(REPO, "benchmark"),
        os.path.join(path, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), path)
    for name, sizes in TINY.items():
        p = os.path.join(path, "benchmark", "configs", f"{name}.json")
        with open(p) as f:
            cfg = json.load(f)
        cfg.update(sizes, **overrides.get(name, {}))
        with open(p, "w") as f:
            json.dump(cfg, f)
    for name, params in TINY_TRAFFIC.items():
        p = os.path.join(path, "benchmark", "traffic", f"{name}.json")
        with open(p) as f:
            mix = json.load(f)
        mix.update(params)
        with open(p, "w") as f:
            json.dump(mix, f)
    return path


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(str(tmp_path))


def cpu_run(root: str, workload: str, seed: int = 2**33 + 5, seconds=1.0,
            trace=False):
    import jax

    from benchmark import harness

    cell = harness.load_cell(workload, root)
    return harness.run(
        cell, seed, seconds, trace, jax.devices(), CPU_PEAKS, time.perf_counter()
    )
