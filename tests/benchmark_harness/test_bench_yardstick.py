"""Peaks, lower-bound bytes against hand-worked values, the refusals of a
run that cannot measure, and the spec's own limits."""

from __future__ import annotations

import json
import os
import re

import pytest
from bench_tiny import REPO


def _json(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


def test_peaks_table_has_the_v5e_with_its_source():
    peaks = _json("benchmark", "peaks.json")
    assert "cloud.google.com" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes"] == 16e9


@pytest.mark.parametrize(
    "config,bits,bytes_per_query",
    [
        # 16 services: 4 bits; 10 paths: 4; 64 clients and '-': 7; 4 status
        # codes: 2; 67,095,000 rows at 3,500/s span 1,917 10 s windows: 11;
        # body sizes below 2^14: 14; latency: 64.
        ("http_node", 4 + 4 + 7 + 2 + 11 + 14 + 64, 67_095_000 * 106 // 8),
    ],
)
def test_lower_bound_bytes(config, bits, bytes_per_query):
    import importlib

    cfg = _json("benchmark", "configs", f"{config}.json")
    ds = importlib.import_module(f"benchmark.datasets.{cfg['dataset']}")
    assert ds.lower_bound_bits(cfg) == bits
    assert cfg["rows"] * ds.lower_bound_bits(cfg) // 8 == bytes_per_query


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize(
    "devices,why",
    [
        ([_Dev("cpu", "cpu")], "no TPU"),
        ([_Dev("tpu", "TPU v9 imaginary")], "device kind"),
        ([_Dev("tpu", "TPU v5 lite")], "needs 4 chips"),
    ],
)
def test_a_run_that_cannot_measure_exits(monkeypatch, devices, why):
    import jax

    from benchmark import harness

    monkeypatch.setattr(jax, "devices", lambda *a: devices)
    chips = 4 if "4 chips" in why else 1
    with pytest.raises(SystemExit) as e:
        harness.require_chip(chips)
    assert e.value.code != 0


def test_run_on_cpu_prints_no_result(capsys):
    from benchmark import run

    with pytest.raises(SystemExit) as e:
        run.main(
            ["--workload", "http_node.history", "--seed", str(2**40),
             "--seconds", "1"]
        )
    assert e.value.code != 0
    assert capsys.readouterr().out == ""


def test_spec_names_and_files():
    """Every name of BENCHMARK.json has a file of its own under paths."""
    spec = _json("BENCHMARK.json")
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    metrics = spec["end_to_end"] + spec["per_layer"]
    for m in metrics:
        assert name.match(m["name"])
        assert os.path.exists(
            os.path.join(REPO, "benchmark", "metrics", f"{m['name']}.py")
        )
    for w in spec["workloads"]:
        assert os.path.exists(
            os.path.join(REPO, "benchmark", "traffic", f"{w['traffic']}.json")
        )
    for c in spec["configs"]:
        cfg = _json(c["file"])
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert set(cfg["limits"]) == set(
            __import__(
                f"benchmark.datasets.{cfg['dataset']}", fromlist=["compare"]
            ).compare({}, {})
        )
