"""One benchmark run of a cell, with every metric and, when traced, the
program's spans per query.

    python3 tools/span_table.py --workload http_node.live --seed 7 \\
        --seconds 51 --trace 1 [--out spans.jsonl]

Runs ``benchmark/harness.run`` as ``benchmark/run.py`` does, on the TPU
only, and prints one JSON line: the end-to-end metrics (which a run of
``benchmark/run.py --trace 1`` leaves out), the per-layer metrics when
traced, the breakdown, and ``span_self_ms``: each program span's self
time in ms a query (``benchmark/spans.py: self_ms``), under
``bench.query`` the time no program span covers. ``--out`` appends the
line to a file as well.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def measure(cell, seed, seconds, trace, devices, peaks, t_start) -> dict:
    """One ``harness.run``, keeping the trace summary the harness loads
    (and would otherwise drop) for the span table."""
    from benchmark import harness, spans, xtrace

    summaries = []
    load = xtrace.load

    def keep(trace_dir):
        summaries.append(load(trace_dir))
        return summaries[-1]

    xtrace.load = keep
    try:
        out = harness.run(cell, seed, seconds, trace, devices, peaks, t_start)
    finally:
        xtrace.load = load
    summary = summaries[0] if summaries else None
    # A traced run's result holds no end-to-end metric, and its set-up
    # is on the harness's earlier ``setup`` line: NaN here.
    setup = out.result["metrics"].get("setup_s", {"value": float("nan")})
    view = harness.RunView(
        cell, out.records, out.window_s, setup["value"], peaks, summary
    )
    return {
        "workload": cell.name,
        "seed": seed,
        "trace": int(trace),
        "correct": out.result["correct"],
        "end_to_end": harness.read_metrics(view, cell.end_to_end),
        "per_layer": out.result["metrics"] if trace else {},
        "device": out.result["device"],
        "breakdown": out.result.get("breakdown"),
        "span_self_ms": spans.self_ms(summary),
        "read_batches": [r.profile.get("read_batches") for r in out.records],
        "read_visits": [r.profile.get("read_visits") for r in out.records],
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=1)
    p.add_argument("--out")
    args = p.parse_args(argv)
    from benchmark import harness

    cell = harness.load_cell(args.workload)
    devices, peaks = harness.require_chip(cell.chips)
    harness.enable_compile_cache()
    line = measure(
        cell, args.seed, args.seconds, bool(args.trace), devices, peaks, T_START
    )
    text = json.dumps(line, default=str)
    print(text, flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
