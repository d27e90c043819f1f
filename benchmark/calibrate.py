"""Readings the benchmark's settings were made from, on the chip. Not run
by the benchmark itself.

    # the compared numbers of the program and of the control, per seed
    python3 benchmark/calibrate.py seeds --workload http_node.history \\
        --seeds 11,12,13 --seconds 5
    # an open-loop mix at several rates, each for --seconds, one set-up
    python3 benchmark/calibrate.py sweep --workload http_node.live \\
        --rates 2,3,4,5 --seconds 20 --seed 5
    # a small trace of a known program, for the trace reduction's test
    python3 benchmark/calibrate.py trace-sample --out chiprun_out/x

Each prints one JSON line per reading. The control is the plain
reference one precision step down, put in the program's place (see the
datasets' ``reference(..., precision="low")``).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.abspath(sys.path[0]) == HERE:
    sys.path[0] = os.path.dirname(HERE)


def _chip(workload):
    from benchmark import harness
    from benchmark.run import prebuild_native

    cell = harness.load_cell(workload)
    prebuild_native()
    devices, peaks = harness.require_chip(cell.chips)
    import pixie_tpu  # noqa: F401

    harness.enable_compile_cache()
    return harness, cell, devices, peaks


def seeds(args) -> None:
    harness, cell, devices, peaks = _chip(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run(
            cell, seed, args.seconds, False, devices, peaks, time.perf_counter()
        )
        control, _ = harness.check(cell, out.timeline, out.records, seed, "low")
        print(
            json.dumps(
                {
                    "seed": seed,
                    "correct": out.result["correct"],
                    "program": {
                        k: c["value"] for k, c in out.result["checks"].items()
                    },
                    "control": control,
                    "checked_answers": out.result["checked_answers"],
                    "metrics": out.result["metrics"],
                    "failed": out.result["failed"],
                },
                default=str,
            ),
            flush=True,
        )
        del out
        gc.collect()


def sweep(args) -> None:
    import numpy as np

    harness, cell, devices, peaks = _chip(args.workload)
    prep = harness.prepare(cell, args.seed, args.seconds * 8, devices)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            prep.traffic.mix = dict(cell.traffic, rate_per_s=rate)
            recs, _, unserved = prep.traffic._open(args.seconds)
            lat = np.array([r.latency_s for r in recs]) * 1000
            half = len(lat) // 2
            print(
                json.dumps(
                    {
                        "rate_per_s": rate,
                        "refreshes": len(recs),
                        "unserved": unserved,
                        "p50_ms": float(np.percentile(lat, 50)),
                        "p95_ms": float(np.percentile(lat, 95)),
                        "first_half_p50_ms": float(np.median(lat[:half])),
                        "second_half_p50_ms": float(np.median(lat[half:])),
                        "last_ms": float(lat[-1]),
                        "service_ms_p50": float(
                            np.median([1000 * (r.end - r.start) for r in recs])
                        ),
                    }
                ),
                flush=True,
            )
            time.sleep(2.0)  # let a backlog drain before the next rate
    finally:
        prep.close()


def trace_sample(args) -> None:
    """A few small device programs under bench.window / bench.query spans."""
    import jax
    import jax.numpy as jnp

    from benchmark import harness

    harness.require_chip(1)
    f = jax.jit(lambda x: (x * 2.0 + 1.0).sum())
    x = jnp.ones((2048, 2048), jnp.float32)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(args.out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.query"):
                f(x).block_until_ready()
            time.sleep(0.01)
    jax.profiler.stop_trace()
    print(json.dumps({"trace_dir": args.out}))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = p.add_subparsers(dest="mode", required=True)
    s = sub.add_parser("seeds")
    s.add_argument("--workload", required=True)
    s.add_argument("--seeds", required=True)
    s.add_argument("--seconds", type=float, default=5.0)
    w = sub.add_parser("sweep")
    w.add_argument("--workload", required=True)
    w.add_argument("--rates", required=True)
    w.add_argument("--seconds", type=float, default=20.0)
    w.add_argument("--seed", type=int, default=1)
    t = sub.add_parser("trace-sample")
    t.add_argument("--out", required=True)
    args = p.parse_args(argv)
    {"seeds": seeds, "sweep": sweep, "trace-sample": trace_sample}[args.mode](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
