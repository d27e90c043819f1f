"""The benchmark's command: one run of one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload http_node.history --seed 7 \\
        --seconds 30 --trace 0

(``python3 -m benchmark.run`` is the same.) It runs on a TPU or not at
all: with no TPU, fewer chips than the cell asks for, or a device kind
missing from ``benchmark/peaks.json`` it exits non-zero and prints no
result. Earlier stdout lines carry the set-up parts and the window's
counts; the last is the result object. The numbers ``correct`` was
decided on are the last lines of stderr.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if os.path.abspath(sys.path[0]) == HERE:
    # Run as a script, sys.path[0] is benchmark/; the package lives above.
    sys.path[0] = ROOT


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def prebuild_native() -> None:
    """Build the program's native library for this machine before JAX
    starts: the build is a child process, and no child may start once
    JAX holds the chip. Without a toolchain the program's numpy
    fallbacks serve."""
    import importlib.util

    path = os.path.join(ROOT, "pixie_tpu", "native", "host_runtime.py")
    spec = importlib.util.spec_from_file_location("_native_prebuild", path)
    if spec is None:
        return
    mod = importlib.util.module_from_spec(spec)
    try:
        spec.loader.exec_module(mod)
    except Exception as e:  # no toolchain: the program's fallbacks serve
        print(f"native prebuild: {type(e).__name__}: {e}", file=sys.stderr)


def main(argv=None) -> int:
    args = parse_args(argv)
    from benchmark import harness

    cell = harness.load_cell(args.workload)
    prebuild_native()
    devices, peaks = harness.require_chip(cell.chips)
    import pixie_tpu  # noqa: F401  (fails here, before any output, without the program)

    harness.enable_compile_cache()
    result = harness.run(
        cell, args.seed, args.seconds, bool(args.trace), devices, peaks, T_START
    ).result
    print(json.dumps(result), flush=True)
    harness.report_checks(result["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
