"""Cost-model prediction-accuracy microbenchmark (r22).

Drives real fold dispatches through the full engine path with the
CostModel observing, and reports the model's RELATIVE prediction error
(|predicted - measured| / measured, recorded predict-before-ingest by
``observe``) in two regimes:

  cold    the first few dispatches after a reset — predictions come
          from the backoff rungs (family throughput, roofline prior)
          or are honestly absent (``None`` = no opinion, no error
          recorded; the ``cold.predictions`` count says how often the
          cold model voiced one at all).
  warmed  after ``MB_CM_WARM_RUNS`` queries the error reservoirs are
          cleared (samples/rates kept) and the same workload repeats —
          every error in the ``warmed`` block is a prediction made by
          the converged model.

Headline: ``warmed_p50_rel_err`` pooled across families. The r22
acceptance bar is <= 0.30 (bench.py config 11 gates on it).

With ``MB_WRITE_BENCH_DETAIL=1`` the summary lands in BENCH_DETAIL.json
under the ``cost_model`` key, like ``mesh`` / ``join`` / ``codec``.

Run: JAX_PLATFORMS=cpu python tools/microbench_cost_model.py
Env: MB_CM_ROWS       rows in the bench table (default 120_000)
     MB_CM_COLD_RUNS  queries in the cold phase (default 3)
     MB_CM_WARM_RUNS  queries in the warmed phase (default 8)
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


AGG_QUERY = (
    "df = px.DataFrame(table='cm_bench')\n"
    "g = df.groupby('service').agg("
    "n=('lat', px.count), s=('lat', px.sum),"
    " mn=('lat', px.min), mx=('lat', px.max))\n"
    "px.display(g, 'out')\n"
)


def _pooled(errors: dict, q: float):
    pool = sorted(
        e for vals in errors.values() for e in vals
    )
    if not pool:
        return None
    return float(pool[min(int(q * len(pool)), len(pool) - 1)])


def run_cost_model_bench(
    rows: int = 120_000, cold_runs: int = 3, warm_runs: int = 8
) -> dict:
    """Cold-vs-warmed prediction-error sweep; returns the summary dict
    (the ``cost_model`` block). Callable from bench.py config 11."""
    import jax

    from pixie_tpu.engine import Carnot
    from pixie_tpu.parallel import MeshExecutor, profiler
    from pixie_tpu.serving import cost_model
    from pixie_tpu.types import DataType, Relation

    platform = jax.devices()[0].platform
    log(f"platform: {platform}  rows={rows}  "
        f"cold={cold_runs} warm={warm_runs}")

    cost_model.reset()
    cost_model.set_enabled(True)
    profiler.set_enabled(True)  # roofline prior needs cost_analysis rows

    rng = np.random.default_rng(22)
    data = {
        "service": np.array(
            [f"svc{i}" for i in rng.integers(0, 64, rows)]
        ),
        "status": rng.integers(0, 7, rows),
        "lat": rng.standard_normal(rows),
    }
    ex = MeshExecutor(block_rows=1 << 14)
    carnot = Carnot(device_executor=ex)
    rel = Relation.of(
        ("service", DataType.STRING),
        ("status", DataType.INT64),
        ("lat", DataType.FLOAT64),
    )
    carnot.table_store.create_table("cm_bench", rel).write_pydict(data)

    m = cost_model.model()
    for _ in range(cold_runs):
        carnot.execute_query(AGG_QUERY)
    assert not ex.fallback_errors, ex.fallback_errors
    cold_state = m.state()
    cold = {
        "families": m.error_snapshot(),
        "predictions": sum(
            len(v) for v in cold_state["errors"].values()
        ),
        "pooled_p50": _pooled(cold_state["errors"], 0.5),
    }

    # Keep the learned samples/rates, drop the cold-phase errors: every
    # error recorded from here on is a warmed-model prediction.
    warm_seed = m.state()
    warm_seed["errors"] = {}
    m.load_state(warm_seed)
    for _ in range(warm_runs):
        carnot.execute_query(AGG_QUERY)
    assert not ex.fallback_errors, ex.fallback_errors
    warm_state = m.state()
    warmed = {
        "families": m.error_snapshot(),
        "predictions": sum(
            len(v) for v in warm_state["errors"].values()
        ),
        "pooled_p50": _pooled(warm_state["errors"], 0.5),
        "pooled_p90": _pooled(warm_state["errors"], 0.9),
    }

    header = f"{'regime':>8} {'preds':>6} {'p50_err':>9} {'p90_err':>9}"
    log(header)
    log("-" * len(header))
    for name, blk in (("cold", cold), ("warmed", warmed)):
        p50 = blk.get("pooled_p50")
        p90 = blk.get("pooled_p90")
        log(
            f"{name:>8} {blk['predictions']:>6} "
            f"{('%.3f' % p50) if p50 is not None else '-':>9} "
            f"{('%.3f' % p90) if p90 is not None else '-':>9}"
        )

    p50 = warmed["pooled_p50"]
    p90 = warmed["pooled_p90"]
    summary = {
        "platform": platform,
        "rows": rows,
        "cold_runs": cold_runs,
        "warm_runs": warm_runs,
        "cold": cold,
        "warmed": warmed,
        "sample_counts": m.sample_counts(),
        # Always present: pooled warmed-phase p50/p90 relative error.
        # r22 bar: p50 <= 0.30.
        "warmed_p50_rel_err": round(p50, 4) if p50 is not None else None,
        "warmed_p90_rel_err": round(p90, 4) if p90 is not None else None,
        "pass_p50_under_030": bool(p50 is not None and p50 <= 0.30),
        "note": (
            "Relative error of predict-before-ingest estimates vs "
            "measured dispatch wall time; CPU numbers bound the "
            "mechanism, TPU rates await a hardware campaign."
        ),
    }
    cost_model.reset()  # leave no learned state behind for the caller
    return summary


def record_cost_model_detail(summary: dict, path: str = None) -> None:
    """Merge one sweep into BENCH_DETAIL.json's ``cost_model`` block
    (read-modify-write: the other recorded blocks survive)."""
    bd_path = path or os.path.join(REPO, "BENCH_DETAIL.json")
    with open(bd_path) as f:
        detail = json.load(f)
    detail["cost_model"] = summary
    with open(bd_path, "w") as f:
        json.dump(detail, f, indent=1)
        f.write("\n")
    log("BENCH_DETAIL.json updated (cost_model)")


def main() -> int:
    import pixie_tpu  # noqa: F401  (enables x64)

    rows = int(os.environ.get("MB_CM_ROWS", 120_000))
    cold_runs = int(os.environ.get("MB_CM_COLD_RUNS", 3))
    warm_runs = int(os.environ.get("MB_CM_WARM_RUNS", 8))
    summary = run_cost_model_bench(
        rows=rows, cold_runs=cold_runs, warm_runs=warm_runs
    )
    print(json.dumps(summary, indent=1))
    if os.environ.get("MB_WRITE_BENCH_DETAIL") == "1":
        record_cost_model_detail(summary)
    return 0


if __name__ == "__main__":
    sys.exit(main())
