"""Mesh-width microbenchmark (r21): fold scaling vs simulated hosts.

One fixed groupby workload (count / sum / min / max / HLL / count-min —
the mergeable UDA lanes) folded through the full engine path at each
mesh width over the SAME device pool: ``hosts:1,d:8`` is the flat
1-host baseline, ``hosts:2,d:4`` / ``hosts:4,d:2`` / ``hosts:8,d:1``
re-partition the identical 8 devices under a leading ``hosts`` axis.
The fold is bit-identical by construction (collectives reduce over the
full axis tuple), so any per-device rate delta IS the cross-host
combine-tree overhead — psum/pmax over the extra axis plus the
gather-merge tree for sketch states — which this sweep reports per
width against the width-1 baseline.

Headline: ``mesh_scaling_x`` — per-device fold rate at width 4 relative
to 1-host (always present; falls back to the widest measured width when
4 is not available). The r21 acceptance bar is >= 0.7.

With ``MB_WRITE_BENCH_DETAIL=1`` the summary lands in BENCH_DETAIL.json
under the ``mesh`` key, like ``join`` and ``codec``.

``MB_MESH_CHAOS=1`` runs the r23 recovery bench instead (bench.py
config 12): a windowed streaming fold at ``hosts:2,d:N/2`` with one
simulated host killed mid-stream — recovery wall seconds and the
refolded-window fraction land under ``mesh_chaos``.

Run: JAX_PLATFORMS=cpu python tools/microbench_mesh.py
Env: MB_MESH_ROWS     rows folded per width (default 200_000)
     MB_MESH_WIDTHS   comma list of host counts (default 1,2,4,8)
     MB_RUNS          timed repetitions, best-of (default 3)
     MB_MESH_CHAOS    1 = run the r23 recovery bench instead
     MB_MESH_WINDOWS  stream windows for the recovery bench (default 8)
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


AGG_QUERY = (
    "df = px.DataFrame(table='mesh_bench')\n"
    "g = df.groupby('service').agg("
    "n=('lat', px.count), s=('lat', px.sum),"
    " mn=('lat', px.min), mx=('lat', px.max),"
    " u=('service', px.approx_count_distinct),"
    " cm=('status', px.count_min))\n"
    "px.display(g, 'out')\n"
)


def run_mesh_bench(rows: int = 200_000, runs: int = 3, widths=None) -> dict:
    """Sweep mesh widths over the local device pool; returns the summary
    dict (the ``mesh`` block). Callable from bench.py config 10."""
    import jax

    from pixie_tpu.distributed.mesh import MeshConfig
    from pixie_tpu.engine import Carnot
    from pixie_tpu.parallel import MeshExecutor
    from pixie_tpu.types import DataType, Relation

    ndev = len(jax.devices())
    widths = [
        w
        for w in (widths or [1, 2, 4, 8])
        if w <= ndev and ndev % w == 0
    ]
    if 1 not in widths:
        widths.insert(0, 1)
    platform = jax.devices()[0].platform
    log(f"devices: {ndev} ({platform})  rows={rows}  runs={runs}")

    rng = np.random.default_rng(21)
    data = {
        "service": np.array(
            [f"svc{i}" for i in rng.integers(0, 64, rows)]
        ),
        "status": rng.integers(0, 7, rows),
        "lat": rng.standard_normal(rows),
    }

    header = (
        f"{'geometry':>14} {'fold_ms':>9} {'Mrows/s':>9} "
        f"{'/device':>9} {'overhead':>9}"
    )
    log(header)
    log("-" * len(header))

    entries = []
    baseline_out = None
    for w in widths:
        cfg = MeshConfig.parse(f"hosts:{w},d:{ndev // w}", ndev)
        ex = MeshExecutor(block_rows=1 << 15, mesh_config=cfg)
        carnot = Carnot(device_executor=ex)
        rel = Relation.of(
            ("service", DataType.STRING),
            ("status", DataType.INT64),
            ("lat", DataType.FLOAT64),
        )
        carnot.table_store.create_table("mesh_bench", rel).write_pydict(
            data
        )
        out = carnot.execute_query(AGG_QUERY).table("out")  # warm
        assert not ex.fallback_errors, ex.fallback_errors
        if baseline_out is None:
            baseline_out = out
        else:
            # The sweep doubles as a correctness gate: every width must
            # reproduce the 1-host fold bit-exactly, sketches included.
            for k in baseline_out:
                assert np.array_equal(
                    np.asarray(baseline_out[k]), np.asarray(out[k])
                ), f"width {w} diverged on {k}"
        t = float("inf")
        for _ in range(runs):
            t0 = time.perf_counter()
            carnot.execute_query(AGG_QUERY)
            t = min(t, time.perf_counter() - t0)
        entries.append(
            {
                "hosts": w,
                "geometry": cfg.signature(),
                "fold_s": round(t, 6),
                "mrows_s": round(rows / t / 1e6, 3),
                "per_device_mrows_s": round(rows / t / 1e6 / ndev, 4),
            }
        )

    base = entries[0]
    for e in entries:
        # Same devices, same rows, bit-identical output: the rate gap
        # vs width 1 is the cross-host combine-tree cost.
        e["combine_overhead_pct"] = round(
            (base["mrows_s"] - e["mrows_s"]) / base["mrows_s"] * 100.0, 1
        )
        log(
            f"{e['geometry']:>14} {e['fold_s'] * 1e3:>9.1f} "
            f"{e['mrows_s']:>9.3f} {e['per_device_mrows_s']:>9.4f} "
            f"{e['combine_overhead_pct']:>8.1f}%"
        )

    at4 = next(
        (e for e in entries if e["hosts"] == 4), entries[-1]
    )
    summary = {
        "platform": platform,
        "runs": runs,
        "rows": rows,
        "total_devices": ndev,
        "widths": entries,
        # Always present: per-device fold rate at width 4 (or the widest
        # measured width) relative to the 1-host baseline. r21 bar: 0.7.
        "mesh_scaling_x": round(
            at4["per_device_mrows_s"] / base["per_device_mrows_s"], 3
        ),
        "scaling_width": at4["hosts"],
        "note": (
            "Simulated hosts re-partition one local device pool; the "
            "overhead column prices the combine tree only. Real "
            "multi-host numbers await a TPU pod campaign."
        ),
    }
    return summary


def run_mesh_chaos_bench(
    rows: int = 120_000, windows: int = 8, runs: int = 3
) -> dict:
    """r23 recovery microbench: one simulated host killed mid-stream.

    A windowed streaming fold runs at ``hosts:2,d:N/2`` with
    ``mesh.host_loss`` armed to fire after ``windows // 2`` window
    dispatches. The executor's degradation ladder re-plans the fold on
    the surviving geometry and resumes from the last window-boundary
    checkpoint; the summary prices that recovery — wall seconds over
    the unfaulted fold and the fraction of windows refolded — and
    asserts the recovered output is bit-identical to an unfaulted flat
    fold. Callable from bench.py config 12."""
    import jax

    from pixie_tpu.distributed.mesh import MeshConfig
    from pixie_tpu.engine import Carnot
    from pixie_tpu.parallel import MeshExecutor
    from pixie_tpu.types import DataType, Relation
    from pixie_tpu.utils import faults, flags

    ndev = len(jax.devices())
    platform = jax.devices()[0].platform
    win_rows = max(1, rows // windows)
    log(
        f"devices: {ndev} ({platform})  rows={rows}  "
        f"windows={windows} ({win_rows} rows each)"
    )

    rng = np.random.default_rng(23)
    data = {
        "service": np.array(
            [f"svc{i}" for i in rng.integers(0, 64, rows)]
        ),
        "status": rng.integers(0, 7, rows),
        "lat": rng.standard_normal(rows),
    }
    rel = Relation.of(
        ("service", DataType.STRING),
        ("status", DataType.INT64),
        ("lat", DataType.FLOAT64),
    )

    def cold_fold(cfg):
        # Fresh executor + store per fold: a warm executor with no new
        # rows serves the repeat from its stream cache (one merge
        # dispatch, no windows), so only cold folds exercise the full
        # windowed stream. Both sides of the recovery delta pay the
        # same cold compile, leaving ladder re-plan + degraded-rung
        # compile + post-checkpoint refold as the difference.
        ex = MeshExecutor(block_rows=1 << 15, mesh_config=cfg)
        carnot = Carnot(device_executor=ex)
        carnot.table_store.create_table("mesh_bench", rel).write_pydict(
            data
        )
        t0 = time.perf_counter()
        out = carnot.execute_query(AGG_QUERY).table("out")
        wall = time.perf_counter() - t0
        return ex, carnot, out, wall

    fault_after = max(1, windows // 2)
    flags.set("streaming_window_rows", win_rows)
    try:
        # Unfaulted flat fold: the bit-identity truth.
        _, _, truth, _ = cold_fold(MeshConfig.flat(ndev))

        cfg = MeshConfig.parse(f"hosts:2,d:{ndev // 2}", ndev)
        unfaulted = float("inf")
        for _ in range(runs):
            unfaulted = min(unfaulted, cold_fold(cfg)[3])

        # Kill one simulated host after fault_after window dispatches:
        # the fold must resume from the last checkpoint on the degraded
        # rung. The faulted wall includes the degraded rung's compile —
        # that IS part of what recovery costs.
        faults.arm("mesh.host_loss", count=1, after=fault_after)
        try:
            ex, carnot, out, faulted = cold_fold(cfg)
        finally:
            faults.reset()
        assert not ex.fallback_errors, ex.fallback_errors
        for k in truth:
            assert np.array_equal(
                np.asarray(truth[k]), np.asarray(out[k])
            ), f"recovered fold diverged on {k}"
        snap = ex.mesh_recovery_snapshot()
        rs = ex.last_resume_stats
        assert rs is not None, snap
        # New rows + one more fold: the executor must restore its full
        # configured geometry once the loss clears.
        carnot.table_store.get_table("mesh_bench").write_pydict(data)
        carnot.execute_query(AGG_QUERY)
        restored = not ex.mesh_recovery_snapshot()["degraded"]
    finally:
        flags.reset("streaming_window_rows")

    frac = round(rs["refolded_windows"] / rs["total_windows"], 4)
    summary = {
        "platform": platform,
        "rows": rows,
        "windows": rs["total_windows"],
        "geometry": cfg.signature(),
        "fault_after_window": fault_after,
        "unfaulted_fold_s": round(unfaulted, 6),
        "faulted_fold_s": round(faulted, 6),
        # Wall-clock price of the host loss: ladder re-plan + degraded
        # rung compile + refolding the post-checkpoint windows.
        "recovery_seconds": round(max(0.0, faulted - unfaulted), 6),
        "resumed_from_window": rs["resumed_from_window"],
        "refolded_windows": rs["refolded_windows"],
        "refolded_window_fraction": frac,
        # Deterministic headline (higher is better): the fraction of
        # the stream the window checkpoints did NOT have to refold.
        "checkpoint_saved_fraction": round(1.0 - frac, 4),
        "degrade_events": snap["degrade_events"],
        "bit_identical": True,
        "restored_after_next_fold": restored,
        "note": (
            "Simulated host loss on one local device pool; recovery "
            "seconds include the degraded rung's one-time compile. "
            "Real multi-host numbers await a TPU pod campaign."
        ),
    }
    log(
        f"recovery: {summary['recovery_seconds']:.3f}s over unfaulted "
        f"{summary['unfaulted_fold_s']:.3f}s; refolded "
        f"{rs['refolded_windows']}/{rs['total_windows']} windows"
    )
    return summary


def record_mesh_chaos_detail(summary: dict, path: str = None) -> None:
    """Merge one mesh recovery bench into BENCH_DETAIL.json's
    ``mesh_chaos`` block (read-modify-write: other blocks survive)."""
    bd_path = path or os.path.join(REPO, "BENCH_DETAIL.json")
    with open(bd_path) as f:
        detail = json.load(f)
    detail["mesh_chaos"] = summary
    with open(bd_path, "w") as f:
        json.dump(detail, f, indent=1)
        f.write("\n")
    log("BENCH_DETAIL.json updated (mesh_chaos)")


def record_mesh_detail(summary: dict, path: str = None) -> None:
    """Merge one mesh sweep into BENCH_DETAIL.json's ``mesh`` block
    (read-modify-write: the other recorded blocks survive)."""
    bd_path = path or os.path.join(REPO, "BENCH_DETAIL.json")
    with open(bd_path) as f:
        detail = json.load(f)
    detail["mesh"] = summary
    with open(bd_path, "w") as f:
        json.dump(detail, f, indent=1)
        f.write("\n")
    log("BENCH_DETAIL.json updated (mesh)")


def main() -> int:
    # The hosts axis needs a pool to split: on the CPU backend
    # (JAX_PLATFORMS=cpu) force 8 virtual devices BEFORE it initializes.
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        xf = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in xf:
            os.environ["XLA_FLAGS"] = (
                xf + " --xla_force_host_platform_device_count=8"
            )
    import pixie_tpu  # noqa: F401  (enables x64)

    rows = int(os.environ.get("MB_MESH_ROWS", 200_000))
    runs = int(os.environ.get("MB_RUNS", 3))
    widths_env = os.environ.get("MB_MESH_WIDTHS")
    widths = (
        [int(x) for x in widths_env.split(",") if x.strip()]
        if widths_env
        else None
    )
    if os.environ.get("MB_MESH_CHAOS") == "1":
        # r23: the recovery bench instead of the width sweep.
        summary = run_mesh_chaos_bench(
            rows=rows,
            windows=int(os.environ.get("MB_MESH_WINDOWS", 8)),
            runs=runs,
        )
        print(json.dumps(summary, indent=1))
        if os.environ.get("MB_WRITE_BENCH_DETAIL") == "1":
            record_mesh_chaos_detail(summary)
        return 0
    summary = run_mesh_bench(rows=rows, runs=runs, widths=widths)
    print(json.dumps(summary, indent=1))
    if os.environ.get("MB_WRITE_BENCH_DETAIL") == "1":
        record_mesh_detail(summary)
    return 0


if __name__ == "__main__":
    sys.exit(main())
