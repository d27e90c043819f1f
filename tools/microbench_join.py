"""Join-lane microbenchmark (r19): host hash join vs device sort-merge.

Two engines over the same INNER-join workload (dense int32 keys, one
float64 payload column gathered from each side):

  host hash     — the vectorized numpy core of exec/join_node.py:
                  bincount + stable argsort build a CSR over build rows,
                  probe resolves fanout + repeat-gather emits pairs
                  (what the host engine pays after GroupEncoder).
  device merge  — the r19 lane in ops/segment.py: stable packed-key
                  sort of the build side, searchsorted merge
                  (merge_join_pairs), bounded-fanout gather into the
                  pair cap — one jitted program, timed end-to-end with
                  a host fetch of the leading output rows.

Sweeps probe rows × key cardinality (which sets the expected per-row
fanout: build rows / keys) and reports Mrows/s of probe input and
Mpairs/s of output for both engines, plus the crossover ratio the
device_join_min_rows gate encodes. CPU numbers are directional only —
the gate default stays provisional until the TPU campaign re-runs this
(same caveat as the r8 sort lane).

With ``MB_WRITE_BENCH_DETAIL=1`` the summary lands in BENCH_DETAIL.json
under the ``join`` key, like ``codec``.

Run: JAX_PLATFORMS=cpu python tools/microbench_join.py
Env: MB_JOIN_ROWS  comma list of probe-row counts (default 1<<18,1<<20;
                   on TPU also 1<<22,1<<24)
     MB_JOIN_KEYS  comma list of key cardinalities (default 2^8,2^12,2^16)
     MB_JOIN_BUILD build rows (default probe//4)
     MB_JOIN_MAX_PAIRS  skip sweeps whose output exceeds this (default
                   2^24 — the device_join_max_out default; skips are
                   logged, never silent)
     MB_RUNS       timed repetitions, best-of (default 3)
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


def _ints(env, default):
    raw = os.environ.get(env)
    if not raw:
        return default
    return [int(x) for x in raw.split(",") if x.strip()]


def host_inner_join(bk, bv, pk, pv, nkeys):
    """The vectorized host core: CSR build + fanout probe + repeat-gather."""
    counts = np.bincount(bk, minlength=nkeys)
    order = np.argsort(bk, kind="stable")
    starts = np.concatenate([[0], np.cumsum(counts)])
    fanout = counts[pk]
    total = int(fanout.sum())
    right_idx = np.repeat(np.arange(len(pk)), fanout)
    run_base = np.repeat(np.cumsum(fanout) - fanout, fanout)
    ramp = np.arange(total) - run_base
    left_idx = order[starts[pk][right_idx] + ramp]
    return bv[left_idx], pv[right_idx]


def main() -> int:
    import jax


    import pixie_tpu  # noqa: F401  (enables x64)
    import jax.numpy as jnp

    from pixie_tpu.ops import segment

    dev = jax.devices()[0]
    on_cpu = dev.platform == "cpu"
    rows_list = _ints(
        "MB_JOIN_ROWS",
        [1 << 18, 1 << 20] if on_cpu else [1 << 18, 1 << 20, 1 << 22, 1 << 24],
    )
    keys_list = _ints("MB_JOIN_KEYS", [1 << 8, 1 << 12, 1 << 16])
    max_pairs = int(os.environ.get("MB_JOIN_MAX_PAIRS", 1 << 24))
    runs = int(os.environ.get("MB_RUNS", 3))
    log(f"device: {dev}  runs={runs}")

    def device_join(nb):
        @jax.jit
        def fn(bk, bv, pk, pv, cap_m):
            sk, si = jax.lax.sort(
                (bk, jnp.arange(nb, dtype=jnp.int32)),
                num_keys=1,
                is_stable=True,
            )
            bi, pi, valid, _ = segment.merge_join_pairs(
                sk, si, pk, cap_m.shape[0]
            )
            lv = jnp.where(valid, bv[jnp.clip(bi, 0, nb - 1)], 0.0)
            rv = jnp.where(valid, pv[jnp.clip(pi, 0, pk.shape[0] - 1)], 0.0)
            return lv, rv

        return fn

    results = []
    header = (
        f"{'probe':>9} {'build':>9} {'keys':>7} {'pairs':>10} | "
        f"{'host':>8} {'device':>8}  Mpairs/s   speedup"
    )
    log(header)
    log("-" * len(header))
    rng = np.random.default_rng(19)
    for n_probe in rows_list:
        n_build = int(os.environ.get("MB_JOIN_BUILD", n_probe // 4))
        for nkeys in keys_list:
            bk = rng.integers(0, nkeys, n_build).astype(np.int32)
            bv = rng.standard_normal(n_build)
            pk = rng.integers(0, nkeys, n_probe).astype(np.int32)
            pv = rng.standard_normal(n_probe)
            pairs = int(
                (
                    np.bincount(bk, minlength=nkeys).astype(np.int64)
                    * np.bincount(pk, minlength=nkeys)
                ).sum()
            )
            if pairs > max_pairs:
                log(
                    f"{n_probe:>9} {n_build:>9} {nkeys:>7} {pairs:>10} | "
                    f"skipped (> MB_JOIN_MAX_PAIRS={max_pairs})"
                )
                continue
            # Same pow2 pair cap the pipeline plans from host counts.
            cap_m = 1 << max(pairs - 1, 1).bit_length()

            t_host = float("inf")
            for _ in range(runs):
                t0 = time.perf_counter()
                host_inner_join(bk, bv, pk, pv, nkeys)
                t_host = min(t_host, time.perf_counter() - t0)

            fn = device_join(n_build)
            jbk, jbv = jnp.asarray(bk), jnp.asarray(bv)
            jpk, jpv = jnp.asarray(pk), jnp.asarray(pv)
            jcap = jnp.zeros(cap_m, jnp.int8)
            jax.block_until_ready((jbk, jbv, jpk, jpv, jcap))
            with segment.platform_hint(dev.platform):
                out = fn(jbk, jbv, jpk, jpv, jcap)  # compile + warm
                np.asarray(out[0][:8])
                t_dev = float("inf")
                for _ in range(runs):
                    t0 = time.perf_counter()
                    out = fn(jbk, jbv, jpk, jpv, jcap)
                    np.asarray(out[0][:8])
                    t_dev = min(t_dev, time.perf_counter() - t0)

            r = {
                "probe_rows": n_probe,
                "build_rows": n_build,
                "keys": nkeys,
                "pairs": pairs,
                "host_mpairs_s": round(pairs / t_host / 1e6, 1),
                "device_mpairs_s": round(pairs / t_dev / 1e6, 1),
                "device_rows_s": round(n_probe / t_dev, 0),
                "speedup_x": round(t_host / t_dev, 2),
            }
            results.append(r)
            log(
                f"{n_probe:>9} {n_build:>9} {nkeys:>7} {pairs:>10} | "
                f"{r['host_mpairs_s']:>8.1f} {r['device_mpairs_s']:>8.1f}"
                f"             {r['speedup_x']:>6.2f}x"
            )

    summary = {
        "platform": dev.platform,
        "runs": runs,
        "sweeps": results,
        "best_speedup_x": max(r["speedup_x"] for r in results),
        # The admission gate the sweep informs: below this combined row
        # count the host core wins outright (dispatch + sort overhead).
        "device_join_min_rows_default": 1 << 18,
        "note": (
            "CPU numbers are directional; the gate default is provisional "
            "pending the TPU campaign (same posture as the r8 sort lane)."
        ),
    }
    print(json.dumps(summary, indent=1))

    if os.environ.get("MB_WRITE_BENCH_DETAIL") == "1":
        path = os.path.join(REPO, "BENCH_DETAIL.json")
        with open(path) as f:
            detail = json.load(f)
        detail["join"] = summary
        with open(path, "w") as f:
            json.dump(detail, f, indent=1)
            f.write("\n")
        log("BENCH_DETAIL.json updated (join)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
