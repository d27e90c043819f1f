"""Sort–compact lane microbenchmark (r8): ns/row for the three
segment-reduction designs across row counts and segment counts.

  direct scatter     — jax.ops.segment_max (the ~7 ns/row scalar-unit
                       floor on a v5e; cost scales with OPERAND length)
  sort+full-scatter  — segment.sorted_segment_max_small (the losing
                       r4/r5 design: packed-key sort, deduped indices,
                       but the scatter still walks all n elements)
  sort–compact       — segment.sorted_segment_reduce_compact (the r8
                       lane: second sort compacts the <= nseg winners
                       to the front; the final scatter operand has
                       STATIC length nseg)

Also reports the generic two-operand variant (arbitrary-dtype min/max,
segment.sorted_segment_minmax_compact) at one representative shape, and
prints the table that feeds the measured-cost comment block in
ops/segment.py.

Every body carries REAL state through a lax.scan (like the pipeline), so
XLA cannot fold the work away; timings end in jax.block_until_ready.

Usage: python tools/microbench_sort_reduce.py
Env:   MB_ROWS  comma list of total row counts     (default 1M,4M,16M,64M
                on TPU; 1M,4M on CPU — CPU sorts are slow)
       MB_SEGS  comma list of segment counts        (default 2^10,2^13,2^16)
       MB_BLOCK rows per scan block                 (default 2^21, bench's)
       MB_RUNS  timed repetitions (best-of)         (default 3)
"""

import os
import sys
import time


sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pixie_tpu  # noqa: F401,E402  (enables x64)
import jax
import jax.numpy as jnp

from pixie_tpu.ops import segment

VALUE_BITS = 5  # the HLL rho domain


def log(msg):
    print(msg, flush=True)


def _ints(env, default):
    raw = os.environ.get(env)
    if not raw:
        return default
    return [int(x) for x in raw.split(",") if x.strip()]


def bench(fn, args, rows, runs):
    jax.block_until_ready(fn(*args))  # compile
    best = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e9 / rows  # ns/row


def scan_body(update):
    """jit(fn(blocks_flat, blocks_vals)) carrying an int32[nseg] state."""

    def fn(nseg, flat_blocks, val_blocks):
        def step(carry, xs):
            f, v = xs
            return jnp.maximum(carry, update(f, v, nseg)), None

        out, _ = jax.lax.scan(
            step, jnp.zeros(nseg, jnp.int32), (flat_blocks, val_blocks)
        )
        return out

    return jax.jit(fn, static_argnums=0)


def main():
    dev = jax.devices()[0]
    on_cpu = dev.platform == "cpu"
    rows_list = _ints(
        "MB_ROWS",
        [1 << 20, 1 << 22] if on_cpu else [1 << 20, 1 << 22, 1 << 24, 1 << 26],
    )
    segs_list = _ints("MB_SEGS", [1 << 10, 1 << 13, 1 << 16])
    block = int(os.environ.get("MB_BLOCK", 1 << 21))
    runs = int(os.environ.get("MB_RUNS", 3))
    log(f"device: {dev}  block={block}  runs={runs}")

    direct = scan_body(
        lambda f, v, nseg: jax.ops.segment_max(v, f, num_segments=nseg)
    )
    full = scan_body(
        lambda f, v, nseg: segment.sorted_segment_max_small(
            f, v, VALUE_BITS, nseg
        )
    )
    compact = scan_body(
        lambda f, v, nseg: segment.sorted_segment_reduce_compact(
            f, v, VALUE_BITS, nseg, None, "max"
        )
    )

    header = (
        f"{'rows':>10} {'nseg':>8} | {'scatter':>9} {'sort+full':>9} "
        f"{'compact':>9}  ns/row (max-reduction, value_bits={VALUE_BITS})"
    )
    log(header)
    log("-" * len(header))
    key = jax.random.PRNGKey(0)
    results = []
    for total in rows_list:
        b = min(block, total)
        k = max(total // b, 1)
        kf, kv = jax.random.split(key)
        for nseg in segs_list:
            if not segment.compact_fits_i32(nseg, VALUE_BITS):
                continue
            flat = jax.random.randint(kf, (k, b), 0, nseg, jnp.int32)
            vals = jax.random.randint(
                kv, (k, b), 0, 1 << VALUE_BITS, jnp.int32
            )
            jax.block_until_ready((flat, vals))
            rows = k * b
            with segment.platform_hint(dev.platform):
                t_sc = bench(direct, (nseg, flat, vals), rows, runs)
                t_fu = bench(full, (nseg, flat, vals), rows, runs)
                t_co = bench(compact, (nseg, flat, vals), rows, runs)
            log(
                f"{rows:>10} {nseg:>8} | {t_sc:>9.2f} {t_fu:>9.2f} "
                f"{t_co:>9.2f}"
            )
            results.append((rows, nseg, t_sc, t_fu, t_co))

    # Generic (arbitrary-dtype) min/max variant at one shape: what the
    # pipeline's high-cardinality min/max group-by lane pays.
    total = rows_list[-1]
    b = min(block, total)
    k = max(total // b, 1)
    nseg = segs_list[0]
    gids = jax.random.randint(key, (k, b), 0, nseg, jnp.int32)
    fvals = jax.random.normal(key, (k, b), jnp.float64) * 1e6

    def generic(kind):
        def fn(flat_blocks, val_blocks):
            def step(carry, xs):
                f, v = xs
                if kind == "compact":
                    m = segment.sorted_segment_minmax_compact(
                        v, f, nseg, None, False
                    )
                else:
                    m = jax.ops.segment_max(v, f, num_segments=nseg)
                return jnp.maximum(carry, m), None

            out, _ = jax.lax.scan(
                step, jnp.full(nseg, -jnp.inf, jnp.float64), (flat_blocks, val_blocks)
            )
            return out

        return jax.jit(fn)

    jax.block_until_ready((gids, fvals))
    with segment.platform_hint(dev.platform):
        g_sc = bench(generic("scatter"), (gids, fvals), k * b, runs)
        g_co = bench(generic("compact"), (gids, fvals), k * b, runs)
    log(
        f"\nf64 min/max, {k*b} rows x {nseg} segs: scatter {g_sc:.2f} "
        f"vs sort–compact {g_co:.2f} ns/row"
    )
    log(
        "\npaste-worthy summary (update ops/segment.py's measured-cost "
        "comment when run on hardware):"
    )
    for rows, nseg, t_sc, t_fu, t_co in results:
        log(
            f"  {rows//(1<<20)}M rows x {nseg} segs: scatter {t_sc:.1f} / "
            f"sort+full {t_fu:.1f} / compact {t_co:.1f} ns/row"
        )


if __name__ == "__main__":
    sys.exit(main())
