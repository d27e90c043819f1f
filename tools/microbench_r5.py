"""Round-5 kernel microbenchmarks on the real chip.

Measures the per-row cost of the sketch/aggregation primitives that bound
bench configs 3/4/5, plus prototypes of the r5 redesigns:
  - count-min: 4x sorted counts (r4) vs direct scatter vs ONE-sort run-length
    vs small-domain histogram path
  - t-digest: 2-key sort (r4) vs packed single-key sort
  - HLL: sorted vs scatter register update
  - fused limb einsum at varying row counts (narrowed-sum payoff)
  - any(): scatter seg_max vs packed-key sort
  - raw sort costs at 2M/8M/32M

Every body carries REAL state through a lax.scan (like the pipeline), so
XLA cannot fold the work away; results block on the final state tensors.

Usage: python tools/microbench_r5.py [total_rows_millions]
"""

import sys
import time

import numpy as np

import pixie_tpu  # noqa: F401  (enables x64)
import jax
import jax.numpy as jnp

from pixie_tpu.ops import countmin, hashing, hll, segment, tdigest

TOTAL = int(sys.argv[1]) * (1 << 20) if len(sys.argv) > 1 else (32 << 20)


def log(msg):
    print(msg, flush=True)


def bench(name, fn, args, rows, runs=3):
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    compile_s = time.perf_counter() - t0
    best = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    log(
        f"{name:34s} {best*1e9/rows:7.2f} ns/row  "
        f"({rows/best/1e6:8.1f} Mrows/s)  compile {compile_s:5.1f}s"
    )
    return best


def scan_over(init_fn, body, K):
    """body(state, *block_cols) -> state; returns jit(fn(*blocks))."""

    def fn(*blocks):
        def step(carry, xs):
            return body(carry, *xs), None

        out, _ = jax.lax.scan(step, init_fn(), blocks)
        return out

    return jax.jit(fn)


def main():
    key = jax.random.PRNGKey(0)
    dev = jax.devices()[0]
    log(f"device: {dev}, total rows per measurement: {TOTAL}")

    B = 8 << 20  # 8M-row blocks
    K = TOTAL // B

    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    gids16 = jax.random.randint(k1, (K, B), 0, 16, jnp.int32)
    gids4k = jax.random.randint(k2, (K, B), 0, 4096, jnp.int32)
    vals_i = jax.random.randint(k3, (K, B), 0, 1 << 20, jnp.int64)
    vals_small = jax.random.randint(k3, (K, B), 0, 4, jnp.int64)
    vals_f = (
        jax.random.exponential(k4, (K, B), jnp.float32).astype(jnp.float64)
        * 3e7
    )
    codes12 = jax.random.randint(k5, (K, B), 0, 4096, jnp.int32)
    mask = jnp.ones((K, B), jnp.bool_)
    jax.block_until_ready((gids16, gids4k, vals_i, vals_f, codes12))

    with segment.platform_hint(dev.platform):
        # ---- raw sorts: carry a sampled-order-stats accumulator ----------
        for n in (2 << 20, 8 << 20, 32 << 20):
            kk = max(min(TOTAL // n, 4), 1)
            d = jax.random.randint(key, (kk, n), 0, 1 << 30, jnp.int32)

            def sort_body(acc, x):
                s = jnp.sort(x)
                return acc + s[:: 4096].astype(jnp.float64)

            f = scan_over(
                lambda n=n: jnp.zeros((n + 4095) // 4096, jnp.float64),
                sort_body,
                kk,
            )
            bench(f"sort_i32 n={n>>20}M", f, (d,), kk * n)

        d2a = jax.random.randint(k1, (K, B), 0, 1 << 30, jnp.int32)
        d2b = jax.random.randint(k2, (K, B), 0, 1 << 30, jnp.int32)

        def sort2_body(acc, x, y):
            a, b = jax.lax.sort((x, y), num_keys=2)
            return (
                acc
                + a[::4096].astype(jnp.float64)
                + b[::4096].astype(jnp.float64)
            )

        f = scan_over(
            lambda: jnp.zeros(B // 4096, jnp.float64), sort2_body, K
        )
        bench("sort_2key_i32 n=8M", f, (d2a, d2b), K * B)

        def sortp_body(acc, x, y):
            a, b = jax.lax.sort((x, y), num_keys=1)
            return (
                acc
                + a[::4096].astype(jnp.float64)
                + b[::4096].astype(jnp.float64)
            )

        f = scan_over(
            lambda: jnp.zeros(B // 4096, jnp.float64), sortp_body, K
        )
        bench("sort_1key+payload n=8M", f, (d2a, d2b), K * B)

        def sort3_body(acc, x, y, z):
            a, b, c = jax.lax.sort((x, y, z), num_keys=1)
            return (
                acc
                + a[::4096].astype(jnp.float64)
                + b[::4096].astype(jnp.float64)
                + c[::4096].astype(jnp.float64)
            )

        f = scan_over(
            lambda: jnp.zeros(B // 4096, jnp.float64), sort3_body, K
        )
        bench("sort_1key+2payload n=8M", f, (d2a, d2b, gids4k), K * B)

        # ---- count-min variants ------------------------------------------
        G, depth, width = 16, countmin.DEFAULT_DEPTH, countmin.DEFAULT_WIDTH

        def cm_body(strategy):
            def body(st, g, v, m):
                segment.set_sorted_strategy(strategy)
                out = countmin.update(st, g, v, m)
                segment.set_sorted_strategy(None)
                return out

            return body

        f = scan_over(lambda: countmin.init(G), cm_body(True), K)
        bench("cm_r4_sorted4 (16g)", f, (gids16, vals_i, mask), K * B)
        f = scan_over(lambda: countmin.init(G), cm_body(False), K)
        bench("cm_scatter (16g)", f, (gids16, vals_i, mask), K * B)

        def cm_sort1_body(st, g, v, m):
            h1, h2 = hashing.hash32_pair(v, seed=1)
            gg = jnp.where(m, g, jnp.int32(G))
            s_g, s_h1, s_h2 = jax.lax.sort(
                (gg, h1.astype(jnp.int32), h2.astype(jnp.int32)), num_keys=3
            )
            n = v.shape[0]
            idx = jnp.arange(n, dtype=jnp.int32)
            first = jnp.concatenate(
                [
                    jnp.ones(1, jnp.bool_),
                    (s_g[1:] != s_g[:-1])
                    | (s_h1[1:] != s_h1[:-1])
                    | (s_h2[1:] != s_h2[:-1]),
                ]
            )
            start_at = jnp.where(first, idx, jnp.int32(n))
            nxt = jnp.flip(
                jax.lax.cummin(
                    jnp.flip(
                        jnp.concatenate(
                            [start_at[1:], jnp.full(1, n, jnp.int32)]
                        )
                    )
                )
            )
            runlen = jnp.where(first, nxt - idx, 0)
            keep = first & (s_g < G)
            h1u, h2u = s_h1.astype(jnp.uint32), s_h2.astype(jnp.uint32)
            nseg = G * width
            outs = []
            for dd in range(depth):
                b = (
                    (h1u + jnp.uint32(dd) * h2u) & jnp.uint32(width - 1)
                ).astype(jnp.int32)
                flat = jnp.where(keep, s_g * width + b, jnp.int32(nseg))
                cnt = (
                    jnp.zeros(nseg + 1, jnp.int32)
                    .at[flat]
                    .add(jnp.where(first, runlen, 0), mode="drop")
                )
                outs.append(cnt[:-1].reshape(G, width))
            return st + jnp.stack(outs, axis=1)

        f = scan_over(lambda: countmin.init(G), cm_sort1_body, K)
        bench("cm_sort1 (16g)", f, (gids16, vals_i, mask), K * B)

        def cm_hist_body(st, g, v, m):
            flat = g * 256 + v.astype(jnp.int32)
            hist = segment.limb_einsum_sums(
                [m.astype(jnp.float32)], flat, G * 256
            )[0]
            cells = jnp.arange(G * 256, dtype=jnp.int32)
            vals = (cells % 256).astype(jnp.int64)
            cg = cells // 256
            h1, h2 = hashing.hash32_pair(vals, seed=1)
            outs = []
            for dd in range(depth):
                b = (
                    (h1 + jnp.uint32(dd) * h2) & jnp.uint32(width - 1)
                ).astype(jnp.int32)
                flat2 = cg * width + b
                cnt = (
                    jnp.zeros(G * width, jnp.float64)
                    .at[flat2]
                    .add(hist)
                    .astype(jnp.int64)
                )
                outs.append(cnt.reshape(G, width))
            return st + jnp.stack(outs, axis=1)

        f = scan_over(lambda: countmin.init(G), cm_hist_body, K)
        bench(
            "cm_hist_smalldomain (16g)", f, (gids16, vals_small, mask), K * B
        )

        # ---- t-digest variants -------------------------------------------
        f = scan_over(
            lambda: tdigest.init(G),
            lambda st, g, v, m: tdigest.update(st, g, v, m),
            K,
        )
        bench("td_r4_2keysort (16g)", f, (gids16, vals_f, mask), K * B)

        CAP = tdigest.DEFAULT_CAPACITY

        def td_packed_body(st, g, v, m):
            vf = v.astype(jnp.float32)
            u = jax.lax.bitcast_convert_type(vf, jnp.uint32)
            mapped = jnp.where(
                (u >> jnp.uint32(31)) > 0, ~u, u | jnp.uint32(0x80000000)
            )
            gg = jnp.where(m, g, jnp.int32(G)).astype(jnp.uint32)
            key_u = (gg << jnp.uint32(27)) | (mapped >> jnp.uint32(5))
            ks = jnp.sort(key_u)
            g_s = (ks >> jnp.uint32(27)).astype(jnp.int32)
            mp = ks << jnp.uint32(5)
            uu = jnp.where(
                (mp >> jnp.uint32(31)) > 0, mp & jnp.uint32(0x7FFFFFFF), ~mp
            )
            v_s = jax.lax.bitcast_convert_type(uu, jnp.float32)
            n = v.shape[0]
            w_s = (g_s < G).astype(jnp.float32)
            counts_i = segment.seg_count(g_s, G + 1).astype(jnp.int32)
            starts_i = jnp.cumsum(counts_i) - counts_i
            rank = (jnp.arange(n, dtype=jnp.int32) - starts_i[g_s]).astype(
                jnp.float32
            )
            counts = counts_i.astype(jnp.float32)
            qmid = (rank + 0.5) / jnp.maximum(counts[g_s], 1.0)
            cl = tdigest._cluster_ids(qmid, CAP)
            flat = jnp.where(g_s < G, g_s * CAP + cl, G * CAP)
            nseg = G * CAP + 1
            w_new = segment.seg_sum(w_s, flat, nseg)[:-1].reshape(G, CAP)
            m_sum = segment.seg_sum(v_s * w_s, flat, nseg)[:-1].reshape(
                G, CAP
            )
            batch = {
                "means": jnp.where(
                    w_new > 0, m_sum / jnp.maximum(w_new, 1.0), 0.0
                ),
                "weights": w_new,
            }
            return tdigest.merge(st, batch)

        f = scan_over(lambda: tdigest.init(G), td_packed_body, K)
        bench("td_packedkey (16g)", f, (gids16, vals_f, mask), K * B)

        # ---- HLL (4096 groups, like config 3) ----------------------------
        def hll_body(strategy):
            def body(st, g, v, m):
                segment.set_sorted_strategy(strategy)
                out = hll.update(st, g, v, m)
                segment.set_sorted_strategy(None)
                return out

            return body

        f = scan_over(lambda: hll.init(4096), hll_body(True), K)
        bench("hll_sorted (4096g)", f, (gids4k, vals_i, mask), K * B)
        f = scan_over(lambda: hll.init(4096), hll_body(False), K)
        bench("hll_scatter (4096g)", f, (gids4k, vals_i, mask), K * B)

        # ---- fused limb einsum at varying widths -------------------------
        def einsum_body(nrows, nseg):
            def body(st, g, v, m):
                limbs = segment.limb_rows_i64(v) + segment.limb_rows_i64(
                    v + 1
                )
                rows = list(limbs[: nrows - 1]) + [m.astype(jnp.float32)]
                return st + segment.limb_einsum_sums(rows, g, nseg)

            return body

        for nrows in (2, 9, 17):
            f = scan_over(
                lambda nrows=nrows: jnp.zeros((nrows, 4096), jnp.float64),
                einsum_body(nrows, 4096),
                K,
            )
            bench(
                f"einsum_{nrows}rows (4096seg)",
                f,
                (gids4k, vals_i, mask),
                K * B,
            )
        f = scan_over(
            lambda: jnp.zeros((9, 16), jnp.float64), einsum_body(9, 16), K
        )
        bench("einsum_9rows (16seg)", f, (gids16, vals_i, mask), K * B)

        # ---- any(): scatter vs packed sort -------------------------------
        f = scan_over(
            lambda: jnp.zeros(4096, jnp.int32),
            lambda st, g, v, m: jnp.maximum(
                st, segment.seg_max(v, g, 4096, m)
            ),
            K,
        )
        bench("anymax_scatter_i32 (4096g)", f, (gids4k, codes12, mask), K * B)

        f = scan_over(
            lambda: jnp.zeros(4096, jnp.int32),
            lambda st, g, v, m: jnp.maximum(
                st, segment.sorted_segment_max_small(g, v, 12, 4096, m)
            ),
            K,
        )
        bench("anymax_sorted (4096g)", f, (gids4k, codes12, mask), K * B)

        # ---- r5 engine-shaped composites ---------------------------------
        # config-5 shape: new tdigest.update + count-min cell lane.
        lut4 = jnp.asarray([200, 301, 404, 500], jnp.int64)

        def cfg5_body(st, g, v, m, codes):
            td_st, cm_st = st
            td_st = tdigest.update(td_st, g, v, m)
            C = 4
            flat = g * C + codes.astype(jnp.int32)
            h = segment.limb_einsum_sums([m.astype(jnp.float32)], flat, G * C)
            hist = h[0].astype(jnp.int64).reshape(G, C)
            cm_st = countmin.cell_update(cm_st, hist, lut4)
            return (td_st, cm_st)

        codes4 = jax.random.randint(k5, (K, B), 0, 4, jnp.int32)
        f = scan_over(
            lambda: (tdigest.init(G), countmin.init(G)), cfg5_body, K
        )
        bench(
            "cfg5_td_new+cm_cell (16g)",
            f,
            (gids16, vals_f, mask, codes4),
            K * B,
        )

        # new tdigest.update alone (packed sort + fused einsum inside)
        f = scan_over(
            lambda: tdigest.init(G),
            lambda st, g, v, m: tdigest.update(st, g, v, m),
            K,
        )
        bench("td_new (16g)", f, (gids16, vals_f, mask), K * B)

        # config-4 shape: fused count einsum only (any is host-side now)
        def cfg4_body(st, g, v, m):
            rows = segment.limb_rows_i64(v) + [m.astype(jnp.float32)]
            return st + segment.limb_einsum_sums(rows, g, 4096)

        f = scan_over(
            lambda: jnp.zeros((9, 4096), jnp.float64), cfg4_body, K
        )
        bench("cfg4_fused_counts (4096g)", f, (gids4k, vals_i, mask), K * B)

        # scatter cost vs nseg (is the scalar unit nseg-sensitive?)
        for nseg in (16, 4096, 1 << 20):
            f = scan_over(
                lambda nseg=nseg: jnp.zeros(nseg, jnp.int32),
                lambda st, g, v, m: jnp.maximum(
                    st,
                    segment.seg_max(
                        v, g % nseg if nseg < 4096 else g, nseg, m
                    ),
                ),
                K,
            )
            bench(
                f"segmax_scatter nseg={nseg}",
                f,
                (gids4k, codes12, mask),
                K * B,
            )

    # ---- correctness spot checks ------------------------------------------
    log("--- correctness spot checks ---")
    rng = np.random.default_rng(0)
    n = 50_000
    g_np = rng.integers(0, G, n).astype(np.int32)
    v_np = rng.integers(0, 1 << 20, n).astype(np.int64)
    m_np = rng.random(n) < 0.9
    ref = countmin.update(
        countmin.init(G),
        jnp.asarray(g_np),
        jnp.asarray(v_np),
        jnp.asarray(m_np),
    )
    got = cm_sort1_body(
        countmin.init(G),
        jnp.asarray(g_np),
        jnp.asarray(v_np),
        jnp.asarray(m_np),
    )
    assert np.array_equal(np.asarray(ref), np.asarray(got)), "cm_sort1 wrong"
    log("cm_sort1 matches r4 countmin.update exactly")

    # cm_hist over a small domain must also match exactly.
    v_small_np = rng.integers(0, 4, n).astype(np.int64)
    ref2 = countmin.update(
        countmin.init(G),
        jnp.asarray(g_np),
        jnp.asarray(v_small_np),
        jnp.asarray(m_np),
    )
    got2 = cm_hist_body(
        countmin.init(G),
        jnp.asarray(g_np),
        jnp.asarray(v_small_np),
        jnp.asarray(m_np),
    )
    assert np.array_equal(np.asarray(ref2), np.asarray(got2)), "cm_hist wrong"
    log("cm_hist matches r4 countmin.update exactly")

    # td_packed quantiles close to numpy truth
    st = tdigest.init(1)
    st = td_packed_body(
        st,
        jnp.zeros(n, jnp.int32),
        jnp.asarray(rng.exponential(3e7, n)),
        jnp.ones(n, jnp.bool_),
    )
    q = np.asarray(tdigest.quantile_values(st, [0.5, 0.99]))[0]
    true_p50 = 3e7 * np.log(2)
    assert abs(q[0] - true_p50) / true_p50 < 0.05, (q[0], true_p50)
    log(f"td_packed p50 within 5% of truth ({q[0]:.3g} vs {true_p50:.3g})")


if __name__ == "__main__":
    main()
