"""Masked segment reductions — the group-by aggregation primitive.

The reference aggregates row-at-a-time into an absl hash map of per-group UDA
objects (src/carnot/exec/agg_node.cc: HashRowBatch -> AggHashValue ->
UDA::Update). On TPU there are no dynamic hash maps inside a compiled
program; instead group keys are dense int32 segment ids (strings arrive
dictionary-encoded; other key types are densified host-side by
pixie_tpu.exec's GroupDictionary) and aggregation is an XLA segment
reduction over a static number of segments. Padding rows carry mask=False.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# Strategy selection: XLA's scatter-add lowers to the TPU's scalar scatter
# unit (~160M rows/s measured on v5e — and int64 scatter is ~12x worse at
# ~13M rows/s, the dominant cost of integer group-by sums in round 3); a
# one-hot matvec/einsum rides the MXU at ~240M rows/s up to a few thousand
# segments, with cost scaling ~n*num_segments beyond. Non-sum reductions
# (max/min) have no einsum form; above SORTED_MIN_ROWS they ride the r8
# sort–COMPACT lane instead (two i32-class sorts + an O(num_segments)
# scatter; see sorted_segment_reduce_compact); int64/float64 sums past
# the MXU's segment range sort too (sorted_segment_sum). CPU prefers
# scatter everywhere. Tests can pin a strategy via set_strategy() /
# set_sorted_strategy().
import contextlib
import threading

from pixie_tpu.utils import flags

_FORCE: Optional[str] = None
_TLS = threading.local()  # per-thread platform hint: agents run in threads
MATMUL_MAX_SEGMENTS = 8192


def set_strategy(s: Optional[str]) -> None:
    """Force 'matmul' or 'scatter' (None = auto by platform)."""
    global _FORCE
    assert s in (None, "matmul", "scatter")
    _FORCE = s


class platform_hint:
    """Context manager: pin the platform these kernels will execute on for
    the CURRENT THREAD. jax.default_backend() is a process-wide default
    that can differ from the mesh/device a program is traced for (e.g. CPU
    exec graph on a TPU-attached host); concurrent agent threads each carry
    their own hint."""

    def __init__(self, platform: Optional[str]):
        self.platform = platform

    def __enter__(self):
        self._old = getattr(_TLS, "hint", None)
        _TLS.hint = self.platform
        return self

    def __exit__(self, *exc):
        _TLS.hint = self._old
        return False


def _use_matmul(num_segments: int) -> bool:
    if _FORCE is not None:
        return _FORCE == "matmul"
    platform = getattr(_TLS, "hint", None) or jax.default_backend()
    return platform != "cpu" and num_segments <= MATMUL_MAX_SEGMENTS


def matmul_strategy(num_segments: int) -> bool:
    """Public strategy probe for composite sketches (histogram)."""
    return _use_matmul(num_segments)


_FORCE_SORTED: Optional[bool] = None


def set_sorted_strategy(v: Optional[bool]) -> None:
    """Force the sort-based reduction lane on (True) / off (False);
    None = auto (sorted_strategy below). History: the r4 sort-DEDUP
    design issued a FULL-LENGTH scatter (dropped duplicates are not free
    — the scalar unit walks every index) and lost to the direct scatter
    everywhere (r5: count-min 43 vs 27 ns/row, HLL 12.6 vs 10.6). The r8
    sort–COMPACT lane removes that full-length scatter entirely
    (sorted_segment_reduce_compact: the ≤ nseg winners are compacted to
    the front by a second sort and the final scatter operand has STATIC
    length nseg), so the lane is back on by default on TPU above
    SORTED_MIN_ROWS, behind the ``sorted_compact`` flag."""
    global _FORCE_SORTED
    _FORCE_SORTED = v


def sorted_strategy(n_rows: Optional[int] = None, nseg: Optional[int] = None) -> bool:
    """Should this reduction ride the sort–compact lane?

    Auto policy (no force): TPU-class platforms only (CPU scatters are
    cheap and its sorts are not), ``sorted_compact`` flag on, at least
    SORTED_MIN_ROWS rows, and — when the caller knows its segment count —
    nseg small enough relative to n that the compacted O(nseg) scatter
    tail is actually negligible (≥4x shorter than the direct scatter)."""
    if _FORCE_SORTED is not None:
        return _FORCE_SORTED
    if not flags.sorted_compact:
        return False
    if n_rows is not None and n_rows < SORTED_MIN_ROWS:
        return False
    if n_rows is not None and nseg is not None and nseg * 4 > n_rows:
        return False
    platform = getattr(_TLS, "hint", None) or jax.default_backend()
    return platform != "cpu"


# -- reduction-lane telemetry: which lane each traced program chose.
# Incremented at TRACE time (once per compiled program, not per run) so
# bench.py can record the chosen lane per config next to rows/s.
LANE_COUNTS: dict[str, int] = {}


def lane_count(name: str) -> None:
    LANE_COUNTS[name] = LANE_COUNTS.get(name, 0) + 1
    for sink in getattr(_TLS, "sinks", ()):
        sink.add(name)


@contextlib.contextmanager
def lane_sink(lanes: set):
    """Add to the set ``lanes`` every lane counted on this thread inside
    the with-block: a program that opens one around its traced body keeps
    the lanes it was compiled with."""
    stack = _TLS.__dict__.setdefault("sinks", [])
    stack.append(lanes)
    try:
        yield
    finally:
        stack.pop()


def reduce_lanes(reset: bool = False) -> dict:
    snap = dict(LANE_COUNTS)
    if reset:
        LANE_COUNTS.clear()
    return snap


def _matvec_sum(values_f32, seg_ids, num_segments: int):
    """sum per segment as [1,n]@[n,S] — MXU path, f32 accumulate."""
    oh = jax.nn.one_hot(seg_ids, num_segments, dtype=jnp.float32)
    return values_f32 @ oh


_F64_CHUNK = 256  # bounds f32 in-chunk accumulation error (~chunk*eps relative)


def _matvec_sum_f64(values, seg_ids, num_segments: int):
    """f64 segment sums that still ride the MXU: split each value into
    hi/lo float32 parts (exact to ~2^-48 relative), matmul each part in
    per-chunk batches, and accumulate the chunk partials in float64 — so
    representation error is ~f64-level and f32 accumulation is bounded to
    _F64_CHUNK elements, keeping device sums consistent with the f64
    scatter/host path (they diverged before; ADVICE r1)."""
    n = values.shape[0]
    if n == 0:
        return jnp.zeros((num_segments,), jnp.float64)
    chunk = min(_F64_CHUNK, n)
    pad = (-n) % chunk
    if pad:
        values = jnp.pad(values, (0, pad))  # pad value 0: no-op in a sum
        seg_ids = jnp.pad(seg_ids, (0, pad))
    c = values.shape[0] // chunk
    hi = values.astype(jnp.float32)
    lo = (values - hi.astype(jnp.float64)).astype(jnp.float32)
    oh = jax.nn.one_hot(
        seg_ids.reshape(c, chunk), num_segments, dtype=jnp.float32
    )
    parts_hi = jnp.einsum("ck,cks->cs", hi.reshape(c, chunk), oh)
    parts_lo = jnp.einsum("ck,cks->cs", lo.reshape(c, chunk), oh)
    return jnp.sum(
        parts_hi.astype(jnp.float64) + parts_lo.astype(jnp.float64), axis=0
    )


_LIMB_CHUNK = 1 << 16  # 8-bit limbs: in-chunk f32 sums <= 2^16*255 < 2^24


def _chunked_onehot_sums(V, seg_ids, num_segments: int, chunk: int):
    """[R, n] f32 rows -> [R, S] f64 per-segment sums sharing ONE one-hot,
    accumulating f32 within ``chunk``-sized pieces and f64 across them.
    The precision contract is the CALLER's: limb_einsum_sums feeds exact
    small ints (error-free), f32_rows_einsum feeds arbitrary f32
    (~chunk*eps relative in-chunk error)."""
    n = V.shape[1]
    chunk = min(chunk, max(n, 1))
    pad = (-n) % chunk
    if pad:
        V = jnp.pad(V, ((0, 0), (0, pad)))
        seg_ids = jnp.pad(seg_ids, (0, pad))  # pad rows are 0: no-op
    c = V.shape[1] // chunk
    oh = jax.nn.one_hot(
        seg_ids.reshape(c, chunk), num_segments, dtype=jnp.float32
    )
    parts = jnp.einsum("vck,cks->vcs", V.reshape(-1, c, chunk), oh)
    return jnp.sum(parts.astype(jnp.float64), axis=1)  # [R, S]


def limb_rows_i64(values) -> list:
    """Decompose int64 (two's-complement bit pattern) into eight 8-bit
    limbs as f32 rows. Reconstruction mod 2^64 reproduces exact wrapped
    int64 sums. Only native 32-bit ALU ops (bitcast + shifts/masks)."""
    w = jax.lax.bitcast_convert_type(values.astype(jnp.int64), jnp.uint32)
    rows = []
    for word in (w[..., 0], w[..., 1]):
        for sh in (0, 8, 16, 24):
            rows.append(
                ((word >> jnp.uint32(sh)) & jnp.uint32(0xFF)).astype(
                    jnp.float32
                )
            )
    return rows


def limb_einsum_sums(rows, seg_ids, num_segments: int):
    """Exact per-segment sums of non-negative f32 integer rows — each
    value MUST be an integer in [0, 255] — sharing ONE one-hot:
    [L, n] -> [L, S] float64.

    Exactness: within a chunk every f32 partial sum is an integer
    <= chunk (2^16) * 255 < 2^24, so each add is exact; chunk partials
    are accumulated in f64 (integers < 2^52, exact). Values above 255
    would overflow the 2^24 exact-integer range of f32 mid-chunk — wider
    values must be limb-decomposed first (limb_rows_i64). The MXU does
    the heavy lifting — this replaces the s64 scalar scatter (12x
    slower)."""
    return _chunked_onehot_sums(
        jnp.stack(rows), seg_ids, num_segments, _LIMB_CHUNK
    )


_F32_CHUNK = 1 << 16


def f32_rows_einsum(rows, seg_ids, num_segments: int):
    """Per-segment sums of several f32 rows sharing ONE one-hot:
    [R, n] -> [R, S] float64. Unlike limb_einsum_sums the row values are
    arbitrary f32 (not exact small ints): in-chunk accumulation is f32
    (relative error ~chunk*eps of the chunk partial), chunk partials
    accumulate in f64. Right for f32-grained sketch states (t-digest
    weights/means); exact integer sums must use limb_einsum_sums. The
    one-hot generation dominates, so batching all rows into one einsum
    costs the same as one row (r5 measured: 2 rows 3.76ns vs 9 rows
    4.05ns at 4096 segments)."""
    V = jnp.stack([r.astype(jnp.float32) for r in rows])  # [R, n]
    return _chunked_onehot_sums(V, seg_ids, num_segments, _F32_CHUNK)


def reconstruct_i64(limb_totals):
    """[8, S] f64 limb sums -> exact int64 sums (mod 2^64)."""
    acc = limb_totals[0].astype(jnp.int64)
    for i in range(1, 8):
        acc = acc + (limb_totals[i].astype(jnp.int64) << (8 * i))
    return acc


# -- sort–compact reduction lane (r8, TPU fast path) -------------------------
# TPU's scalar unit serializes scatters: ~7 ns/element at ANY segment
# count, and the cost scales with the scatter OPERAND LENGTH, not the
# unique count — the r4/r5 sort-dedup design still paid a full-length
# scatter and lost. The r8 lane removes it: sort so each segment's
# winning value sorts first, mask the first occurrences, then COMPACT
# the ≤ nseg winners to the front with a second sort keyed
# (winner ? packed_key : SENTINEL) and finish with a scatter whose
# operand has STATIC length nseg (~16K registers) instead of n (64M
# rows). Expected TPU cost: two i32 sorts (0.6–2.4 ns/row measured on a
# v5e at 2M–32M rows, STATUS r5) + an O(nseg) tail, vs ~7 ns/row for the
# direct scatter. tools/microbench_sort_reduce.py sweeps rows x segments
# for all three designs (direct scatter / sort+full-scatter /
# sort–compact). CPU-measured (this container, 1M–4M rows x 2^10–2^16
# segs): scatter 39–46 ns/row, sort+full-scatter 119–125, sort–compact
# 109–120 — compaction beats the full scatter at every shape, but CPU
# sorts are so slow the direct scatter wins outright, which is why the
# lane is TPU-gated (re-run the microbench on hardware to refresh the
# v5e column). Shared by HLL register maxes, count-min bucket counts, and
# (via the generic two-operand variant) high-cardinality min/max
# group-bys; the sentinel segment `nseg` collects masked/losing rows and
# lands on a dropped slot.

# Lane threshold: below this the direct scatter wins. r4 measured 1<<22
# for the sort+FULL-scatter design; the compact lane's scatter tail is
# O(nseg), so the crossover is just where two sorts beat ~7 ns/row —
# readjusted to 1<<20 (provisional: re-measure with
# tools/microbench_sort_reduce.py on hardware).
SORTED_MIN_ROWS = 1 << 20


def compact_fits_i32(nseg: int, value_bits: int) -> bool:
    """Can (segment, value) pack into one non-negative int32 key with a
    sentinel segment? Shared overflow gate: callers must fall back to the
    direct scatter past it (sorted_segment_reduce_compact raises)."""
    return (nseg + 1) << value_bits < (1 << 31)


def sorted_segment_reduce_compact(
    flat, values, value_bits: int, nseg: int, mask=None, mode: str = "max"
):
    """Segment reduction via sort → first-occurrence → COMPACT → O(nseg)
    scatter. The compaction is the r8 algorithmic idea: XLA scatter cost
    scales with operand length, so the winners are compacted to the
    front (second sort keyed ``winner ? packed_key : SENTINEL`` — the
    packed key already orders by segment) and statically sliced to
    ``nseg`` before the final scatter, which therefore touches nseg
    elements instead of n.

    Modes over int32 results:
      'max' / 'min' — reduce ``values`` (small non-negative ints
        < 2^value_bits, e.g. HLL rho) per segment. Empty segments hold 0
        for max (matching sorted_segment_max_small) and
        (2^value_bits - 1) for min.
      'count' — rows per segment; ``values``/``value_bits`` ignored.

    Raises ValueError when (nseg+1) << value_bits overflows int32 — the
    caller must take the direct-scatter lane instead (silent wraparound
    would corrupt every segment id past the boundary)."""
    if mode not in ("max", "min", "count"):
        raise ValueError(f"unknown sort–compact mode {mode!r}")
    if mode == "count":
        value_bits = 0
    if not compact_fits_i32(nseg, value_bits):
        raise ValueError(
            "sorted_segment_reduce_compact: (nseg+1) << value_bits "
            f"overflows int32 (nseg={nseg}, value_bits={value_bits}); "
            "use the direct-scatter lane"
        )
    n = flat.shape[0]
    vmax = jnp.int32((1 << value_bits) - 1)
    if n == 0:
        fill = vmax if mode == "min" else jnp.int32(0)
        return jnp.full(nseg, fill, jnp.int32)
    sentinel = jnp.int32(nseg << value_bits)
    if mode == "count":
        key = flat.astype(jnp.int32)
        if mask is not None:
            key = jnp.where(mask, key, jnp.int32(nseg))
        ks = jnp.sort(key)
        idx = jnp.arange(n, dtype=jnp.int32)
        first = jnp.concatenate([jnp.ones(1, jnp.bool_), ks[1:] != ks[:-1]])
        # Index of the next run start AFTER each position: reverse cummin
        # of start positions (n where not a start).
        start_at = jnp.where(first, idx, jnp.int32(n))
        nxt = jnp.flip(
            jax.lax.cummin(
                jnp.flip(
                    jnp.concatenate([start_at[1:], jnp.full(1, n, jnp.int32)])
                )
            )
        )
        runlen = jnp.where(first, nxt - idx, 0)
        keep = first & (ks < nseg)
        ckey, ccnt = jax.lax.sort(
            (jnp.where(keep, ks, jnp.int32(nseg)), runlen), num_keys=1
        )
        k = min(nseg, n)
        seg, cnt = ckey[:k], ccnt[:k]
        live = seg < nseg
        return (
            jnp.zeros(nseg, jnp.int32)
            .at[jnp.where(live, seg, nseg)]
            .add(jnp.where(live, cnt, 0), mode="drop")
        )
    # max/min: pack (segment, value) into one key so each segment's
    # winning value sorts FIRST within its run.
    vkey = (vmax - values) if mode == "max" else values
    key = (flat.astype(jnp.int32) << value_bits) | vkey.astype(jnp.int32)
    if mask is not None:
        key = jnp.where(mask, key, sentinel)
    ks = jnp.sort(key)
    flat_s = ks >> value_bits
    first = jnp.concatenate(
        [jnp.ones(1, jnp.bool_), flat_s[1:] != flat_s[:-1]]
    )
    keep = first & (flat_s < nseg)
    # Compact: the winners' packed keys already order by segment, so one
    # more sort with losers collapsed onto the sentinel brings the ≤ nseg
    # winners to the front; the slice length is STATIC.
    cks = jnp.sort(jnp.where(keep, ks, sentinel))[: min(nseg, n)]
    seg = cks >> value_bits
    val = cks & vmax
    if mode == "max":
        val = vmax - val
    live = seg < nseg
    fill = jnp.int32(0) if mode == "max" else vmax
    return (
        jnp.full(nseg, fill, jnp.int32)
        .at[jnp.where(live, seg, nseg)]
        .set(jnp.where(live, val, fill), mode="drop")
    )


def sorted_segment_minmax_compact(
    values, seg_ids, num_segments: int, mask=None, is_min: bool = False
):
    """Per-segment min/max of ARBITRARY-dtype values (int64/float64
    group-by args) via a two-operand lexicographic sort + the same
    compaction: sort (segment, value) ascending, take the first (min) or
    last (max) row of each segment's run, compact the winners with a
    second sort, and scatter nseg elements. Empty segments hold the same
    identity fill seg_min/seg_max produce, so elementwise state merges
    are unchanged."""
    ident = _identity_for(values.dtype, is_min=is_min)
    n = values.shape[0]
    if n == 0:
        return jnp.full(num_segments, ident, values.dtype)
    seg = seg_ids.astype(jnp.int32)
    if mask is not None:
        seg = jnp.where(mask, seg, jnp.int32(num_segments))
    seg_s, val_s = jax.lax.sort((seg, values), num_keys=2)
    if is_min:
        winner = jnp.concatenate(
            [jnp.ones(1, jnp.bool_), seg_s[1:] != seg_s[:-1]]
        )
    else:
        winner = jnp.concatenate(
            [seg_s[1:] != seg_s[:-1], jnp.ones(1, jnp.bool_)]
        )
    winner = winner & (seg_s < num_segments)
    ckey, cval = jax.lax.sort(
        (jnp.where(winner, seg_s, jnp.int32(num_segments)), val_s),
        num_keys=1,
    )
    k = min(num_segments, n)
    seg_c, val_c = ckey[:k], cval[:k]
    live = seg_c < num_segments
    return (
        jnp.full(num_segments, ident, values.dtype)
        .at[jnp.where(live, seg_c, num_segments)]
        .set(jnp.where(live, val_c, ident), mode="drop")
    )


def _run_sums(values, run_start):
    """Inclusive sums of ``values`` that restart at every ``run_start``:
    a Hillis–Steele scan, ceil(log2 n) steps of one contiguous shift and
    one add over the whole array. (lax.associative_scan's strided halves
    took minutes to compile for the TPU at 2^21 rows.)"""
    n = values.shape[0]
    acc, started = values, run_start
    step = 1
    while step < n:
        prev = jnp.concatenate([jnp.zeros(step, acc.dtype), acc[:-step]])
        prev_started = jnp.concatenate(
            [jnp.zeros(step, jnp.bool_), started[:-step]]
        )
        acc = jnp.where(started, acc, prev + acc)
        started = started | prev_started
        step *= 2
    return acc


# Rows one sort of sorted_segment_sum spans. A TPU sort that carries
# 64-bit values is slow to compile, and a fold program holds one per
# 64-bit sum: in 2^17-row tiles the http_node fold (2^21-row blocks,
# 32,768 segments, an int64 and a float64 sum) compiles in 47 s for a
# described v5e, untiled in 77 s, with both sums scattered in 13 s.
# Tiles cost run time (each tile's segment table): on a v5e, that
# block's two sums take 40-43 ms tiled and 19.5 ms untiled, against
# the scatters' 296-436 ms.
_SUM_SORT_ROWS = 1 << 17


def sorted_segment_sum(values, seg_ids, num_segments: int, mask=None):
    """Per-segment sums of int64 or float64 values with no 64-bit
    scatter of the values. Each tile of rows sorts (segment id, value)
    on the id alone; a scan over the sorted values that restarts at every
    run (and tile) is read at each run's last row, found from the tile's
    segment counts; the tiles' run sums add up per segment.

    int64: every add wraps modulo 2^64 as the scatter's do, so the sums
    are bit-identical. float64: each segment's rounding is its own (a
    difference of global prefix sums would cancel against every row
    sorted before it); the sort moves the value and never compares or
    rounds it (the TPU cannot bitcast an emulated f64 to int64 bits).
    Masked rows and ids outside [0, num_segments) count for no segment;
    empty segments read 0.

    Cost, for n rows in t = ceil(n / 2^17) tiles: the tiles' sorts
    (n log n), a scan of ceil(log2 n) steps over n, and a segment table
    a tile, t x (num_segments + 1) wide: one int32 scatter of n counts
    into it, its cumsum, a 64-bit gather of the run ends and the sum
    over tiles. So the table outgrows the rows once num_segments passes
    n / t; sum_sorted_strategy keeps the lane below that."""
    n = values.shape[0]
    if n == 0:
        return jnp.zeros(num_segments, values.dtype)
    seg = seg_ids.astype(jnp.int32)
    keep = (seg >= 0) & (seg < num_segments)
    if mask is not None:
        keep = keep & mask
    seg = jnp.where(keep, seg, jnp.int32(num_segments))
    tiles = -(-n // _SUM_SORT_ROWS)
    rows = -(-n // tiles)
    pad = tiles * rows - n
    if pad:
        seg = jnp.concatenate([seg, jnp.full(pad, num_segments, jnp.int32)])
        values = jnp.concatenate([values, jnp.zeros(pad, values.dtype)])
    seg = seg.reshape(tiles, rows)
    seg_s, val_s = jax.lax.sort(
        (seg, values.reshape(tiles, rows)), dimension=1, num_keys=1
    )
    run_start = jnp.concatenate(
        [jnp.ones((tiles, 1), jnp.bool_), seg_s[:, 1:] != seg_s[:, :-1]],
        axis=1,
    )
    run_sum = _run_sums(val_s.reshape(-1), run_start.reshape(-1))
    # Each tile's run bounds from its segment counts (an int32 scatter,
    # which every sum of the block shares): the run of segment s ends at
    # the tile's count of ids <= s.
    width = num_segments + 1
    tile_key = jnp.arange(tiles, dtype=jnp.int32)[:, None] * width + seg
    counts = jax.ops.segment_sum(
        jnp.ones(tiles * rows, jnp.int32),
        tile_key.reshape(-1),
        num_segments=tiles * width,
    ).reshape(tiles, width)[:, :num_segments]
    end = jnp.cumsum(counts, axis=1)
    last = jnp.take_along_axis(
        run_sum.reshape(tiles, rows), jnp.maximum(end - 1, 0), axis=1
    )
    return jnp.where(counts > 0, last, jnp.zeros((), values.dtype)).sum(
        axis=0
    )


def sorted_segment_counts(flat, nseg: int, mask=None):
    """Per-segment counts via sort + run-length + compaction (r8: the
    r4 unique-index scatter was still FULL-length — XLA walks every
    index — so it lost; the compacted scatter touches nseg elements).
    Exact; int32 result (callers widen)."""
    return sorted_segment_reduce_compact(
        flat, None, 0, nseg, mask, mode="count"
    )


def sorted_segment_max_small(flat, values, value_bits: int, nseg: int, mask=None):
    """Per-segment max of small non-negative ints (< 2^value_bits) via a
    single packed-key sort: key = flat << bits | (max_value - value), so
    each segment's LARGEST value sorts first and the first-occurrence mask
    yields unique scatter indices. Requires (nseg+1) << value_bits < 2^31.
    Returns int32 maxes (0 for empty segments).

    NOTE (r8): the scatter here is still FULL-LENGTH (unique indices are
    not cheaper — XLA scatter cost scales with operand length), which is
    why this design lost to the direct scatter in r5. Kept as the
    sort+full-scatter comparand for tools/microbench_sort_reduce.py;
    production consumers use sorted_segment_reduce_compact."""
    n = flat.shape[0]
    if n == 0:
        return jnp.zeros(nseg, jnp.int32)
    vmax = jnp.int32((1 << value_bits) - 1)
    key = (flat << value_bits) | (vmax - values)
    if mask is not None:
        key = jnp.where(mask, key, jnp.int32(nseg << value_bits))
    ks = jnp.sort(key)
    flat_s = ks >> value_bits
    val_s = vmax - (ks & vmax)
    first = jnp.concatenate(
        [jnp.ones(1, jnp.bool_), flat_s[1:] != flat_s[:-1]]
    )
    keep = first & (flat_s < nseg)
    idx = jnp.where(keep, flat_s, nseg)
    out = (
        jnp.zeros(nseg + 1, jnp.int32)
        .at[idx]
        .max(jnp.where(keep, val_s, 0), mode="drop")
    )
    return out[:-1]


# -- r19: sort-merge join primitives ----------------------------------------
# The join lane reuses the r8 idioms directly: a stable packed-key sort
# orders the build side (reproducing the host JoinNode's per-key original
# row order), searchsorted runs the merge, and the sentinel-sort
# compaction brings unmatched rows to the front for the outer variants.
# Output is bounded by host-computed caps (exact match/unmatched counts
# from bincount, padded to a power of two) so every shape is static.


def merge_join_pairs(sorted_build_keys, build_order, probe_keys, pair_cap: int):
    """Emit up to ``pair_cap`` (build_row, probe_row) match pairs of an
    equijoin between a SORTED build side and an unsorted probe side.

    ``sorted_build_keys``/``build_order`` come from one stable sort of the
    build keys (order = original row index), so within each key the build
    rows appear in original order — matching the host JoinNode's stable
    ``_build_order``. Pairs are probe-row-major: for probe row p with
    fanout f, its f pairs occupy slots [prefix[p]-f, prefix[p]).

    Returns ``(build_rows, probe_rows, valid, fanout)`` — all int32 except
    the bool ``valid`` mask; slots past the true match count are invalid
    (clipped gathers; callers mask or slice them away). ``fanout`` is the
    per-probe-row match count (0 for masked/padded rows whose key is a
    sentinel absent from the build side). Callers guarantee the true match
    total fits ``pair_cap`` and int32."""
    nb = sorted_build_keys.shape[0]
    np_ = probe_keys.shape[0]
    lo = jnp.searchsorted(
        sorted_build_keys, probe_keys, side="left"
    ).astype(jnp.int32)
    hi = jnp.searchsorted(
        sorted_build_keys, probe_keys, side="right"
    ).astype(jnp.int32)
    fanout = hi - lo
    prefix = jnp.cumsum(fanout)
    t = jnp.arange(pair_cap, dtype=jnp.int32)
    # Slot t belongs to the first probe row whose prefix exceeds t.
    probe_rows = jnp.minimum(
        jnp.searchsorted(prefix, t, side="right").astype(jnp.int32),
        jnp.int32(np_ - 1),
    )
    base = prefix[probe_rows] - fanout[probe_rows]
    build_pos = jnp.clip(lo[probe_rows] + (t - base), 0, nb - 1)
    return build_order[build_pos], probe_rows, t < prefix[-1], fanout


def local_sort_merge(lkey, rkey, lmask, rmask, cap_m: int, cap_r: int, cap_l: int):
    """The sort→merge→compact core shared by the replicated (v1) and
    partitioned (r21 mesh) join lanes, over whatever key slice the
    caller holds — the whole table when replicated, one hosts-axis
    shard when partitioned.

    ``lkey``/``rkey`` are sentinel-applied (padded build rows carry a
    key above every real id, padded probe rows one higher still, so
    neither can pair). One stable sort orders the build side by
    (key, original row), reproducing the host JoinNode's per-key
    original row order; ``merge_join_pairs`` emits probe-row-major
    match pairs; the sentinel-sort compaction fronts unmatched rows
    for the outer variants (cap 0 skips a section).

    Returns ``(build_rows, probe_rows, fanout, ur, ul)`` — int32 row
    indices into the caller's key slices; ``ur``/``ul`` are None when
    their cap is 0."""
    sl_key, sl_idx = jax.lax.sort(
        (lkey, jnp.arange(lkey.shape[0], dtype=jnp.int32)),
        num_keys=1,
        is_stable=True,
    )
    build_rows, probe_rows, _pv, fanout = merge_join_pairs(
        sl_key, sl_idx, rkey, cap_m
    )
    ur = ul = None
    if cap_r:
        ur = compact_unmatched_rows(rmask & (fanout == 0), cap_r)
    if cap_l:
        sr_key = jnp.sort(rkey)
        l_matched = jnp.searchsorted(
            sr_key, lkey, side="right"
        ) > jnp.searchsorted(sr_key, lkey, side="left")
        ul = compact_unmatched_rows(lmask & ~l_matched, cap_l)
    return build_rows, probe_rows, fanout, ur, ul


def compact_unmatched_rows(unmatched, cap: int):
    """Compact the indices of ``unmatched`` rows to the front, preserving
    original row order — the r8 sentinel-sort idiom (losers collapse onto
    sentinel ``n``, one sort, static slice). Returns int32[cap]; entries
    >= n are padding."""
    n = unmatched.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    out = jnp.sort(jnp.where(unmatched, idx, jnp.int32(n)))[: min(cap, n)]
    if cap > n:
        out = jnp.concatenate([out, jnp.full(cap - n, n, jnp.int32)])
    return out


def sum_sorted_strategy(n_rows: int, num_segments: int, dtype) -> bool:
    """Should seg_sum take sorted_segment_sum? Only 64-bit sums above
    MATMUL_MAX_SEGMENTS (below it they ride the MXU), and then — unless
    set_sorted_strategy forces it — on a TPU-class platform, with the
    ``sorted_compact`` flag on, at least SORTED_MIN_ROWS rows, and
    segment tables (one a 2^17-row tile) no larger than the rows. Reads
    shapes, dtype and platform only, so one process compiles the same
    program as the next."""
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.int64), jnp.dtype(jnp.float64)):
        return False
    if num_segments <= MATMUL_MAX_SEGMENTS:
        return False
    if _FORCE_SORTED is not None:
        return _FORCE_SORTED
    if not flags.sorted_compact or n_rows < SORTED_MIN_ROWS:
        return False
    tiles = -(-n_rows // _SUM_SORT_ROWS)
    if tiles * (num_segments + 1) > n_rows:
        return False
    platform = getattr(_TLS, "hint", None) or jax.default_backend()
    return platform != "cpu"


def seg_sum(values, seg_ids, num_segments: int, mask=None):
    if _use_matmul(num_segments) and jnp.issubdtype(
        values.dtype, jnp.floating
    ):
        if values.dtype == jnp.float64:
            v = values if mask is None else jnp.where(mask, values, 0.0)
            return _matvec_sum_f64(v, seg_ids, num_segments)
        v = values.astype(jnp.float32)
        if mask is not None:
            v = jnp.where(mask, v, 0.0)
        return _matvec_sum(v, seg_ids, num_segments).astype(values.dtype)
    if _use_matmul(num_segments) and values.dtype == jnp.int64:
        # int32 stays on the (fast) s32 scatter; int64 scatter is ~12x
        # slower than s32, so exact limb sums on the MXU win decisively.
        v = values if mask is None else jnp.where(mask, values, 0)
        totals = limb_einsum_sums(
            limb_rows_i64(v), seg_ids.astype(jnp.int32), num_segments
        )
        return reconstruct_i64(totals)
    if sum_sorted_strategy(values.shape[0], num_segments, values.dtype):
        lane_count("sum_sorted")
        return sorted_segment_sum(values, seg_ids, num_segments, mask)
    v = values if mask is None else jnp.where(mask, values, 0)
    return jax.ops.segment_sum(v, seg_ids, num_segments=num_segments)


def seg_count(seg_ids, num_segments: int, mask=None):
    if _use_matmul(num_segments):
        ones = (
            jnp.ones(seg_ids.shape, jnp.float32)
            if mask is None
            else mask.astype(jnp.float32)
        )
        # Chunk-exact at any n: in-chunk f32 sums are integers <= 2^16.
        totals = limb_einsum_sums(
            [ones], seg_ids.astype(jnp.int32), num_segments
        )
        return totals[0].astype(jnp.int64)
    # Scatter-add in int32 — TPU emulates s64 scatters at ~3x the cost —
    # and widen after: a single call covers one block (< 2^31 rows), so the
    # int32 partial is exact; the int64 accumulation across blocks happens
    # in the caller's state.
    ones = (
        jnp.ones(seg_ids.shape, jnp.int32)
        if mask is None
        else mask.astype(jnp.int32)
    )
    return jax.ops.segment_sum(
        ones, seg_ids, num_segments=num_segments
    ).astype(jnp.int64)


def seg_min(values, seg_ids, num_segments: int, mask=None):
    # min has no MXU einsum form; the sort–compact lane replaces the
    # ~7 ns/row scalar scatter above SORTED_MIN_ROWS (r8).
    if sorted_strategy(values.shape[0], num_segments):
        lane_count("minmax_sorted_compact")
        return sorted_segment_minmax_compact(
            values, seg_ids, num_segments, mask, is_min=True
        )
    lane_count("minmax_scatter")
    if mask is not None:
        fill = _identity_for(values.dtype, is_min=True)
        values = jnp.where(mask, values, fill)
    return jax.ops.segment_min(values, seg_ids, num_segments=num_segments)


def seg_max(values, seg_ids, num_segments: int, mask=None):
    if sorted_strategy(values.shape[0], num_segments):
        lane_count("minmax_sorted_compact")
        return sorted_segment_minmax_compact(
            values, seg_ids, num_segments, mask, is_min=False
        )
    lane_count("minmax_scatter")
    if mask is not None:
        fill = _identity_for(values.dtype, is_min=False)
        values = jnp.where(mask, values, fill)
    return jax.ops.segment_max(values, seg_ids, num_segments=num_segments)


def seg_any(values, seg_ids, num_segments: int, mask=None):
    v = values.astype(jnp.int32)
    if mask is not None:
        v = jnp.where(mask, v, 0)
    return jax.ops.segment_max(v, seg_ids, num_segments=num_segments).astype(jnp.bool_)


def seg_mean_state(values, seg_ids, num_segments: int, mask=None):
    """(sum, count) pair — mergeable across shards before the divide."""
    return (
        seg_sum(values, seg_ids, num_segments, mask),
        seg_count(seg_ids, num_segments, mask),
    )


def _identity_for(dtype, is_min: bool):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf if is_min else -jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.max if is_min else info.min, dtype)


def flat_segment_ids(gids, inner_ids, inner_size: int):
    """Compose (group, bucket) -> flat segment id for 2-D scatter-free
    histogram updates: segment-reduce over gids*inner_size+inner then reshape."""
    return gids.astype(jnp.int32) * inner_size + inner_ids.astype(jnp.int32)
