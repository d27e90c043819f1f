"""HyperLogLog distinct-count sketch, vectorized over groups.

Net-new UDA (the reference ships no HLL — SURVEY.md §6): state is a dense
[num_groups, m] int32 register tensor (m = 2^precision), merge is
elementwise max — so the cross-device merge lowers to a single `lax.pmax`
over ICI.

Update strategy: hashing rides the native-u32 pipeline (TPU has no
64-bit multiplier; a u64 splitmix costs ~5x more per block). The
register update is max-reduction over a small packed domain
(rho < 2^5), which r8 expresses as the sort–COMPACT lane
(segment.sorted_segment_reduce_compact): pack (register, rho) into one
i32 key, sort so each register's winning rho sorts first, compact the
≤ nseg winners to the front with a second sort, and finish with an
O(nseg) scatter — the full-length ~7ns/row scalar scatter the r5
sort-DEDUP attempt still paid (and lost to, 12.6 vs 10.6 ns/row) is
gone from the lane entirely. Below segment.SORTED_MIN_ROWS (or past the
i32 packing boundary, or on CPU) the direct scatter-max remains the
lane of record; small-domain columns keep the r7 MXU cell lane
(cell_update).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from pixie_tpu.ops import hashing, segment

DEFAULT_PRECISION = 11  # m=2048 registers -> ~2.3% standard error
_RHO_BITS = 5  # rho <= 32 - precision + 1 <= 29 for precision >= 4


def init(num_groups: int, precision: int = DEFAULT_PRECISION):
    if precision < 4:
        raise ValueError(f"HLL precision must be >= 4 (got {precision})")
    return jnp.zeros((num_groups, 1 << precision), jnp.int32)


def _reg_rho(values, precision: int):
    """(register index, rho) from a 32-bit hash stream."""
    h = hashing.hash32(values)
    reg = (h >> jnp.uint32(32 - precision)).astype(jnp.int32)
    rest = h << jnp.uint32(precision)
    rho = jnp.minimum(
        hashing.clz32(rest) + 1, jnp.int32(32 - precision + 1)
    ).astype(jnp.int32)
    return reg, rho


def update(state, gids, values, mask=None):
    num_groups, m = state.shape
    precision = int(m).bit_length() - 1  # derived: m == 2**precision
    reg, rho = _reg_rho(values, precision)
    flat = segment.flat_segment_ids(gids, reg, m)
    nseg = num_groups * m
    if segment.sorted_strategy(flat.shape[0], nseg) and (
        segment.compact_fits_i32(nseg, _RHO_BITS)
    ):
        # Sort–compact register update (r8): rho packs into the key so
        # each register's largest rho sorts first; the winners compact to
        # the front and the final scatter operand is O(nseg), not O(n).
        # The i32 packing boundary falls back to the scatter below — a
        # wrapped key would silently corrupt register ids.
        segment.lane_count("hll_sorted_compact")
        maxes = segment.sorted_segment_reduce_compact(
            flat, rho, _RHO_BITS, nseg, mask, mode="max"
        )
        return jnp.maximum(state, maxes.reshape(num_groups, m))
    segment.lane_count("hll_scatter")
    if mask is not None:
        rho = jnp.where(mask, rho, 0)
    # Direct scatter-max regardless of the generic minmax lane: this IS
    # the fallback for rows/boundaries the compact lane rejected.
    maxes = jax.ops.segment_max(rho, flat, num_segments=nseg)
    return jnp.maximum(state, maxes.reshape(num_groups, m))


def cell_update(state, hist, lut):
    """Fold a per-(group, value-code) histogram into the registers.

    ``hist``: [num_groups, C] int64 row counts per cell; ``lut``: [C] the
    int64 value each code stands for. Every row of a cell carries the
    same (register, rho) pair, so maxing rho over PRESENT cells
    (hist > 0 — cardinality ignores multiplicity) reproduces the row-wise
    scatter exactly while touching num_groups*C elements instead of n
    rows: approx_count_distinct on small-domain int columns rides the
    pipeline's MXU cell lane like count-min does.
    """
    num_groups, m = state.shape
    precision = int(m).bit_length() - 1
    reg, rho = _reg_rho(lut, precision)  # [C] each
    rho_gc = jnp.where(hist > 0, rho[None, :], 0).astype(jnp.int32)
    flat = (
        jnp.arange(num_groups, dtype=jnp.int32)[:, None] * m + reg[None, :]
    ).reshape(-1)
    maxes = segment.seg_max(rho_gc.reshape(-1), flat, num_groups * m)
    return jnp.maximum(state, maxes.reshape(num_groups, m))


def merge(a, b):
    return jnp.maximum(a, b)


def _alpha(m: int) -> float:
    if m == 16:
        return 0.673
    if m == 32:
        return 0.697
    if m == 64:
        return 0.709
    return 0.7213 / (1 + 1.079 / m)


def estimate_terms(state):
    """The estimate's integer inputs per group, [G, 3] int64: the sum of
    2^(32 - rho), the zero-register count, and m. Registers hold
    rho <= 33 - precision, so every term is an integer and the sum
    (<= m * 2^32) fits int64: exact on every backend, in any order. This
    is the device half of finalize; the host forms the f64 estimate
    (estimate_from_terms), since the TPU emulates f64 and its estimates
    rounded differently from the host engine's (PR 21's chip smoke)."""
    g, m = state.shape
    terms = jnp.left_shift(jnp.int64(1), 32 - state.astype(jnp.int64))
    return jnp.stack(
        [
            terms.sum(axis=1),
            jnp.sum(state == 0, axis=1, dtype=jnp.int64),
            jnp.full((g,), m, jnp.int64),
        ],
        axis=1,
    )


def estimate_from_terms(terms) -> np.ndarray:
    """Per-group cardinality estimates (int64, rounded) on the host, with
    the standard small-range (linear counting) and 32-bit large-range
    corrections. The large-range term compensates hash collisions as raw
    estimates approach the 2^32 hash space (registers derive from 32-bit
    hashes since r4; without it, estimates undercount past ~2^32/30)."""
    terms = np.asarray(terms, np.int64)
    if terms.shape[0] == 0:
        return np.zeros(0, np.int64)
    m = int(terms[0, 2])
    two32 = float(1 << 32)
    raw = _alpha(m) * m * m * two32 / terms[:, 0].astype(np.float64)
    zeros = terms[:, 1].astype(np.float64)
    linear = m * np.log(np.maximum(m / np.maximum(zeros, 1e-9), 1.0))
    use_linear = (raw <= 2.5 * m) & (zeros > 0)
    large = -two32 * np.log(
        np.maximum(1.0 - np.minimum(raw, two32 * 0.9999) / two32, 1e-12)
    )
    corrected = np.where(raw > two32 / 30.0, large, raw)
    return np.round(np.where(use_linear, linear, corrected)).astype(np.int64)


def estimate(state) -> np.ndarray:
    """Per-group cardinality estimates [num_groups] (int64, host)."""
    return estimate_from_terms(estimate_terms(state))
