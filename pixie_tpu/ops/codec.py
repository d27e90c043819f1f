"""Staging codec: lightweight per-column compression with DEVICE-side decode.

After r6–r8 overlapped pack/transfer/compute, the wire itself is the cold
path (bench config 1: 572s of 613s in ``stage_transfer`` at ~100MB/s
host→HBM in round 5, through a remote backend since retired; the rate on
a local chip is not measured). The classic column-store result applies
directly: lightweight compression pays off most when the decoder runs
where the data lands (Abadi et al., SIGMOD 2006) — so the host packs
ENCODED shards, the wire/DMA carries the compressed representation, and a
small jitted program expands it in HBM ahead of the fold. The decoded
blocks are BIT-IDENTICAL to what the uncompressed pack would have
transferred, so everything downstream (fold programs, staged-cache
entries, shared scans) is untouched.

Encoders (chosen per column at plan time, passthrough when none pays):

- **RLE** (``rle``): per device-shard run values + cumulative run ends.
  Decode = ``searchsorted(ends, iota, 'right')`` + gather — a pure
  VPU-gather expansion, bit-exact for every dtype including NaN floats
  (run detection compares BIT PATTERNS via an unsigned view, so NaN runs
  compress instead of fragmenting). Wins on sorted/low-churn columns:
  gids of time-ordered group keys, status codes, enum-ish ints.
- **Delta** (``delta``): per-shard base + frame-of-reference-shifted
  deltas in the narrowest unsigned dtype that fits the column's global
  delta range. Decode = masked ``cumsum`` in int64 (exact) + cast.
  Wins on timestamps and monotone-ish ids whose VALUE range defeats
  plain frame-of-reference narrowing (a 64M-row time_ column spans
  >2^31 ns so ships as raw int64, but its deltas are ~constant: 8x).
  A non-monotone "monotone guess" simply has a wide delta range and
  falls back to passthrough at plan time; a pathological window that
  still overflows raises ``CodecOverflow`` and ships raw (per window).
  r16: a column whose delta RANGE fits 4 bits (a fixed-cadence
  timestamp has ~1 distinct delta) ships sub-byte — two deltas packed
  per byte (``delta_dtype="nib"``), halving the dominant column's wire
  bytes again vs u8. Decode unpacks nibbles (shift+mask, VPU-cheap)
  then runs the identical exact-int64 cumsum.

Both operate on the PACKED representation (after frame-of-reference
narrowing / f32-for-sketch / int-dictionary encoding, before the
[D, nblk, B] reshape), so the codec composes with — never replaces —
the r5 narrowing stack, and decode output == packed block by
construction. Decode programs are cached per (kind, dtypes, geometry,
run capacity) with bucketed capacities, so they share executables and
.jax_cache entries exactly like the fold units they feed.

This module also owns the raw→plan block CONVERTERS used by
device-resident ingest (serving/resident.py): ring tables hold
raw-dtype blocks; a query's plan-dtype view (narrow/f32/intdict) is
computed ON DEVICE from them, trading cheap TPU cycles for zero wire
bytes on the hot tail.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class CodecOverflow(Exception):
    """A window's data exceeded the plan's encoded capacity (more runs
    than runs_cap, or a delta outside the planned range). Callers ship
    that window raw — correctness never depends on the plan's guess."""


# Unsigned views for bit-pattern run detection: floats compare by bits so
# NaN == NaN (payload-exact) and -0.0 != +0.0 — both are what a LOSSLESS
# codec needs (decode is a gather of the original bit patterns).
_BITVIEW = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}


def _bits(a: np.ndarray) -> np.ndarray:
    if a.dtype.kind == "f":
        return a.view(_BITVIEW[a.dtype.itemsize])
    return a


def bucket_cap(n: int) -> int:
    """Round an encoded capacity up to its signature bucket (same
    quarter-octave pow2-scaled buckets as staging.bucket_block_count),
    bounding decode-program shape variety to O(log) distinct
    capacities."""
    if n <= 8:
        return max(n, 1)
    step = 1 << ((n - 1).bit_length() - 3)
    return ((n + step - 1) // step) * step


@dataclasses.dataclass(frozen=True)
class CodecPlan:
    """Per-column encode/decode recipe, fixed across a staging's windows
    (stream) or for its single monolithic window."""

    kind: str  # "rle" | "delta"
    dtype: str  # decoded (packed block) dtype str
    d: int  # device shards per window
    shard_len: int  # nblk * b elements per shard
    runs_cap: int = 0  # rle: padded runs per shard (bucketed)
    delta_dtype: str = ""  # delta: encoded delta dtype str ("nib" = u4x2)
    delta_off: int = 0  # delta: frame-of-reference offset on deltas

    def wire_nbytes(self) -> int:
        """Encoded bytes per window (static; what the wire carries)."""
        if self.kind == "rle":
            per = np.dtype(self.dtype).itemsize + 4  # values + i32 ends
            return self.d * self.runs_cap * per
        if self.delta_dtype == "nib":
            # Two 4-bit deltas per byte (+base+rows per shard).
            return self.d * ((self.shard_len + 1) // 2 + 8 + 4)
        per = np.dtype(self.delta_dtype).itemsize
        return self.d * (self.shard_len * per + 8 + 4)  # deltas+base+rows

    def block_nbytes(self) -> int:
        """Decoded bytes per window (what lands in HBM)."""
        return self.d * self.shard_len * np.dtype(self.dtype).itemsize

    def sig(self) -> str:
        """Decode-program identity (offset/base ride as traced args, so
        every staging sharing kind+dtype+geometry shares one
        executable and one .jax_cache entry)."""
        if self.kind == "rle":
            return (
                f"rle:{self.dtype}:d{self.d}:l{self.shard_len}"
                f":r{self.runs_cap}"
            )
        return (
            f"delta:{self.dtype}:{self.delta_dtype}:d{self.d}"
            f":l{self.shard_len}"
        )


@dataclasses.dataclass
class CodecPayload:
    """One window's encoded column: the arrays the wire actually
    carries. ``arrays`` order matches the decoder's signature."""

    plan: CodecPlan
    arrays: tuple  # rle: (values, ends); delta: (bases, deltas, rows)

    @property
    def nbytes(self) -> int:
        return int(sum(a.nbytes for a in self.arrays))


# -- planning ----------------------------------------------------------------


def _shard_bounds(
    num_rows: int, window_rows: int, shard_len: int, d: int
) -> np.ndarray:
    """Start offsets of every (window, device-shard) slice of the row
    range, clipped to num_rows — the units encode operates on."""
    n_windows = max((num_rows + window_rows - 1) // window_rows, 1)
    starts = []
    for w in range(n_windows):
        base = w * window_rows
        for s in range(d):
            starts.append(min(base + s * shard_len, num_rows))
    return np.asarray(starts, np.int64)


def _max_runs_per_shard(arr: np.ndarray, starts: np.ndarray) -> int:
    """Largest run count any shard sees, from ONE pass over the column
    (change flags + add.reduceat per shard)."""
    if arr.size <= 1:
        return 1
    v = _bits(arr)
    chg = v[1:] != v[:-1]
    # Shard s covers [starts[s], starts[s+1]); runs <= changes in that
    # span + 1 (the span includes the shard's trailing boundary — a
    # cheap upper bound; runs_cap only needs to dominate).
    idx = np.minimum(starts, chg.size - 1)
    counts = np.add.reduceat(chg, idx).astype(np.int64)
    # reduceat quirk: a segment whose start equals the next start (an
    # empty/clipped shard) returns chg[idx] instead of 0 — zero it.
    width = np.diff(np.append(idx, chg.size))
    counts = np.where(width > 0, counts, 0)
    return int(counts.max()) + 1 if counts.size else 1


def _delta_range(arr: np.ndarray) -> tuple[int, int]:
    """(min, max) of consecutive diffs, chunked so the int64 temp stays
    bounded on gigarow columns."""
    lo, hi = 0, 0
    chunk = 1 << 24
    first = True
    for off in range(0, arr.size - 1, chunk):
        a = arr[off : min(off + chunk + 1, arr.size)].astype(np.int64)
        dd = np.diff(a)
        if dd.size == 0:
            continue
        dmin, dmax = int(dd.min()), int(dd.max())
        if first:
            lo, hi = dmin, dmax
            first = False
        else:
            lo, hi = min(lo, dmin), max(hi, dmax)
    return lo, hi


def _delta_dtype_for(rng: int) -> Optional[str]:
    """Narrowest encoded-delta representation for a frame-of-reference
    delta range: "nib" (two 4-bit deltas per byte, r16) below 16, else
    u8/u16 dtype strs. A range past 16 bits defeats delta entirely."""
    if rng <= 0xF:
        return "nib"
    if rng <= 0xFF:
        return np.dtype(np.uint8).str
    if rng <= 0xFFFF:
        return np.dtype(np.uint16).str
    return None


def plan_codec(
    arr: np.ndarray,
    block_dtype: np.dtype,
    d: int,
    nblk: int,
    b: int,
    window_rows: int,
    num_rows: int,
    min_ratio: float,
    affine: bool,
) -> Optional[CodecPlan]:
    """Pick the cheapest encoder for a column, or None (passthrough).

    ``arr`` is the RAW host column; stats that survive the pack
    transform are computed on it directly (run boundaries are invariant
    under the affine narrow / int-dict transforms, and diffs are
    invariant under affine shifts), so the full packed column never
    materializes at plan time. ``affine`` is True when the pack
    transform preserves diffs (raw / narrow), enabling delta;
    f32-cast and int-dict columns are RLE-only. A column whose best
    encoder saves less than ``min_ratio`` ships passthrough."""
    if arr.size == 0 or num_rows <= 0:
        return None
    block_dtype = np.dtype(block_dtype)
    shard_len = nblk * b
    block_bytes = block_dtype.itemsize * d * shard_len  # per window
    starts = _shard_bounds(num_rows, window_rows, shard_len, d)
    candidates: list[CodecPlan] = []
    # RLE: runs_cap = observed max + slack for the padding run and the
    # clip-to-n boundary; every later window is a slice the plan's pass
    # already covered, so encode can only see fewer runs.
    runs_cap = bucket_cap(
        min(_max_runs_per_shard(arr, starts) + 2, shard_len)
    )
    rle = CodecPlan(
        kind="rle",
        dtype=block_dtype.str,
        d=d,
        shard_len=shard_len,
        runs_cap=runs_cap,
    )
    if rle.wire_nbytes() * min_ratio <= block_bytes:
        candidates.append(rle)
    if affine and arr.dtype.kind in "iu" and arr.size > 1:
        lo, hi = _delta_range(arr)
        ddt = _delta_dtype_for(hi - lo)
        if ddt is not None:
            delta = CodecPlan(
                kind="delta",
                dtype=block_dtype.str,
                d=d,
                shard_len=shard_len,
                delta_dtype=ddt,
                delta_off=lo,
            )
            if delta.wire_nbytes() * min_ratio <= block_bytes:
                candidates.append(delta)
    if not candidates:
        return None
    return min(candidates, key=lambda p: p.wire_nbytes())


def plan_codec_local(
    packed: np.ndarray,
    d: int,
    nblk: int,
    b: int,
    rows: int,
    min_ratio: float,
) -> Optional[CodecPlan]:
    """Single-window plan from the PACKED (transformed, padded) flat
    array itself — the monolithic-staging and resident-ingest entry
    point, where cross-window stability is moot and exact stats are
    free."""
    shard_len = nblk * b
    return plan_codec(
        packed[: max(rows, 1)],
        packed.dtype,
        d,
        nblk,
        b,
        window_rows=d * shard_len,
        num_rows=max(rows, 1),
        min_ratio=min_ratio,
        affine=packed.dtype.kind in "iu",
    )


# -- host encode -------------------------------------------------------------


def encode_window(
    packed_flat: np.ndarray, plan: CodecPlan, rows: int
) -> CodecPayload:
    """Encode one window's packed flat array ([d * shard_len], padded
    with zeros past ``rows``) into its wire payload. Raises
    CodecOverflow when the window defeats the plan — the caller ships
    that window raw."""
    d, L = plan.d, plan.shard_len
    shards = packed_flat.reshape(d, L)
    if plan.kind == "rle":
        values = np.zeros((d, plan.runs_cap), dtype=packed_flat.dtype)
        ends = np.full((d, plan.runs_cap), L, dtype=np.int32)
        for s in range(d):
            v = shards[s]
            bitsv = _bits(v)
            chg = np.flatnonzero(bitsv[1:] != bitsv[:-1]) + 1
            if chg.size + 1 > plan.runs_cap:
                raise CodecOverflow(
                    f"{chg.size + 1} runs > cap {plan.runs_cap}"
                )
            starts = np.concatenate(([0], chg))
            values[s, : starts.size] = v[starts]
            ends[s, : starts.size] = np.append(chg, L).astype(np.int32)
        return CodecPayload(plan, (values, ends))
    # delta
    nib = plan.delta_dtype == "nib"
    ddt = np.dtype(np.uint8) if nib else np.dtype(plan.delta_dtype)
    dmax = 0xF if nib else (1 << (8 * ddt.itemsize)) - 1
    bases = np.zeros(d, np.int64)
    rows_v = np.clip(rows - np.arange(d) * L, 0, L).astype(np.int32)
    deltas = np.zeros((d, L), dtype=ddt)
    for s in range(d):
        r = int(rows_v[s])
        if r == 0:
            continue
        v = shards[s][:r].astype(np.int64)
        bases[s] = v[0]
        if r > 1:
            enc = np.diff(v) - plan.delta_off
            if enc.size and (
                int(enc.min()) < 0 or int(enc.max()) > dmax
            ):
                raise CodecOverflow("delta outside planned range")
            deltas[s, 1:r] = enc.astype(ddt)
    if nib:
        # Two 4-bit deltas per byte, even index in the low nibble. L is
        # padded to even below so the odd tail has a zero high nibble.
        half = (L + 1) // 2
        if L % 2:
            deltas = np.concatenate(
                [deltas, np.zeros((d, 1), np.uint8)], axis=1
            )
        deltas = (deltas[:, 0::2] | (deltas[:, 1::2] << 4))[:, :half]
    return CodecPayload(plan, (bases, deltas, rows_v))


# -- device decode -----------------------------------------------------------


@functools.lru_cache(maxsize=128)
def _decoder(mesh: Mesh, sig: str, nblk: int, b: int):
    """Jitted decode program per (mesh, plan signature, geometry).
    Payload inputs are device-sharded on the leading axis and every
    lane is device-local (vmap over shards, no collectives); the output
    is the [D, nblk, B] block the fold would have received from an
    uncompressed transfer, bit for bit."""
    axis_name = tuple(mesh.axis_names)  # dim0 over every mesh axis
    sharding = NamedSharding(mesh, P(axis_name))
    parts = sig.split(":")
    kind = parts[0]
    L = nblk * b
    if kind == "rle":
        vdtype = np.dtype(parts[1])
        R = int(parts[4][1:])

        def dec_rle(values, ends):
            iota = jnp.arange(L, dtype=jnp.int32)

            def one(v, e):
                j = jnp.searchsorted(e, iota, side="right")
                return v[jnp.minimum(j, R - 1)].reshape(nblk, b)

            return jax.vmap(one)(values, ends)

        return jax.jit(dec_rle, out_shardings=sharding)

    vdtype = np.dtype(parts[1])
    nib = parts[2] == "nib"

    def dec_delta(bases, deltas, rows, off):
        iota = jnp.arange(L, dtype=jnp.int32)

        def one(b0, dl, r):
            if nib:
                # Unpack two 4-bit deltas per byte (low nibble first).
                lo16 = dl & 0xF
                hi16 = dl >> 4
                dl = jnp.stack([lo16, hi16], axis=-1).reshape(-1)[:L]
            d64 = dl.astype(jnp.int64) + off
            d64 = jnp.where((iota > 0) & (iota < r), d64, 0)
            v = b0 + jnp.cumsum(d64)
            v = jnp.where(iota < r, v, 0)
            return v.astype(vdtype).reshape(nblk, b)

        return jax.vmap(one, in_axes=(0, 0, 0))(bases, deltas, rows)

    return jax.jit(dec_delta, out_shardings=sharding, static_argnums=())


def decoder(mesh: Mesh, plan: CodecPlan, nblk: int, b: int):
    """The jitted decode program for ``plan`` at this geometry. Call
    with ``put_payload(mesh, payload)``'s device args (delta appends
    the plan's offset as a traced scalar, so the executable is shared
    across offsets and tables)."""
    return _decoder(mesh, plan.sig(), nblk, b)


def put_payload(mesh: Mesh, payload: CodecPayload) -> list:
    """device_put a payload's host arrays for the decoder: arrays shard
    on the leading (device) axis — this is the only wire transfer the
    column pays — and the delta offset rides replicated."""
    axis_name = tuple(mesh.axis_names)  # dim0 over every mesh axis
    sharded = NamedSharding(mesh, P(axis_name))
    repl = NamedSharding(mesh, P())
    args = [jax.device_put(a, sharded) for a in payload.arrays]
    if payload.plan.kind == "delta":
        args.append(jax.device_put(np.int64(payload.plan.delta_off), repl))
    return args


def decode_avals(plan: CodecPlan, mesh: Mesh):
    """ShapeDtypeStructs of the decoder's args (for background AOT
    compilation on the staging worker)."""
    axis_name = tuple(mesh.axis_names)  # dim0 over every mesh axis
    sharding = NamedSharding(mesh, P(axis_name))
    repl = NamedSharding(mesh, P())
    d, L = plan.d, plan.shard_len
    if plan.kind == "rle":
        return (
            jax.ShapeDtypeStruct(
                (d, plan.runs_cap), np.dtype(plan.dtype), sharding=sharding
            ),
            jax.ShapeDtypeStruct(
                (d, plan.runs_cap), np.int32, sharding=sharding
            ),
        )
    nib = plan.delta_dtype == "nib"
    return (
        jax.ShapeDtypeStruct((d,), np.int64, sharding=sharding),
        jax.ShapeDtypeStruct(
            (d, (L + 1) // 2 if nib else L),
            np.uint8 if nib else np.dtype(plan.delta_dtype),
            sharding=sharding,
        ),
        jax.ShapeDtypeStruct((d,), np.int32, sharding=sharding),
        jax.ShapeDtypeStruct((), np.int64, sharding=repl),
    )


# -- raw→plan converters (device-resident ingest) ----------------------------
#
# Ring tables (serving/resident.py) hold RAW-dtype blocks — the pack
# recipe (narrow offsets, f32 cast, int-dict codes) is query/staging
# specific and can't be fixed at ingest time. These converters compute
# the plan-dtype view ON DEVICE, reproducing pack_stream_window's host
# transform bit for bit: identity, (x - off).astype(dt),
# x.astype(f32), and min(searchsorted(lut, x), C-1).astype(dt).


@functools.lru_cache(maxsize=128)
def _converter(
    mesh: Mesh,
    kind: str,
    src_dtype: str,
    dst_dtype: str,
    nblk: int,
    b: int,
    lut_len: int,
):
    axis_name = tuple(mesh.axis_names)  # dim0 over every mesh axis
    sharding = NamedSharding(mesh, P(axis_name))
    dst = np.dtype(dst_dtype)

    if kind == "raw":
        fn = lambda x: x.astype(dst)
    elif kind == "f32":
        fn = lambda x: x.astype(jnp.float32)
    elif kind == "narrow":

        def fn(x, off):
            return (x.astype(jnp.int64) - off).astype(dst)

    elif kind == "intdict":

        def fn(x, lut):
            c = jnp.searchsorted(lut, x)
            return jnp.minimum(c, lut_len - 1).astype(dst)

    else:  # pragma: no cover - plan kinds are closed
        raise ValueError(f"unknown convert kind {kind!r}")
    return jax.jit(fn, out_shardings=sharding)


def convert_block(mesh: Mesh, col_plan, raw_block, int_dtype=None):
    """Apply a StreamPlan col_plan ("raw"/"f32"/"narrow"/"intdict") to a
    raw-dtype device block, returning the plan-dtype block the fold
    expects. ``raw_block`` is [D, nblk, B]; scalars/LUTs ride as traced
    args so executables are shared across offsets and tables."""
    kind, info = col_plan
    d, nblk, b = raw_block.shape
    if kind == "raw":
        dst = np.dtype(raw_block.dtype) if int_dtype is None else int_dtype
        fn = _converter(
            mesh, "raw", str(raw_block.dtype), np.dtype(dst).str, nblk, b, 0
        )
        return fn(raw_block)
    if kind == "f32":
        fn = _converter(
            mesh, "f32", str(raw_block.dtype), "f4", nblk, b, 0
        )
        return fn(raw_block)
    if kind == "narrow":
        dt, off = info
        fn = _converter(
            mesh, "narrow", str(raw_block.dtype), np.dtype(dt).str, nblk, b, 0
        )
        return fn(raw_block, np.int64(off))
    if kind == "intdict":
        lut, dt = info
        lut = np.asarray(lut)
        fn = _converter(
            mesh,
            "intdict",
            str(raw_block.dtype),
            np.dtype(dt).str,
            nblk,
            b,
            int(lut.shape[0]),
        )
        return fn(raw_block, lut.astype(np.int64))
    raise ValueError(f"unknown col plan kind {kind!r}")
