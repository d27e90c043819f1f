"""Host→HBM staging: table columns → device-sharded padded blocks.

The TPU analogue of the reference's per-PEM data locality: every device owns
a contiguous shard of the table's rows ([D, nblk, B] layout, sharded on the
leading device axis), padded to static shapes with a validity mask — XLA
requires static shapes, and padding+mask is how streaming row counts meet
that constraint (SURVEY.md §7 "Streaming/windowed execution vs XLA's static
shapes").

Strings never ship to HBM: their int32 dictionary codes do (table/column.py
write-side encoding), and group keys densify to gids host-side before
staging (ops/segment.py's contract).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pixie_tpu.table.column import DictColumn
from pixie_tpu.table.table import Table
from pixie_tpu.utils import faults, flags, trace

DEFAULT_BLOCK_ROWS = 1 << 17

# Cold-path phase timings (cumulative seconds since last reset): where a
# first query's latency goes — host column reads, gid densification,
# host-side pack, host→HBM transfer, program trace+compile+execute.
# bench.py resets before each cold query and writes the breakdown to the
# ledger (VERDICT r4 weakness 4).
COLD_PROFILE: dict[str, float] = {}


def reset_cold_profile() -> dict:
    snap = dict(COLD_PROFILE)
    COLD_PROFILE.clear()
    return snap


def count_read_batches(n: int) -> None:
    """COLD_PROFILE["read_batches"]: table-cursor batches holding rows
    that one pass over a table walked (each helper that walks a cursor
    counts its own pass once)."""
    COLD_PROFILE["read_batches"] = COLD_PROFILE.get("read_batches", 0.0) + n


def count_read_visits(n: int) -> None:
    """COLD_PROFILE["read_visits"]: table segments that one pass's
    cursor examined (``Cursor.segments_visited``), counted beside
    count_read_batches by the same helpers."""
    COLD_PROFILE["read_visits"] = COLD_PROFILE.get("read_visits", 0.0) + n


def count_key_evals(n: int) -> None:
    """COLD_PROFILE["key_evals"]: group-key evaluations that one key
    plan ran (one per fixed-size chunk of rows, MeshExecutor._plan_keys)."""
    COLD_PROFILE["key_evals"] = COLD_PROFILE.get("key_evals", 0.0) + n


def count_device_aggs(n: int) -> None:
    """COLD_PROFILE["device_aggs"]: aggregations that one offload
    answered on the device (one per branch of a fan-out)."""
    COLD_PROFILE["device_aggs"] = COLD_PROFILE.get("device_aggs", 0.0) + n


# Observed staged (decoded, HBM-resident) bytes per row, by table — the
# metadata admission control uses to estimate a query's staging cost
# BEFORE the cold stage starts (serving/admission.estimate_staging_bytes).
# Updated after every staging; survives cache eviction.
OBSERVED_BPR: dict[str, float] = {}


def record_observed_bpr(table_name: str, nbytes: int, rows: int) -> None:
    if table_name and rows > 0 and nbytes > 0:
        OBSERVED_BPR[table_name] = nbytes / rows


class timed:
    """with timed('stage') as sp: ... — accumulates into COLD_PROFILE, and
    runs the block as a ``device.<key>`` span (utils/trace.py) under the
    running query's ambient context: nested phases parent to it, and a
    profiler trace shows it on the device's clock. ``sp.set(k=v)`` adds
    span attributes. ``span=False`` for a phase that runs once a window
    or a column: it only adds to COLD_PROFILE, inside its caller's span,
    so that a query's span count does not grow with its data."""

    def __init__(self, key: str, span: bool = True):
        self.key = key
        self.span = trace.span(f"device.{key}") if span else None

    def __enter__(self):
        if self.span is not None:
            self.span.__enter__()
        self.t0 = time.perf_counter()
        return self.span

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        COLD_PROFILE[self.key] = COLD_PROFILE.get(self.key, 0.0) + dt
        if self.span is not None:
            self.span.__exit__(*exc)
        return False


@dataclasses.dataclass
class StagedColumns:
    """Columns resident on the mesh + the host-side key bookkeeping."""

    blocks: dict[str, jax.Array]  # name -> [D, nblk, B], device-sharded
    mask: jax.Array  # [D, nblk, B] bool, False on padding
    gids: Optional[jax.Array]  # [D, nblk, B] int32 (None: no grouping)
    num_rows: int
    num_devices: int
    block_rows: int
    num_groups: int
    capacity: int  # padded static group capacity (pow2)
    key_columns: list  # per group col: np.ndarray or DictColumn, gid order
    dictionaries: dict  # col name -> StringDictionary (for aux/LUT building)
    # Frame-of-reference narrowing: int64 columns whose value RANGE fits a
    # narrower dtype ship as uint8/int32 of (value - offset); the compiled
    # program widens per block (cast + add, VPU-cheap). Host→HBM transfer
    # was the cold-path bottleneck in round 5 (~19MB/s through a remote
    # backend since retired; local PCIe is ~10GB/s), so staged bytes are
    # the metric that matters.
    narrow_offsets: dict = dataclasses.field(default_factory=dict)
    # Int-dictionary columns: blocks[name] holds SMALL-DOMAIN CODES
    # (uint8/uint16) and int_dicts[name] is the [C] int64 value LUT — the
    # cell lane aggregates per (group, code) histogram instead of per row.
    int_dicts: dict = dataclasses.field(default_factory=dict)
    # A fan-out's staging (one table staged for several aggregations):
    # the gid blocks of each branch that groups by host gids, by the
    # branch's aggregation node id. ``gids`` is then None.
    branch_gids: dict = dataclasses.field(default_factory=dict)


def _pow2_at_least(n: int, floor: int = 8) -> int:
    c = floor
    while c < n:
        c <<= 1
    return c


def bucket_block_count(n: int) -> int:
    """Round a per-device block count up to its signature bucket.

    Buckets are quarter-octave, pow2-scaled: within each octave
    (2^(k-1), 2^k] counts round up to multiples of 2^(k-3), i.e. the
    bucket set is {1..8, 10, 12, 14, 16, 20, 24, 28, 32, 40, ...}. That
    bounds shape variety to O(log) distinct block counts (so compiled
    programs and the persistent .jax_cache are shared across tables whose
    padded sizes land in the same bucket) at <= 25% padding waste — a
    strict pow2 bucket would cost up to 100% extra masked blocks, which
    at gigarow scale is real HBM and host->HBM transfer."""
    if n <= 8:
        return max(n, 1)
    step = 1 << ((n - 1).bit_length() - 3)
    return ((n + step - 1) // step) * step


def block_geometry(
    num_rows: int, d: int, block_rows: int
) -> tuple[int, int]:
    """(per-device block size b, blocks-per-device nblk) for a staging of
    ``num_rows`` over ``d`` devices. With ``signature_buckets`` the
    geometry derives from the pow2-padded row count and nblk rounds up to
    its bucket (padding rows are masked), so tables in the same bucket
    produce identical block shapes — and therefore share one compiled
    program in-process and one .jax_cache entry across processes."""
    if flags.signature_buckets:
        padded = _pow2_at_least(max(num_rows, 1), floor=1)
        b = min(block_rows, _pow2_at_least(max(padded // d, 1), floor=256))
        nblk = bucket_block_count(
            max((num_rows + d * b - 1) // (d * b), 1)
        )
    else:
        b = min(
            block_rows, _pow2_at_least(max(num_rows // d, 1), floor=256)
        )
        nblk = max((num_rows + d * b - 1) // (d * b), 1)
    return b, nblk


def read_columns(
    table: Table,
    columns: list[str],
    start_time: Optional[int] = None,
    stop_time: Optional[int] = None,
) -> tuple[dict[str, np.ndarray], int]:
    """Materialize needed columns via a cursor (host side). String columns
    come back as their int32 code arrays."""
    cols, n, _w, _nw = read_columns_windowed(
        table, columns, start_time, stop_time, want_windows=False
    )
    return cols, n


def read_columns_windowed(
    table: Table,
    columns: list[str],
    start_time: Optional[int] = None,
    stop_time: Optional[int] = None,
    want_windows: bool = True,
):
    """Like read_columns, plus per-row WINDOW ids derived from the
    cursor's end-of-window markers (a batch with eow=True closes the
    current window — the same boundaries the host AggNode emits on,
    exec/agg_node.py consume_next_impl). Returns
    (cols, n, window_ids|None, n_windows)."""
    batches = []
    cur = table.cursor(start_time, stop_time)
    while not cur.done():
        b = cur.next_batch()
        if b is None:
            break
        if b.num_rows or b.eow:
            batches.append(b)
    count_read_batches(sum(1 for b in batches if b.num_rows))
    count_read_visits(cur.segments_visited)
    cols: dict[str, np.ndarray] = {}
    n = sum(b.num_rows for b in batches)
    for name in columns:
        parts = []
        for b in batches:
            if not b.num_rows:
                continue
            c = b.col(name)
            parts.append(c.codes if isinstance(c, DictColumn) else np.asarray(c))
        cols[name] = (
            np.concatenate(parts) if parts
            else np.empty(0, np.int32)
        )
    wids = None
    n_windows = 1
    if want_windows:
        parts = []
        w = 0
        for b in batches:
            if b.num_rows:
                parts.append(np.full(b.num_rows, w, np.int64))
            if b.eow:
                w += 1
        wids = (
            np.concatenate(parts) if parts else np.empty(0, np.int64)
        )
        # Rows after the last eow belong to a final (unclosed) window.
        n_windows = w + 1 if (not batches or not batches[-1].eow) else w
        n_windows = max(n_windows, 1)
    return cols, n, wids, n_windows


def int_dict_encode(
    arr: np.ndarray, max_card: int
) -> Optional[tuple[np.ndarray, np.ndarray]]:
    """(codes, sorted value LUT) when the column has <= max_card distinct
    values, else None. Costs one sample-unique + one searchsorted pass +
    one verify compare over the column — paid once per staging (cached
    with it). Telemetry int columns (status codes, ports, enum-ish ids)
    are routinely tiny-domain."""
    if arr.size == 0 or arr.dtype != np.int64 or max_card < 2:
        return None
    lut = np.unique(arr[: 1 << 16])
    if len(lut) > max_card:
        return None
    codes = np.searchsorted(lut, arr)
    codes = np.minimum(codes, len(lut) - 1)
    ok = lut[codes] == arr
    if not ok.all():
        extra = np.unique(arr[~ok])
        lut = np.unique(np.concatenate([lut, extra]))
        if len(lut) > max_card:
            return None
        codes = np.searchsorted(lut, arr)
    dtype = np.uint8 if len(lut) <= 256 else np.uint16
    return codes.astype(dtype), lut


def _narrow_int(arr: np.ndarray) -> tuple[np.ndarray, Optional[int]]:
    """Frame-of-reference narrowing for int columns: ship (value - min) as
    uint8/uint16 (or int32 for int64 inputs) when the RANGE fits, with the
    offset reconstructed on device (widened back to int64 per block).
    Applies to int64 values AND int32 dictionary codes — low-cardinality
    string columns (services, pods) ship at 1 byte/row, ports/status codes
    at 2. (None offset = as-is.) Host→HBM transfer is the cold-path
    bottleneck, so staged bytes are the metric that matters."""
    if arr.size == 0 or arr.dtype not in (np.int64, np.int32):
        return arr, None
    lo = int(arr.min())
    hi = int(arr.max())
    rng = hi - lo
    if rng <= 0xFF:
        return (arr - lo).astype(np.uint8), lo
    if rng <= 0xFFFF:
        return (arr - lo).astype(np.uint16), lo
    if arr.dtype == np.int64 and rng < (1 << 31):
        return (arr - lo).astype(np.int32), lo
    return arr, None


import functools


@functools.lru_cache(maxsize=64)
def _mask_builder(mesh: Mesh, d: int, nblk: int, b: int):
    """Jitted per (mesh, geometry) — a fresh jit per staging would pay a
    trace+compile each time; num_rows stays a traced argument so one
    compiled kernel serves every row count at this geometry."""
    axis_name = tuple(mesh.axis_names)  # dim0 over every mesh axis
    sharding = NamedSharding(mesh, P(axis_name))

    def make(n):
        idx = jax.lax.broadcasted_iota(jnp.int64, (d, nblk, b), 0) * (
            nblk * b
        ) + jax.lax.broadcasted_iota(jnp.int64, (d, nblk, b), 1) * b + (
            jax.lax.broadcasted_iota(jnp.int64, (d, nblk, b), 2)
        )
        return idx < n

    return jax.jit(make, out_shardings=sharding)


def _build_mask(mesh: Mesh, d: int, nblk: int, b: int, num_rows: int):
    """Validity mask computed ON the mesh (iota < num_rows): at 1 byte/row
    a transferred mask is a material slice of cold-path bytes."""
    return _mask_builder(mesh, d, nblk, b)(num_rows)


def stage_columns(
    mesh: Mesh,
    cols: dict[str, np.ndarray],
    num_rows: int,
    gids: Optional[np.ndarray] = None,
    num_groups: int = 1,
    key_columns: Optional[list] = None,
    dictionaries: Optional[dict] = None,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    f32_cols: Optional[set] = None,
    int_dicts: Optional[dict] = None,
) -> StagedColumns:
    """Pad/reshape host columns into [D, nblk, B] and shard over the mesh.

    ``f32_cols`` names float64 columns consumed only by f32-state sketch
    UDAs (t-digest keeps f32 centroids): staging them as f32 halves their
    transfer with zero end-to-end precision change. ``int_dicts`` maps
    column names already replaced by small-domain codes (see
    int_dict_encode) to their value LUTs."""
    from pixie_tpu.ops import codec as _codec

    axis_name = tuple(mesh.axis_names)  # dim0 over every mesh axis
    d = mesh.devices.size
    b, nblk = block_geometry(num_rows, d, block_rows)
    total = d * nblk * b
    sharding = NamedSharding(mesh, P(axis_name))

    def flat_pad(arr, fill):
        out = np.full(total, fill, dtype=arr.dtype if arr.size else np.int32)
        out[:num_rows] = arr
        return out

    def shape3(arr, fill):
        return flat_pad(arr, fill).reshape(d, nblk, b)

    use_codec = flags.staging_codec
    narrow_offsets: dict[str, int] = {}
    blocks: dict[str, jax.Array] = {}
    for name, a in cols.items():
        with timed("stage_host_pack", span=False):
            if f32_cols and name in f32_cols and a.dtype == np.float64:
                a = a.astype(np.float32)
            else:
                a, off = _narrow_int(a)
                if off is not None:
                    narrow_offsets[name] = off
            flat = flat_pad(a, 0)
        # Staging codec (r13): ship the packed representation encoded
        # when a lightweight encoder pays; a jitted program expands it
        # in HBM, bit-identical to the uncompressed transfer.
        payload = None
        if use_codec and num_rows > 0:
            with timed("stage_encode", span=False):
                cplan = _codec.plan_codec_local(
                    flat, d, nblk, b, num_rows,
                    flags.staging_codec_min_ratio,
                )
                if cplan is not None:
                    try:
                        payload = _codec.encode_window(flat, cplan, num_rows)
                    except _codec.CodecOverflow:
                        payload = None
        COLD_PROFILE["stage_bytes"] = COLD_PROFILE.get(
            "stage_bytes", 0.0
        ) + float(flat.nbytes)
        if payload is not None:
            with timed("stage_transfer", span=False):
                args = _codec.put_payload(mesh, payload)
                COLD_PROFILE["wire_bytes"] = COLD_PROFILE.get(
                    "wire_bytes", 0.0
                ) + float(payload.nbytes)
            with timed("stage_decode", span=False):
                blocks[name] = _codec.decoder(mesh, cplan, nblk, b)(*args)
        else:
            with timed("stage_transfer", span=False):
                # device_put is async on local backends; do NOT block per
                # column — that serializes transfers behind each other and
                # behind the next column's host pack. One sync below, after
                # every put is in flight (the PJRT runtime retains the host
                # buffer until its transfer completes).
                blocks[name] = jax.device_put(
                    flat.reshape(d, nblk, b), sharding
                )
                COLD_PROFILE["wire_bytes"] = COLD_PROFILE.get(
                    "wire_bytes", 0.0
                ) + float(flat.nbytes)
    with timed("stage_transfer", span=False):
        if blocks:
            jax.block_until_ready(list(blocks.values()))
    mask_dev = _build_mask(mesh, d, nblk, b, num_rows)
    return StagedColumns(
        blocks=blocks,
        mask=mask_dev,
        gids=(
            None
            if gids is None
            else stage_gids(mesh, gids, num_rows, num_groups, block_rows)
        ),
        num_rows=num_rows,
        num_devices=d,
        block_rows=b,
        num_groups=num_groups,
        capacity=_pow2_at_least(max(num_groups, 1)),
        key_columns=list(key_columns or []),
        dictionaries=dict(dictionaries or {}),
        narrow_offsets=narrow_offsets,
        int_dicts=dict(int_dicts or {}),
    )


def stage_gids(
    mesh: Mesh,
    gids: np.ndarray,
    num_rows: int,
    num_groups: int,
    block_rows: int = DEFAULT_BLOCK_ROWS,
) -> jax.Array:
    """Dense gids as [D, nblk, B] device blocks, at the geometry that
    stage_columns gives ``num_rows`` rows."""
    from pixie_tpu.ops import codec as _codec

    d = mesh.devices.size
    b, nblk = block_geometry(num_rows, d, block_rows)
    narrow = _narrow_gids(gids, num_groups)
    gflat = np.zeros(d * nblk * b, narrow.dtype if narrow.size else np.int32)
    gflat[:num_rows] = narrow
    gpayload = None
    if flags.staging_codec and num_rows > 0:
        # r16: the gids lane rides the codec like any value column —
        # sorted/low-churn group keys RLE to ~nothing.
        with timed("stage_encode", span=False):
            gplan = _codec.plan_codec_local(
                gflat, d, nblk, b, num_rows,
                flags.staging_codec_min_ratio,
            )
            if gplan is not None:
                try:
                    gpayload = _codec.encode_window(gflat, gplan, num_rows)
                except _codec.CodecOverflow:
                    gpayload = None
    if gpayload is not None:
        with timed("stage_transfer", span=False):
            gargs = _codec.put_payload(mesh, gpayload)
            COLD_PROFILE["wire_bytes"] = COLD_PROFILE.get(
                "wire_bytes", 0.0
            ) + float(gpayload.nbytes)
        with timed("stage_decode", span=False):
            return _codec.decoder(mesh, gplan, nblk, b)(*gargs)
    sharding = NamedSharding(mesh, P(tuple(mesh.axis_names)))
    return jax.device_put(gflat.reshape(d, nblk, b), sharding)


def repartition_staged(mesh: Mesh, staged: StagedColumns) -> StagedColumns:
    """Re-place one staged table onto ``mesh`` (r23 geometry recovery).

    Every rung of the degradation ladder keeps the total device count
    (losing a host is a trust statement about the ``hosts`` axis, not a
    removal of local silicon), so the [D, nblk, B] shapes are unchanged
    and the move is a pure ``device_put`` resolved through the SAME
    partition-rule tree that placed the shards originally: blocks, mask,
    and gids shard dim 0 over the new axis tuple; values bit-identical.
    Host-side key bookkeeping carries over untouched."""
    from pixie_tpu.distributed import mesh as mesh_lib

    names = [f"blocks/{n}" for n in staged.blocks] + ["mask"]
    if staged.gids is not None or staged.branch_gids:
        names.append("gids")
    sh = mesh_lib.match_partition_rules(
        mesh_lib.STAGED_PARTITION_RULES, names, mesh
    )
    return dataclasses.replace(
        staged,
        blocks={
            n: jax.device_put(a, sh[f"blocks/{n}"])
            for n, a in staged.blocks.items()
        },
        mask=jax.device_put(staged.mask, sh["mask"]),
        gids=(
            jax.device_put(staged.gids, sh["gids"])
            if staged.gids is not None
            else None
        ),
        branch_gids={
            k: jax.device_put(g, sh["gids"])
            for k, g in staged.branch_gids.items()
        },
    )


def _narrow_gids(gids: np.ndarray, num_groups: int) -> np.ndarray:
    """Dense gids ship u8/u16 when the group count fits (the compiled
    programs cast to int32 per block anyway)."""
    if num_groups <= 0xFF + 1:
        return gids.astype(np.uint8)
    if num_groups <= 0xFFFF + 1:
        return gids.astype(np.uint16)
    return gids.astype(np.int32)


@functools.lru_cache(maxsize=64)
def _shard_mask_builder(mesh: Mesh, d: int, nblk: int, b: int, region: int):
    """Per-shard validity mask for partitioned stagings: each hosts-axis
    shard owns a contiguous ``region`` of the flat row space, valid up
    to its own row count (tail-padding WITHIN each region, unlike the
    single global tail _mask_builder models). Jitted per geometry; the
    [H] counts vector stays a traced argument."""
    axis_name = tuple(mesh.axis_names)  # dim0 over every mesh axis
    sharding = NamedSharding(mesh, P(axis_name))

    def make(counts):
        idx = jax.lax.broadcasted_iota(jnp.int64, (d, nblk, b), 0) * (
            nblk * b
        ) + jax.lax.broadcasted_iota(jnp.int64, (d, nblk, b), 1) * b + (
            jax.lax.broadcasted_iota(jnp.int64, (d, nblk, b), 2)
        )
        return (idx % region) < counts[idx // region]

    return jax.jit(make, out_shardings=sharding)


def stage_partitioned(
    mesh: Mesh,
    cols: dict[str, np.ndarray],
    gids: np.ndarray,
    shard_rows: np.ndarray,
    num_groups: int,
    block_rows: int = DEFAULT_BLOCK_ROWS,
) -> StagedColumns:
    """Stage shard-major host columns so each hosts-axis shard owns a
    contiguous region of devices (the r21 distributed join's layout).

    ``cols``/``gids`` arrive ALREADY permuted shard-major (rows of
    shard h contiguous, original order preserved within a shard) with
    ``shard_rows[h]`` rows per shard. Geometry is per-host: every host
    gets the block_geometry of the LARGEST shard over its ``d/H``
    devices, so regions are uniform (one compiled program) and ragged
    shards tail-pad within their own region — the per-shard mask comes
    from _shard_mask_builder, not the global-tail mask. Narrowing
    matches stage_columns (one frame-of-reference offset per column
    over the whole permuted array); the staging codec is not applied
    on this path (shard regions break the contiguous-rows assumption
    of the window codec plans — revisit if transfer dominates)."""
    axis_name = tuple(mesh.axis_names)  # dim0 over every mesh axis
    H = int(mesh.devices.shape[0])
    d = mesh.devices.size
    d_host = d // H
    shard_rows = np.asarray(shard_rows, np.int64)
    assert shard_rows.shape == (H,) and int(shard_rows.sum()) == len(gids)
    b, nblk = block_geometry(int(max(shard_rows.max(), 1)), d_host, block_rows)
    region = d_host * nblk * b
    total = d * nblk * b
    offs = np.concatenate([[0], np.cumsum(shard_rows)[:-1]])
    sharding = NamedSharding(mesh, P(axis_name))

    def scatter(arr, fill):
        out = np.full(total, fill, dtype=arr.dtype if arr.size else np.int32)
        for h in range(H):
            r = int(shard_rows[h])
            out[h * region : h * region + r] = arr[offs[h] : offs[h] + r]
        return out

    narrow_offsets: dict[str, int] = {}
    blocks: dict[str, jax.Array] = {}
    for name, a in cols.items():
        with timed("stage_host_pack", span=False):
            a, off = _narrow_int(np.asarray(a))
            if off is not None:
                narrow_offsets[name] = off
            flat = scatter(a, 0)
        COLD_PROFILE["stage_bytes"] = COLD_PROFILE.get(
            "stage_bytes", 0.0
        ) + float(flat.nbytes)
        with timed("stage_transfer", span=False):
            blocks[name] = jax.device_put(flat.reshape(d, nblk, b), sharding)
            COLD_PROFILE["wire_bytes"] = COLD_PROFILE.get(
                "wire_bytes", 0.0
            ) + float(flat.nbytes)
    gflat = scatter(_narrow_gids(np.asarray(gids), num_groups), 0)
    gids_dev = jax.device_put(gflat.reshape(d, nblk, b), sharding)
    with timed("stage_transfer", span=False):
        jax.block_until_ready(list(blocks.values()) + [gids_dev])
    mask_dev = _shard_mask_builder(mesh, d, nblk, b, region)(
        jnp.asarray(shard_rows)
    )
    return StagedColumns(
        blocks=blocks,
        mask=mask_dev,
        gids=gids_dev,
        num_rows=int(shard_rows.sum()),
        num_devices=d,
        block_rows=b,
        num_groups=num_groups,
        capacity=_pow2_at_least(max(num_groups, 1)),
        key_columns=[],
        dictionaries={},
        narrow_offsets=narrow_offsets,
        int_dicts={},
    )


# -- streaming, double-buffered staging (the r6 cold-path pipeline) ----------
#
# The monolithic path above materializes the WHOLE table in HBM before the
# first FLOP; at bench scale the cold query is therefore ≈ pack + transfer +
# compute in sequence. The streaming path splits the table into fixed-size
# row windows and runs a three-stage software pipeline: window k+2 is
# host-packed on a background thread, window k+1 is in flight via async
# jax.device_put, and window k is being folded on the mesh — end-to-end
# time becomes ≈ max(pack, transfer, compute) + one window of fill/drain.
# Every window shares one pack recipe (dtypes/offsets/LUTs fixed from the
# FULL columns) so a single compiled fold program serves them all.


@dataclasses.dataclass
class StreamPlan:
    """Per-column pack recipe + window geometry, fixed across windows.

    col_plans[name] is one of ("raw", None), ("f32", None),
    ("narrow", (np_dtype, offset)), ("intdict", (lut, np_dtype)). The
    recipe is derived from the FULL host columns once, so every window's
    blocks share dtypes and shapes — required for one compiled fold
    program to serve all windows, and for the post-stream concatenation
    to be a valid monolithic staging."""

    col_plans: dict
    narrow_offsets: dict  # name -> int offset (frame-of-reference)
    int_dicts: dict  # name -> [C] int64 value LUT
    block_dtypes: dict  # name -> np.dtype of the staged blocks
    window_rows: int
    num_rows: int
    n_windows: int
    d: int
    nblk: int  # blocks per window per device
    b: int
    gid_dtype: Optional[np.dtype]
    num_groups: int
    # Staging codec (r13): name -> ops.codec.CodecPlan for columns whose
    # wire bytes an encoder beats by >= staging_codec_min_ratio. Fixed
    # from the FULL column like every other recipe entry, so all windows
    # share one decode program. Columns absent here ship passthrough.
    codecs: dict = dataclasses.field(default_factory=dict)
    # r16: the GIDS stream rides the codec too — rows grouped by sorted
    # or low-churn keys yield long gid runs that RLE to ~nothing, and
    # the gids lane is a full extra column of wire bytes on every
    # host-gids staging. None = passthrough (random-ish gids).
    gid_codec: Optional[object] = None

    def window_block_nbytes(self) -> int:
        """Decoded (HBM) bytes per full window: column blocks only —
        what stage_bytes accounts per window (gids ride separately)."""
        return sum(
            self.d * self.nblk * self.b * np.dtype(dt).itemsize
            for dt in self.block_dtypes.values()
        )


def int_dict_lut(arr: np.ndarray, max_card: int) -> Optional[np.ndarray]:
    """LUT-only variant of int_dict_encode: the sorted value LUT when the
    column's FULL value set fits max_card, else None. Verified over the
    whole column, so per-window searchsorted encodes against it are exact
    (the per-window encode is what rides the background pack thread)."""
    enc = int_dict_encode(arr, max_card)
    return None if enc is None else enc[1]


def _narrow_int_plan(arr: np.ndarray) -> tuple[np.dtype, Optional[int]]:
    """_narrow_int's decision without the conversion: (dtype, offset) —
    offset None means ship as-is. Computed once over the full column so
    every window narrows identically (stable block dtypes)."""
    if arr.size == 0 or arr.dtype not in (np.int64, np.int32):
        return arr.dtype, None
    lo = int(arr.min())
    rng = int(arr.max()) - lo
    if rng <= 0xFF:
        return np.dtype(np.uint8), lo
    if rng <= 0xFFFF:
        return np.dtype(np.uint16), lo
    if arr.dtype == np.int64 and rng < (1 << 31):
        return np.dtype(np.int32), lo
    return arr.dtype, None


def plan_stream(
    mesh: Mesh,
    cols: dict[str, np.ndarray],
    num_rows: int,
    window_rows: int,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    f32_cols: Optional[set] = None,
    cell_cols: Optional[dict] = None,
    num_groups: int = 1,
    has_gids: bool = False,
    gids: Optional[np.ndarray] = None,
) -> StreamPlan:
    """Fix the pack recipe + window geometry for a streamed staging.

    window_rows is clamped to the table so a small table (or a huge
    window flag) degenerates to ONE window whose geometry matches what
    stage_columns would have chosen — the fold then reproduces the
    monolithic scan bit-for-bit. With ``signature_buckets`` the clamp is
    to the POW2-PADDED row count, so every small table whose padded size
    lands in the same bucket shares one window geometry — and one
    compiled fold executable."""
    d = mesh.devices.size
    clamp = max(num_rows, 1)
    if flags.signature_buckets:
        clamp = _pow2_at_least(clamp, floor=1)
    window_rows = max(min(int(window_rows), clamp), 1)
    n_windows = max((num_rows + window_rows - 1) // window_rows, 1)
    b, nblk = block_geometry(window_rows, d, block_rows)
    col_plans: dict = {}
    narrow_offsets: dict = {}
    int_dicts: dict = {}
    block_dtypes: dict = {}
    for name, a in cols.items():
        if cell_cols and name in cell_cols:
            lut = int_dict_lut(a, cell_cols[name])
            if lut is not None:
                dt = np.dtype(np.uint8 if len(lut) <= 256 else np.uint16)
                col_plans[name] = ("intdict", (lut, dt))
                int_dicts[name] = lut
                block_dtypes[name] = dt
                continue
        if f32_cols and name in f32_cols and a.dtype == np.float64:
            col_plans[name] = ("f32", None)
            block_dtypes[name] = np.dtype(np.float32)
            continue
        dt, off = _narrow_int_plan(a)
        if off is not None:
            col_plans[name] = ("narrow", (dt, off))
            narrow_offsets[name] = off
            block_dtypes[name] = dt
        else:
            col_plans[name] = ("raw", None)
            block_dtypes[name] = (
                np.dtype(a.dtype) if a.size else np.dtype(np.int32)
            )
    gid_dtype = None
    if has_gids:
        gid_dtype = np.dtype(
            np.uint8
            if num_groups <= 0xFF + 1
            else (np.uint16 if num_groups <= 0xFFFF + 1 else np.int32)
        )
    # Staging codec (r13): pick a per-column encoder from the FULL
    # column's stats so every window encodes identically (one decode
    # program serves all windows, and the decoded blocks are exactly
    # what the passthrough pack would have transferred). Delta needs a
    # diff-preserving (raw/narrow int) transform; RLE composes with
    # anything because run boundaries are invariant under the pack
    # transforms (bit-pattern changes map 1:1).
    codecs: dict = {}
    gid_codec = None
    if flags.staging_codec:
        from pixie_tpu.ops import codec as _codec

        for name, a in cols.items():
            kind = col_plans[name][0]
            bdt = np.dtype(block_dtypes[name])
            affine = kind in ("raw", "narrow") and bdt.kind in "iu"
            cp = _codec.plan_codec(
                a, bdt, d, nblk, b, window_rows, num_rows,
                flags.staging_codec_min_ratio, affine,
            )
            if cp is not None:
                codecs[name] = cp
        if gids is not None and gid_dtype is not None and gids.size:
            # r16: the gids lane is an extra full-width column on every
            # host-gids staging; sorted/low-churn group keys make it
            # run-heavy, so plan it like any value column. The narrow
            # cast (astype, values unchanged) preserves both run
            # boundaries and diffs, so stats on the raw gids are exact.
            gid_codec = _codec.plan_codec(
                gids, gid_dtype, d, nblk, b, window_rows, num_rows,
                flags.staging_codec_min_ratio, affine=True,
            )
    return StreamPlan(
        col_plans=col_plans,
        narrow_offsets=narrow_offsets,
        int_dicts=int_dicts,
        block_dtypes=block_dtypes,
        window_rows=window_rows,
        num_rows=num_rows,
        n_windows=n_windows,
        d=d,
        nblk=nblk,
        b=b,
        gid_dtype=gid_dtype,
        num_groups=num_groups,
        codecs=codecs,
        gid_codec=gid_codec,
    )


def pack_stream_window(
    plan: StreamPlan,
    cols: dict[str, np.ndarray],
    gids: Optional[np.ndarray],
    w: int,
    skip_cols: bool = False,
):
    """Host-pack window w per the plan: narrow/f32/int-dict encode + pad +
    reshape to [D, nblk, B]. Runs on the streaming pipeline's background
    thread — this is the 'pack' stage that overlaps transfer and compute.
    Returns (rows, packed_cols, packed_gids, wire_nbytes): with the
    staging codec on, a packed_cols value may be a CodecPayload (the
    compressed representation the wire actually carries — the device
    decode expands it to the identical block), and wire_nbytes counts
    what ships, not what lands. ``skip_cols`` packs only the gids — the
    resident-ingest path, where the window's columns are already in
    HBM and only the query-specific group ids must travel."""
    from pixie_tpu.ops import codec as _codec

    # Fault site: a poisoned stream pack (chaos tests prove the query
    # falls back to monolithic staging, still on-device, and stays
    # correct — MeshExecutor.stream_fallback_errors records it).
    if faults.ACTIVE:
        faults.check("staging.pack")
    with timed("stage_stream_pack", span=False):
        lo = w * plan.window_rows
        hi = min(lo + plan.window_rows, plan.num_rows)
        rows = hi - lo
        total = plan.d * plan.nblk * plan.b

        def flat_pad(a, dtype):
            # np.empty + tail-zero, not np.zeros: the rows prefix is about
            # to be overwritten anyway, and this pack is on the pipeline's
            # critical path when pack is the slowest stage.
            out = np.empty(total, dtype=dtype)
            out[:rows] = a
            if rows < total:
                out[rows:] = 0
            return out

        def shape3(a, dtype):
            return flat_pad(a, dtype).reshape(plan.d, plan.nblk, plan.b)

        packed: dict = {}
        nbytes = 0
        for name, arr in ({} if skip_cols else cols).items():
            a = arr[lo:hi]
            kind, info = plan.col_plans[name]
            if kind == "f32":
                a = a.astype(np.float32)
            elif kind == "narrow":
                dt, off = info
                a = (a - off).astype(dt)
            elif kind == "intdict":
                lut, dt = info
                c = np.searchsorted(lut, a)
                a = np.minimum(c, len(lut) - 1).astype(dt)
            cp = plan.codecs.get(name)
            if cp is not None:
                flat = flat_pad(a, plan.block_dtypes[name])
                try:
                    with timed("stage_encode", span=False):
                        packed[name] = _codec.encode_window(flat, cp, rows)
                    nbytes += packed[name].nbytes
                    continue
                except _codec.CodecOverflow:
                    # A window that defeats the plan ships raw —
                    # correctness never rides the plan's guess.
                    packed[name] = flat.reshape(
                        plan.d, plan.nblk, plan.b
                    )
                    nbytes += packed[name].nbytes
                    continue
            packed[name] = shape3(a, plan.block_dtypes[name])
            nbytes += packed[name].nbytes
        packed_gids = None
        if gids is not None:
            if plan.gid_codec is not None:
                flat = flat_pad(
                    gids[lo:hi].astype(plan.gid_dtype), plan.gid_dtype
                )
                try:
                    with timed("stage_encode", span=False):
                        packed_gids = _codec.encode_window(
                            flat, plan.gid_codec, rows
                        )
                except _codec.CodecOverflow:
                    packed_gids = flat.reshape(
                        plan.d, plan.nblk, plan.b
                    )
            else:
                packed_gids = shape3(
                    gids[lo:hi].astype(plan.gid_dtype), plan.gid_dtype
                )
            nbytes += packed_gids.nbytes
        return rows, packed, packed_gids, nbytes


def put_window_gids(mesh: Mesh, pgids, nblk: int, b: int):
    """Land one window's packed gids on the mesh: a raw [D, nblk, B]
    ndarray device_puts as before; a CodecPayload (r16 gid codec)
    transfers the compressed representation and expands on device —
    bit-identical to the raw put."""
    from pixie_tpu.ops import codec as _codec

    if pgids is None:
        return None
    axis_name = tuple(mesh.axis_names)  # dim0 over every mesh axis
    if isinstance(pgids, _codec.CodecPayload):
        args = _codec.put_payload(mesh, pgids)
        return _codec.decoder(mesh, pgids.plan, nblk, b)(*args)
    return jax.device_put(pgids, NamedSharding(mesh, P(axis_name)))


def staged_gid_nbytes(pgids) -> int:
    """Decoded (HBM) bytes a packed-gids value lands as — the
    stage_bytes accounting view; .nbytes on a CodecPayload is WIRE
    bytes."""
    from pixie_tpu.ops import codec as _codec

    if pgids is None:
        return 0
    if isinstance(pgids, _codec.CodecPayload):
        return pgids.plan.block_nbytes()
    return int(pgids.nbytes)


@functools.lru_cache(maxsize=16)
def _concat_builder(mesh: Mesh, n_parts: int):
    """Jitted device-side concatenation along the block axis, sharding
    preserved (device-local copies; no collective). Used to assemble the
    streamed windows into one monolithic StagedColumns for the warm-path
    HBM cache."""
    axis_name = tuple(mesh.axis_names)  # dim0 over every mesh axis
    sharding = NamedSharding(mesh, P(axis_name))
    return jax.jit(
        lambda *xs: jnp.concatenate(xs, axis=1), out_shardings=sharding
    )


@functools.lru_cache(maxsize=64)
def _zeros_builder(mesh: Mesh, d: int, nblk: int, b: int, dtype_str: str):
    """Device-allocated zero blocks (sharded, NO host transfer): the
    bucket padding appended to a concatenated stream staging. Padding
    blocks are fully masked, so the warm program scans them as no-ops."""
    axis_name = tuple(mesh.axis_names)  # dim0 over every mesh axis
    sharding = NamedSharding(mesh, P(axis_name))
    return jax.jit(
        lambda: jnp.zeros((d, nblk, b), np.dtype(dtype_str)),
        out_shardings=sharding,
    )


def concat_stream_windows(
    mesh: Mesh,
    plan: StreamPlan,
    win_blocks: list,
    win_masks: list,
    win_gids: list,
    key_plan_num_groups: int,
    key_columns: list,
    dictionaries: dict,
) -> StagedColumns:
    """Assemble per-window device blocks into one StagedColumns so warm
    queries hit HBM directly (same contract as stage_columns; the row
    layout is per-window-packed, which the per-window masks encode).
    With ``signature_buckets`` the concatenated block count is padded up
    to its bucket with device-allocated zero blocks (masked, never
    transferred) so the warm program's shapes — and its compiled
    executable + .jax_cache entry — are shared across tables whose
    window counts land in the same bucket."""
    n_windows = len(win_masks)
    total_nblk = n_windows * plan.nblk
    pad_nblk = 0
    if flags.signature_buckets:
        pad_nblk = bucket_block_count(total_nblk) - total_nblk
    if n_windows == 1 and pad_nblk == 0:
        blocks = dict(win_blocks[0])
        mask = win_masks[0]
        gids = win_gids[0]
    else:
        n_parts = n_windows + (1 if pad_nblk else 0)
        cat = _concat_builder(mesh, n_parts)

        def pad(dtype):
            return _zeros_builder(
                mesh, plan.d, pad_nblk, plan.b, np.dtype(dtype).str
            )()

        def cat_padded(parts, dtype):
            if pad_nblk:
                parts = list(parts) + [pad(dtype)]
            return parts[0] if len(parts) == 1 else cat(*parts)

        blocks = {
            name: cat_padded(
                [wb[name] for wb in win_blocks], plan.block_dtypes[name]
            )
            for name in win_blocks[0]
        }
        mask = cat_padded(list(win_masks), np.bool_)
        gids = (
            cat_padded(list(win_gids), plan.gid_dtype)
            if win_gids and win_gids[0] is not None
            else None
        )
    return StagedColumns(
        blocks=blocks,
        mask=mask,
        gids=gids,
        num_rows=plan.num_rows,
        num_devices=plan.d,
        block_rows=plan.b,
        num_groups=max(key_plan_num_groups, 1),
        capacity=_pow2_at_least(max(key_plan_num_groups, 1)),
        key_columns=list(key_columns or []),
        dictionaries=dict(dictionaries or {}),
        narrow_offsets=dict(plan.narrow_offsets),
        int_dicts=dict(plan.int_dicts),
    )
