"""Device-resident incremental ingest: HBM ring tables fed by appends.

The r13 production posture: telemetry is continuous and queries are
repeated, so a hot table should NEVER cold-stage its recent span — the
ingest loop pays the wire incrementally (compressed, off any query's
critical path) and a query finds the last N windows already in HBM,
staging only the cold tail. Crescando/SharedDB's continuously-resident
operational data, on a TPU.

Mechanics (reusing the r6 windowed layout end to end):

- A ``ResidentRing`` attaches to a Table's append listener. Appends
  buffer host-side until a full **ring window** (``resident_window_rows``
  rows, geometry from ``staging.block_geometry`` — exactly the stream
  plan's) is available, which is then packed in RAW column dtypes,
  codec-encoded (``staging_codec``), transferred, and device-decoded
  into [D, nblk, B] blocks that stay resident.
- Queries over the table stream at the ring's window size, so plan
  window w covers the same absolute rows as ring window
  ``(min_row + w·W) / W``. On a hit the pipeline skips pack+transfer
  entirely and runs a jitted raw→plan CONVERT (ops/codec.py:
  narrow/f32/int-dict computed on device) — bit-identical to the host
  pack, zero wire bytes. Misses (partial tail, pre-ring history,
  post-expiry misalignment) take the normal compressed staging path.
- Ring windows are registered with the ResidencyPool as permanently
  pinned bytes (``register_resident``), so /statusz, the byte
  watermark, and admission headroom all see them; the ring's own depth
  bound (``resident_max_windows``) rolls the oldest window out and
  frees its accounting — the device-side analogue of the table store's
  ring-buffer expiry.

Correctness stance: the ring only ever serves FULL windows whose rows it
observed gap-free in row-id order (a skipped row id — e.g. a listener
attached mid-write race — permanently invalidates the ring, never the
query). Everything else falls back to staging from the host columns.
"""

from __future__ import annotations

import threading
from typing import Optional

import numpy as np

from pixie_tpu.types import DataType
from pixie_tpu.utils import flags, metrics_registry


def _log_serving():
    import logging

    return logging.getLogger("pixie_tpu.serving")

_M = metrics_registry()
_WINDOWS = _M.counter(
    "resident_ingest_windows_total",
    "Ring windows staged to HBM by the resident-ingest path.",
)
_WIRE = _M.counter(
    "resident_ingest_wire_bytes_total",
    "Bytes the resident-ingest path actually transferred (encoded).",
)
_HITS = _M.counter(
    "resident_window_hits_total",
    "Query stream windows served from HBM-resident ring windows "
    "(pack+transfer skipped).",
)
_INVALID = _M.counter(
    "resident_ring_invalidated_total",
    "Rings permanently invalidated (row-id gap or column mismatch).",
)
_REPLICATED = _M.counter(
    "ring_replicated_windows_total",
    "Ring windows shipped to follower agents over the codec'd wire "
    "(r17, flag ring_replication_factor > 1), by table.",
)
_REPLICA_ADOPTED = _M.counter(
    "ring_replica_adopted_windows_total",
    "Replica windows decoded into a follower's HBM, by table.",
)
_REPLICA_HITS = _M.counter(
    "replica_window_hits_total",
    "Query stream windows served from a REPLICA ring after failover "
    "(pack+transfer skipped on an agent that never owned the table).",
)
_REPLICA_LAGGED = _M.counter(
    "ring_replica_lagged_windows_total",
    "Replica windows NOT adopted (decode failure, geometry mismatch, "
    "or the resident.replica_lag fault site) — the replica falls "
    "behind the leader's watermark and failover queries re-stage those "
    "rows from the table store instead.",
)
_RESTAGED = _M.counter(
    "ring_restaged_windows_total",
    "Ring windows re-staged into HBM from the durable spill after a "
    "restart (r14, flag durable_resident) — recovered without replaying "
    "table appends.",
)
_SPILL_BYTES = _M.gauge(
    "resident_spill_bytes",
    "On-disk bytes of resident-ring spill logs, by table.",
)

# Raw host dtypes the ring can hold, per column DataType (strings ride
# as their table-dictionary int32 codes, matching read_columns).
_RAW_DTYPES = {
    DataType.BOOLEAN: np.dtype(np.bool_),
    DataType.INT64: np.dtype(np.int64),
    DataType.FLOAT64: np.dtype(np.float64),
    DataType.STRING: np.dtype(np.int32),
    DataType.TIME64NS: np.dtype(np.int64),
}


class ResidentWindow:
    __slots__ = ("index", "start_row", "rows", "blocks", "nbytes")

    def __init__(self, index, start_row, rows, blocks, nbytes):
        self.index = index
        self.start_row = start_row
        self.rows = rows
        self.blocks = blocks  # col -> [D, nblk, B] raw-dtype device array
        self.nbytes = nbytes


class ResidentRing:
    """Per-table HBM ring of full append windows in raw column dtypes."""

    def __init__(self, mesh, table, block_rows: int, pool=None):
        from pixie_tpu.parallel.staging import block_geometry

        self.mesh = mesh
        self.table_name = table.name
        # Replication hook (r17, flag ring_replication_factor > 1): set
        # by the owning agent's replicator; called as hook(table_name,
        # k, start_row, rows, wire_cols, latest_k) with the EXACT
        # encoded payloads the leader's own decode consumed — the wire
        # representation is shared, not recomputed. Called under the
        # ring lock: the hook must only enqueue, never block.
        self.replication_hook = None
        self.window_rows = int(flags.resident_window_rows)
        self.d = mesh.devices.size
        self.b, self.nblk = block_geometry(
            self.window_rows, self.d, block_rows
        )
        self._pool = pool
        self._lock = threading.Lock()
        self.columns: dict[str, np.dtype] = {}
        for c in table.relation:
            dt = _RAW_DTYPES.get(c.data_type)
            if dt is not None:
                self.columns[c.name] = dt
        self.windows: dict[int, ResidentWindow] = {}
        self._valid = bool(self.columns)
        # Buffered host rows cover [_buf_start, _next_row).
        self._next_row = table.end_row_id()
        self._buf_start = self._next_row
        self._buf: dict[str, list] = {n: [] for n in self.columns}
        # Durable spill (r14, flags durable_resident + wal_dir): full
        # windows + the partial buffer mirror to a per-table segment
        # log, and a fresh ring over a recovered table re-stages its
        # windows into HBM from disk (no append replay).
        self._spill = None
        self.recovered_windows = 0
        self.spill_corrupt_records = 0
        if self._valid and flags.durable_resident and flags.wal_dir:
            from pixie_tpu.vizier.durability import RingSpill, ring_spill_path

            try:
                self._spill = RingSpill(
                    ring_spill_path(flags.wal_dir, self.table_name)
                )
                with self._lock:
                    self._recover_from_spill_locked(table)
            except Exception:
                import logging

                logging.getLogger("pixie_tpu.serving").exception(
                    "ring spill unavailable for %r (running without "
                    "durability)", self.table_name,
                )
                self._spill = None

    # -- write side (table append listener) ----------------------------------
    def on_append(self, first_row_id: int, batch) -> None:
        from pixie_tpu.table.column import DictColumn

        with self._lock:
            if not self._valid:
                return
            if first_row_id != self._next_row:
                self._invalidate_locked()
                return
            if batch.num_rows == 0:
                return
            chunk = {}
            for name, dt in self.columns.items():
                c = batch.col(name)
                arr = c.codes if isinstance(c, DictColumn) else np.asarray(c)
                if arr.dtype != dt:
                    # A batch whose host dtype diverges from what
                    # read_columns would return must never be served.
                    self._invalidate_locked()
                    return
                chunk[name] = arr
            for name, arr in chunk.items():
                self._buf[name].append(arr)
            self._next_row += batch.num_rows
            if self._spill is not None:
                # Mirror the partial buffer incrementally: a restart
                # recovers buffered-but-unstaged rows too, not only
                # full windows.
                self._spill.record_append(first_row_id, chunk)
            self._stage_complete_windows_locked()

    def _invalidate_locked(self) -> None:
        self._valid = False
        _INVALID.inc()
        for w in list(self.windows):
            self._release_locked(w)
        self._buf = {n: [] for n in self.columns}
        if self._spill is not None:
            self._spill.record_reset()

    def _stage_complete_windows_locked(self) -> None:
        W = self.window_rows
        while True:
            k = -(-self._buf_start // W)  # first window at/after buffer
            if (k + 1) * W > self._next_row:
                return
            # Compact the buffer to single chunks once per staging.
            for name in self.columns:
                if len(self._buf[name]) > 1:
                    self._buf[name] = [np.concatenate(self._buf[name])]
            lo = k * W - self._buf_start
            win_cols = {
                name: self._buf[name][0][lo : lo + W]
                for name in self.columns
            }
            self._stage_window_locked(k, win_cols)
            # Drop everything through the staged window.
            keep_from = (k + 1) * W - self._buf_start
            for name in self.columns:
                self._buf[name] = [self._buf[name][0][keep_from:]]
            self._buf_start = (k + 1) * W
            if self._spill is not None:
                self._spill.record_trim(self._buf_start)
                self._spill.maybe_compact(
                    set(self.windows), self._buf_start
                )
                _SPILL_BYTES.labels(table=self.table_name).set(
                    self._spill.nbytes()
                )

    def _stage_window_locked(
        self, k: int, win_cols: dict, record: bool = True
    ) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from pixie_tpu.ops import codec as _codec

        if record and self._spill is not None:
            # WAL posture: the window's raw host columns hit disk before
            # the HBM transfer, so a crash at any later point recovers it.
            self._spill.record_window(
                k, k * self.window_rows, self.window_rows, win_cols
            )

        axis_name = tuple(self.mesh.axis_names)  # dim0 over every mesh axis
        sharding = NamedSharding(self.mesh, P(axis_name))
        total = self.d * self.nblk * self.b
        W = self.window_rows
        use_codec = flags.staging_codec
        min_ratio = flags.staging_codec_min_ratio
        blocks = {}
        nbytes = 0
        wire = 0
        wire_cols = {} if self.replication_hook is not None else None
        for name, a in win_cols.items():
            flat = np.zeros(total, dtype=a.dtype)
            flat[:W] = a
            payload = None
            if use_codec:
                cp = _codec.plan_codec_local(
                    flat, self.d, self.nblk, self.b, W, min_ratio
                )
                if cp is not None:
                    try:
                        payload = _codec.encode_window(flat, cp, W)
                    except _codec.CodecOverflow:
                        payload = None
            if payload is not None:
                args = _codec.put_payload(self.mesh, payload)
                blocks[name] = _codec.decoder(
                    self.mesh, cp, self.nblk, self.b
                )(*args)
                wire += payload.nbytes
                if wire_cols is not None:
                    wire_cols[name] = ("codec", payload)
            else:
                blocks[name] = jax.device_put(
                    flat.reshape(self.d, self.nblk, self.b), sharding
                )
                wire += flat.nbytes
                if wire_cols is not None:
                    wire_cols[name] = ("raw", flat)
            nbytes += flat.nbytes
        win = ResidentWindow(k, k * W, W, blocks, nbytes)
        self.windows[k] = win
        _WINDOWS.inc()
        _WIRE.inc(wire)
        if wire_cols is not None and record:
            # Ship the SAME encoded payloads to followers (r17): the
            # replica pays the compressed wire, never a re-encode.
            try:
                self.replication_hook(
                    self.table_name, k, k * W, W, wire_cols, k
                )
                _REPLICATED.inc(table=self.table_name)
            except Exception:
                _log_serving().exception(
                    "ring replication hook failed (ignored)"
                )
        if self._pool is not None:
            self._pool.register_resident(
                ("resident", self.table_name, k), nbytes
            )
        # Ring depth bound: roll the oldest window out.
        cap = max(int(flags.resident_max_windows), 1)
        while len(self.windows) > cap:
            self._release_locked(min(self.windows))

    def _release_locked(self, k: int) -> None:
        self.windows.pop(k, None)
        if self._pool is not None:
            self._pool.release_resident(("resident", self.table_name, k))
        if self._spill is not None:
            self._spill.record_release(k)

    def _recover_from_spill_locked(self, table) -> None:
        """Restart recovery: re-stage full windows into HBM from the
        spill and restore the partial buffer — without replaying table
        appends. Everything is validated against the recovered table
        (row ranges, column set, dtypes); anything questionable is
        dropped, never served (queries fall back to the staging path,
        bit-identical either way)."""
        state = self._spill.recover()
        self.spill_corrupt_records = state["corrupt"]
        table_end = table.end_row_id()
        W = self.window_rows
        restaged = 0
        for k in sorted(state["windows"]):
            start_row, rows, cols = state["windows"][k]
            if rows != W or start_row != k * W or start_row + rows > table_end:
                continue  # geometry drift, or rows the table lost
            if set(cols) != set(self.columns) or any(
                np.asarray(cols[n]).dtype != dt or len(cols[n]) != W
                for n, dt in self.columns.items()
            ):
                continue
            self._stage_window_locked(
                k,
                {n: np.asarray(cols[n]) for n in self.columns},
                record=False,  # already on disk
            )
            restaged += 1
        self.recovered_windows = restaged
        if restaged:
            _RESTAGED.inc(restaged)
        # Partial buffer: usable only when the recorded chunks are
        # gap-free and reach EXACTLY the table's end (the ring's
        # observed-every-row contract, re-established across restart).
        chunks = state["buf"]
        bs = state["buf_start"]
        cov_start = chunks[0][0] if chunks else None
        cov_end = cov_start
        ok = bool(chunks)
        for first_row, cols in chunks:
            rows = len(next(iter(cols.values()))) if cols else 0
            if first_row != cov_end or set(cols) != set(self.columns) or any(
                np.asarray(cols[n]).dtype != dt
                for n, dt in self.columns.items()
            ):
                ok = False
                break
            cov_end = first_row + rows
        if ok and cov_end == table_end:
            if bs is None:
                bs = cov_start
            # A crash between a window record and its trim record leaves
            # a stale buf_start: never re-buffer rows a restaged window
            # already covers.
            if restaged:
                bs = max(bs, (max(self.windows) + 1) * W)
            bs = max(bs, cov_start)
            self._buf = {
                name: [
                    np.concatenate(
                        [np.asarray(c[name]) for _, c in chunks]
                    )[bs - cov_start :]
                ]
                for name in self.columns
            }
            self._buf_start = bs
            self._next_row = table_end
        elif chunks:
            _log_serving().warning(
                "ring %r: discarding unrecoverable spill buffer "
                "(coverage [%s, %s) vs table end %d)",
                self.table_name, cov_start, cov_end, table_end,
            )
        if self._spill is not None:
            # Persist exactly the adopted state: anything recovery
            # rejected (stale geometry, rows this table doesn't have,
            # corrupt payloads) is compacted off disk NOW, so it can
            # never resurrect on a later restart against a table whose
            # rows it no longer matches.
            self._spill.maybe_compact(
                set(self.windows), self._buf_start, force=True
            )
            _SPILL_BYTES.labels(table=self.table_name).set(
                self._spill.nbytes()
            )

    # -- read side (query staging) -------------------------------------------
    def lookup(
        self, start_row: int, rows: int, needed_cols
    ) -> Optional[ResidentWindow]:
        """The resident window covering EXACTLY rows
        [start_row, start_row + rows) with every needed column, or None.
        Only full, aligned windows ever match — misalignment after
        ring-buffer expiry silently degrades to the staging path."""
        W = self.window_rows
        if rows != W or start_row % W != 0:
            return None
        with self._lock:
            if not self._valid:
                return None
            win = self.windows.get(start_row // W)
        if win is None:
            return None
        for name in needed_cols:
            if name not in win.blocks:
                return None
        _HITS.inc()
        return win

    def release_all(self) -> None:
        with self._lock:
            for k in list(self.windows):
                self._release_locked(k)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "table": self.table_name,
                "window_rows": self.window_rows,
                "windows": len(self.windows),
                "resident_rows": len(self.windows) * self.window_rows,
                "bytes": sum(w.nbytes for w in self.windows.values()),
                "valid": self._valid,
                "buffered_rows": self._next_row - self._buf_start,
                "recovered_windows": self.recovered_windows,
                "spill_bytes": (
                    self._spill.nbytes() if self._spill is not None else 0
                ),
            }


class ReplicaRing:
    """A follower agent's HBM mirror of another agent's ResidentRing
    (r17, flag ``ring_replication_factor`` > 1).

    Windows arrive as the leader's EXACT wire representation (codec
    payload or raw flat column) and decode device-side into the same
    [D, nblk, B] raw-dtype blocks a local ring would hold — so a
    failover query on this agent finds the hot span already resident
    (wire ~ 0) and ``lookup`` serves it bit-identically to the leader.
    The replica never observes table appends; its freshness is bounded
    by the leader's advertised watermark (``leader_latest``), and any
    window it lacks — decode failure, geometry mismatch, the
    ``resident.replica_lag`` fault site, or plain lag — silently falls
    back to staging from the table store (the ring-miss path queries
    already take)."""

    def __init__(self, mesh, table_name: str, window_rows: int,
                 block_rows: int, pool=None):
        from pixie_tpu.parallel.staging import block_geometry

        self.mesh = mesh
        self.table_name = table_name
        self.window_rows = int(window_rows)
        self.d = mesh.devices.size
        self.b, self.nblk = block_geometry(
            self.window_rows, self.d, block_rows
        )
        self._pool = pool
        self._lock = threading.Lock()
        self.windows: dict[int, ResidentWindow] = {}
        self.leader_latest = -1  # highest window index the leader staged

    def adopt_window(
        self, k: int, start_row: int, rows: int, wire_cols: dict,
        latest_k: int,
    ) -> bool:
        """Decode one replicated window into HBM. Returns False (and
        counts the lag) when the window cannot be adopted — the replica
        stays behind and correctness rides the staging fallback."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from pixie_tpu.ops import codec as _codec
        from pixie_tpu.utils import faults

        with self._lock:
            self.leader_latest = max(self.leader_latest, int(latest_k))
            W = self.window_rows
            if rows != W or start_row != k * W:
                _REPLICA_LAGGED.inc(table=self.table_name)
                return False
            if faults.ACTIVE and faults.fires("resident.replica_lag"):
                # A dropped/late replication frame: the replica is now
                # behind the leader's watermark for this window.
                _REPLICA_LAGGED.inc(table=self.table_name)
                return False
            axis_name = tuple(self.mesh.axis_names)  # dim0 over every mesh axis
            sharding = NamedSharding(self.mesh, P(axis_name))
            shard_len = self.nblk * self.b
            blocks = {}
            nbytes = 0
            try:
                for name, (kind, data) in wire_cols.items():
                    if kind == "codec":
                        cp = data.plan
                        if cp.d != self.d or cp.shard_len != shard_len:
                            raise ValueError("replica geometry mismatch")
                        args = _codec.put_payload(self.mesh, data)
                        blocks[name] = _codec.decoder(
                            self.mesh, cp, self.nblk, self.b
                        )(*args)
                        nbytes += cp.block_nbytes()
                    else:
                        flat = np.asarray(data)
                        if flat.size != self.d * shard_len:
                            raise ValueError("replica geometry mismatch")
                        blocks[name] = jax.device_put(
                            flat.reshape(self.d, self.nblk, self.b),
                            sharding,
                        )
                        nbytes += flat.nbytes
            except Exception:
                _log_serving().exception(
                    "replica window %d of %r not adopted",
                    k, self.table_name,
                )
                _REPLICA_LAGGED.inc(table=self.table_name)
                return False
            self.windows[k] = ResidentWindow(k, start_row, rows, blocks,
                                             nbytes)
            _REPLICA_ADOPTED.inc(table=self.table_name)
            if self._pool is not None:
                self._pool.register_resident(
                    ("replica", self.table_name, k), nbytes
                )
            cap = max(int(flags.resident_max_windows), 1)
            while len(self.windows) > cap:
                self._release_locked(min(self.windows))
            return True

    def _release_locked(self, k: int) -> None:
        self.windows.pop(k, None)
        if self._pool is not None:
            self._pool.release_resident(("replica", self.table_name, k))

    def release_all(self) -> None:
        with self._lock:
            for k in list(self.windows):
                self._release_locked(k)

    # -- read side: same contract as ResidentRing.lookup ---------------------
    def lookup(
        self, start_row: int, rows: int, needed_cols
    ) -> Optional[ResidentWindow]:
        W = self.window_rows
        if rows != W or start_row % W != 0:
            return None
        with self._lock:
            win = self.windows.get(start_row // W)
        if win is None:
            return None
        for name in needed_cols:
            if name not in win.blocks:
                return None
        _REPLICA_HITS.inc()
        return win

    def snapshot(self) -> dict:
        with self._lock:
            latest = max(self.windows) if self.windows else -1
            # Lag counts every window inside the leader's retention
            # span this replica lacks — holes from dropped replication
            # frames included, not just a short tail.
            cap = max(int(flags.resident_max_windows), 1)
            span_start = max(self.leader_latest - cap + 1, 0)
            lag = sum(
                1
                for k in range(span_start, self.leader_latest + 1)
                if k not in self.windows
            )
            return {
                "table": self.table_name,
                "window_rows": self.window_rows,
                "windows": len(self.windows),
                "latest": latest,
                "leader_latest": self.leader_latest,
                "lag": lag,
                "bytes": sum(w.nbytes for w in self.windows.values()),
            }


class ResidentIngestManager:
    """The MeshExecutor's registry of per-table rings — owned
    (append-fed) rings plus adopted replica rings (r17)."""

    def __init__(self, mesh, block_rows: int, pool=None):
        self.mesh = mesh
        self.block_rows = block_rows
        self.pool = pool
        self._lock = threading.Lock()
        self._rings: dict[str, ResidentRing] = {}
        self._replicas: dict[str, ReplicaRing] = {}
        # Replication hook applied to rings created later (r17).
        self._replication_hook = None

    def enable(self, table) -> Optional[ResidentRing]:
        """Attach a ring to ``table`` (idempotent per table name).
        Returns the ring, or None when the table has no ring-able
        columns."""
        with self._lock:
            ring = self._rings.get(table.name)
            if ring is not None:
                return ring
            ring = ResidentRing(self.mesh, table, self.block_rows, self.pool)
            if not ring.columns:
                return None
            ring.replication_hook = self._replication_hook
            self._rings[table.name] = ring
        table.add_append_listener(ring.on_append)
        return ring

    def set_replication_hook(self, hook) -> None:
        """Install the leader-side replication hook on every owned ring
        (current and future)."""
        with self._lock:
            self._replication_hook = hook
            for ring in self._rings.values():
                ring.replication_hook = hook

    def adopt_replica_window(
        self, table_name: str, window_rows: int, k: int, start_row: int,
        rows: int, wire_cols: dict, latest_k: int,
    ) -> bool:
        """Follower side: decode a replicated window into this agent's
        HBM (creating the table's ReplicaRing on first sight)."""
        with self._lock:
            rep = self._replicas.get(table_name)
            if rep is None or rep.window_rows != int(window_rows):
                if rep is not None:
                    rep.release_all()
                rep = ReplicaRing(
                    self.mesh, table_name, window_rows, self.block_rows,
                    self.pool,
                )
                self._replicas[table_name] = rep
        return rep.adopt_window(k, start_row, rows, wire_cols, latest_k)

    def ring_for(self, table_name: str):
        """The table's serving ring: the owned (append-fed) ring when
        one exists, else an adopted replica ring (r17 failover — the
        agent never owned the table but its HBM already holds the hot
        windows)."""
        with self._lock:
            return self._rings.get(table_name) or self._replicas.get(
                table_name
            )

    def replica_for(self, table_name: str) -> Optional[ReplicaRing]:
        with self._lock:
            return self._replicas.get(table_name)

    def replica_snapshot(self) -> dict:
        with self._lock:
            reps = list(self._replicas.values())
        return {r.table_name: r.snapshot() for r in reps}

    def snapshot(self) -> dict:
        with self._lock:
            rings = list(self._rings.values())
        return {r.table_name: r.snapshot() for r in rings}
