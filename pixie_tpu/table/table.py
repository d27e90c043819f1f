"""Hot/cold in-memory table with ring-buffer expiry and time-sliced cursors.

Ref: src/table_store/table/table.h:51,72-160 — Table keeps a write-side "hot"
partition and a compacted "cold" partition, bounded by a size limit (oldest
data expires first); Cursors are time+row-id indexed and survive concurrent
compaction/expiry because row ids are global and monotonic
(internal/store_with_row_accounting.h).

TPU-first twist: compaction coalesces hot batches into cold batches of
``compacted_rows`` rows — chosen to match the exec engine's device block size
so cold reads stage to HBM with zero re-chunking.
"""

from __future__ import annotations

import bisect
import dataclasses
import operator
import threading
from typing import Optional

import numpy as np

from pixie_tpu.table.column import DictColumn, StringDictionary
from pixie_tpu.table.row_batch import RowBatch
from pixie_tpu.types import DataType, Relation

DEFAULT_SIZE_LIMIT = 64 * 1024 * 1024  # ref: FLAGS_table_store_table_size_limit
DEFAULT_COMPACTED_ROWS = 1 << 17  # 131072 rows/cold batch == device block size
TIME_COLUMN = "time_"


@dataclasses.dataclass
class TableStats:
    """Ref: TableStats (table.h:58)."""

    batches_added: int = 0
    batches_expired: int = 0
    compacted_batches: int = 0
    bytes_added: int = 0
    num_batches: int = 0
    num_rows: int = 0
    bytes: int = 0
    max_table_size: int = 0
    min_time: int = -1


@dataclasses.dataclass
class _Segment:
    first_row_id: int
    batch: RowBatch
    min_time: int
    max_time: int
    hot: bool

    @property
    def num_rows(self) -> int:
        return self.batch.num_rows

    @property
    def end_row_id(self) -> int:
        return self.first_row_id + self.num_rows


_FIRST_ROW_ID = operator.attrgetter("first_row_id")


class Table:
    """Append-only (write side) columnar table with bounded memory."""

    def __init__(
        self,
        relation: Relation,
        size_limit: int = DEFAULT_SIZE_LIMIT,
        compacted_rows: int = DEFAULT_COMPACTED_ROWS,
        name: str = "",
    ):
        self.name = name
        self.relation = relation
        self.size_limit = size_limit
        self.compacted_rows = compacted_rows
        self._lock = threading.RLock()
        self._metric_bytes = None  # bound lazily: name may be set later
        self._metric_batches = None
        self._segments: list[_Segment] = []
        self._next_row_id = 0
        self._bytes = 0
        self._stats = TableStats(max_table_size=size_limit)
        self._stopped = False  # stream end marker for streaming cursors
        # Table-level string dictionaries, shared by every batch written so
        # codes are comparable across the whole table (segment-id property).
        self.dictionaries: dict[str, StringDictionary] = {
            c.name: StringDictionary()
            for c in relation
            if c.data_type == DataType.STRING
        }
        self._time_idx = (
            relation.col_idx(TIME_COLUMN) if relation.has_column(TIME_COLUMN) else -1
        )
        # Append listeners (r13): fn(first_row_id, batch) fired inside
        # the write lock AFTER dictionary adoption, so device-resident
        # ingest rings see every row exactly once, in row-id order, with
        # table-dictionary codes. Keep listeners cheap-ish: they run on
        # the writer's thread.
        self._append_listeners: list = []

    # -- write side --------------------------------------------------------
    def write(self, batch: RowBatch) -> None:
        """Append a hot batch (ref: Table::WriteRowBatch / TransferRecordBatch)."""
        if batch.relation.col_names() != self.relation.col_names():
            raise ValueError(
                f"batch relation {batch.relation} != table relation {self.relation}"
            )
        batch = self._adopt_dictionaries(batch)
        with self._lock:
            if self._time_idx >= 0 and batch.num_rows:
                t = np.asarray(batch.columns[self._time_idx])
                mn, mx = int(t.min()), int(t.max())
            else:
                mn = mx = self._segments[-1].max_time if self._segments else 0
            seg = _Segment(self._next_row_id, batch, mn, mx, hot=True)
            self._segments.append(seg)
            first_row_id = self._next_row_id
            self._next_row_id += batch.num_rows
            for fn in self._append_listeners:
                try:
                    fn(first_row_id, batch)
                except Exception:
                    import logging

                    logging.getLogger("pixie_tpu.table").exception(
                        "append listener failed (ignored)"
                    )
            nbytes = batch.num_bytes()
            self._bytes += nbytes
            self._stats.batches_added += 1
            self._stats.bytes_added += nbytes
            self._expire_locked()
            if self.name:  # occupancy gauges (ref: table_metrics.h)
                if self._metric_bytes is None:
                    from pixie_tpu.utils import metrics_registry

                    m = metrics_registry()
                    self._metric_bytes = m.gauge(
                        "table_bytes", "Resident bytes per table."
                    ).labels(table=self.name)
                    self._metric_batches = m.gauge(
                        "table_batches", "Resident batches per table."
                    ).labels(table=self.name)
                self._metric_bytes.set(self._bytes)
                self._metric_batches.set(len(self._segments))

    def write_pydict(self, data: dict, eow=False, eos=False) -> None:
        self.write(
            RowBatch.from_pydict(
                self.relation, data, dictionaries=self.dictionaries, eow=eow, eos=eos
            )
        )

    def stop(self) -> None:
        """Mark the stream ended (streaming cursors will see eos)."""
        with self._lock:
            self._stopped = True

    def add_append_listener(self, fn) -> None:
        """Register fn(first_row_id, batch), fired under the write lock
        after every append (post dictionary adoption). The r13
        device-resident ingest hook."""
        with self._lock:
            self._append_listeners.append(fn)

    def remove_append_listener(self, fn) -> None:
        with self._lock:
            try:
                self._append_listeners.remove(fn)
            except ValueError:
                pass

    def _adopt_dictionaries(self, batch: RowBatch) -> RowBatch:
        """Re-encode any foreign-dictionary string columns into table dicts."""
        cols = []
        changed = False
        for schema, col in zip(batch.relation, batch.columns):
            if isinstance(col, DictColumn):
                table_dict = self.dictionaries[schema.name]
                if col.dictionary is not table_dict:
                    cols.append(DictColumn(table_dict.encode(col.decode()), table_dict))
                    changed = True
                    continue
            cols.append(col)
        if not changed:
            return batch
        return RowBatch(batch.relation, cols, eow=batch.eow, eos=batch.eos)

    # -- compaction / expiry ----------------------------------------------
    def compact(self) -> int:
        """Coalesce hot batches into cold batches of ``compacted_rows`` rows.

        Ref: Table::CompactHotToCold (kMaxBatchesPerCompactionCall,
        internal/arrow_array_compactor.*). Returns number of cold batches
        produced. Called periodically by the store's compaction thread or
        inline by tests.
        """
        with self._lock:
            hot = [s for s in self._segments if s.hot]
            if not hot:
                return 0
            hot_rows = sum(s.num_rows for s in hot)
            # Leave a partial tail hot unless the table is stopped.
            n_cold_rows = (
                hot_rows if self._stopped else (hot_rows // self.compacted_rows)
                * self.compacted_rows
            )
            if n_cold_rows == 0:
                return 0
            merged = RowBatch.concat([s.batch for s in hot])
            first_id = hot[0].first_row_id
            cold_part = merged.slice(0, n_cold_rows)
            produced = []
            for off in range(0, n_cold_rows, self.compacted_rows):
                chunk = cold_part.slice(off, min(off + self.compacted_rows, n_cold_rows))
                t = (
                    np.asarray(chunk.columns[self._time_idx])
                    if self._time_idx >= 0 and chunk.num_rows
                    else None
                )
                produced.append(
                    _Segment(
                        first_id + off,
                        chunk,
                        int(t.min()) if t is not None else 0,
                        int(t.max()) if t is not None else 0,
                        hot=False,
                    )
                )
            tail_segments = []
            if n_cold_rows < hot_rows:
                tail = merged.slice(n_cold_rows, hot_rows)
                t = (
                    np.asarray(tail.columns[self._time_idx])
                    if self._time_idx >= 0 and tail.num_rows
                    else None
                )
                tail_segments.append(
                    _Segment(
                        first_id + n_cold_rows,
                        tail,
                        int(t.min()) if t is not None else 0,
                        int(t.max()) if t is not None else 0,
                        hot=True,
                    )
                )
            cold_prefix = [s for s in self._segments if not s.hot]
            self._segments = cold_prefix + produced + tail_segments
            self._stats.compacted_batches += len(produced)
            return len(produced)

    def _expire_locked(self) -> None:
        while self._bytes > self.size_limit and len(self._segments) > 1:
            seg = self._segments.pop(0)
            self._bytes -= seg.batch.num_bytes()
            self._stats.batches_expired += 1

    # -- read side ---------------------------------------------------------
    def min_row_id(self) -> int:
        with self._lock:
            return self._segments[0].first_row_id if self._segments else 0

    def end_row_id(self) -> int:
        with self._lock:
            return self._next_row_id

    @property
    def stopped(self) -> bool:
        return self._stopped

    def stats(self) -> TableStats:
        with self._lock:
            s = dataclasses.replace(self._stats)
            s.num_batches = len(self._segments)
            s.num_rows = sum(seg.num_rows for seg in self._segments)
            s.bytes = self._bytes
            s.min_time = self._segments[0].min_time if self._segments else -1
            return s

    def time_bounds(self) -> tuple[Optional[int], Optional[int]]:
        """(min, max) time currently resident, or (None, None) if empty."""
        with self._lock:
            if not self._segments:
                return None, None
            return self._segments[0].min_time, self._segments[-1].max_time

    def cursor(
        self,
        start_time: Optional[int] = None,
        stop_time: Optional[int] = None,
        streaming: bool = False,
    ) -> "Cursor":
        return Cursor(self, start_time, stop_time, streaming)

    def _read_from(
        self, row_id: int, max_rows: int, start_time, stop_time
    ) -> tuple[Optional[RowBatch], int, int]:
        """Return (batch, next_row_id, segments_visited). Batch is None if
        nothing is available yet.

        The loop starts at the segment holding ``row_id``, found by
        bisection on ``first_row_id``: segments are in row-id order and
        row ids are global and monotonic, so every segment before it ends
        at or before ``row_id`` whatever compaction or expiry did since
        the last call. ``segments_visited`` counts the loop's segments."""
        with self._lock:
            segs = self._segments
            i = bisect.bisect_right(segs, row_id, key=_FIRST_ROW_ID) - 1
            visited = 0
            for j in range(max(i, 0), len(segs)):
                seg = segs[j]
                visited += 1
                if seg.end_row_id <= row_id:
                    continue
                # Time-slice pruning on segment [min,max] bounds.
                if start_time is not None and seg.max_time < start_time:
                    row_id = seg.end_row_id
                    continue
                if stop_time is not None and seg.min_time > stop_time:
                    return None, row_id, visited  # telemetry is time-ordered; done
                lo = max(0, row_id - seg.first_row_id)
                hi = min(seg.num_rows, lo + max_rows)
                chunk = seg.batch.slice(lo, hi)
                next_id = seg.first_row_id + hi
                if self._time_idx >= 0 and (
                    start_time is not None or stop_time is not None
                ):
                    t = np.asarray(chunk.columns[self._time_idx])
                    mask = np.ones(len(t), dtype=bool)
                    if start_time is not None:
                        mask &= t >= start_time
                    if stop_time is not None:
                        mask &= t <= stop_time
                    if not mask.all():
                        chunk = chunk.take(np.nonzero(mask)[0])
                return chunk, next_id, visited
            return None, max(row_id, self._next_row_id), visited


class Cursor:
    """Time+row-id indexed iterator; survives concurrent compaction/expiry.

    Ref: Table::Cursor (table.h:127-160). If data the cursor points at has
    been expired from the ring buffer, the cursor silently skips forward (the
    reference logs a data-loss counter; we track ``rows_skipped``).
    """

    def __init__(self, table: Table, start_time, stop_time, streaming: bool):
        self.table = table
        self.start_time = start_time
        self.stop_time = stop_time
        self.streaming = streaming
        self._row_id = table.min_row_id()
        self.rows_skipped = 0
        self.segments_visited = 0  # summed over next_batch calls
        self._done = False

    def done(self) -> bool:
        if self._done:
            return True
        if self.streaming and not self.table.stopped:
            return False
        return self._row_id >= self.table.end_row_id()

    def next_batch(self, max_rows: int = DEFAULT_COMPACTED_ROWS) -> Optional[RowBatch]:
        """Next row batch, or None if no data is currently available."""
        if self._done:
            return None
        min_id = self.table.min_row_id()
        if self._row_id < min_id:
            self.rows_skipped += min_id - self._row_id
            self._row_id = min_id
        batch, next_id, visited = self.table._read_from(
            self._row_id, max_rows, self.start_time, self.stop_time
        )
        self.segments_visited += visited
        advanced = next_id > self._row_id
        self._row_id = next_id
        if batch is None and not advanced:
            if self.stop_time is not None and not self.streaming:
                self._done = True
            return None
        return batch
