"""HBM table-residency manager: the staged-table cache as a managed pool.

Ref posture: the reference's table store evicts cold Arrow batches under
a per-table byte limit (table.h:51 table_store_table_size_limit); our
device-side analogue is the MeshExecutor's staged-cache — HBM-resident
[D, nblk, B] blocks a table version is staged into once, served to every
matching query. Until r12 that cache was an entry-count OrderedDict
(staged_cache_cap=4), blind to the one metric that matters on a device:
BYTES (staging.py: host→HBM transfer is the cold-path bottleneck, and
HBM itself is the scarcest resource a serving fleet shares).

This pool does the accounting the OrderedDict couldn't:

- **Per-entry byte accounting.** An entry's cost is the sum of its
  device block nbytes (columns + mask + gids), computed once at insert
  (``staged_nbytes``). Live totals ride the shared /metrics registry as
  ``device_staged_bytes`` / ``device_staged_pinned_bytes`` so /statusz
  shows HBM residency without touching the device.
- **Query-scoped pinning.** A fold in flight pins its entry
  (``with pool.pin(key): ...``); pinned entries are NEVER evicted — not
  by the byte watermark, not by version supersession, not by the OOM
  clear. (Refcounted jax arrays would keep the memory alive anyway;
  evicting a pinned entry would only make the accounting lie while
  freeing nothing.) A superseded-but-pinned entry leaves the key table
  immediately (lookups miss) but its bytes stay accounted as a zombie
  until the last unpin reaps it. Eviction passes that SKIP a pinned
  entry check the ``serving.evict_pinned_attempt`` fault site so chaos
  tests can prove the skip happens.
- **LRU eviction with high/low watermarks.** With ``hbm_budget_mb`` set,
  an insert that pushes the pool past the high watermark (95% of
  budget) evicts least-recently-used unpinned entries until under the
  low watermark (80%) — hysteresis, so a pool hovering at budget does
  not evict one entry per insert. The entry-count cap
  (``staged_cache_cap``) still applies as a secondary bound.
"""

from __future__ import annotations

import threading
from typing import Any, Optional

from pixie_tpu.utils import faults, flags, metrics_registry, trace

_M = metrics_registry()
_STAGED_BYTES = _M.gauge(
    "device_staged_bytes",
    "Bytes of HBM-resident staged table blocks in the residency pool "
    "(including superseded entries still pinned by in-flight folds).",
)
_PINNED_BYTES = _M.gauge(
    "device_staged_pinned_bytes",
    "Bytes of staged blocks pinned by in-flight folds (never evictable).",
)
_ENTRIES = _M.gauge(
    "device_staged_entries", "Entries in the staged-table residency pool."
)
_EVICTIONS = _M.counter(
    "device_staged_cache_evictions_total",
    "HBM staged-table cache evictions (LRU cap, byte watermark, version "
    "change, or device OOM).",
)
_PIN_SKIPS = _M.counter(
    "device_staged_evict_pinned_skips_total",
    "Eviction passes that skipped an entry because an in-flight fold "
    "had it pinned.",
)

HIGH_WATERMARK = 0.95
LOW_WATERMARK = 0.80


def staged_nbytes(staged: Any) -> int:
    """Device bytes of a StagedColumns entry: column blocks + validity
    mask + (optional) gid blocks, per branch for a fan-out. jax arrays report their on-device
    nbytes; anything without the attribute (test shims) counts 0."""
    total = 0
    for a in getattr(staged, "blocks", {}).values():
        total += int(getattr(a, "nbytes", 0))
    mask = getattr(staged, "mask", None)
    if mask is not None:
        total += int(getattr(mask, "nbytes", 0))
    gids = getattr(staged, "gids", None)
    if gids is not None:
        total += int(getattr(gids, "nbytes", 0))
    for g in getattr(staged, "branch_gids", {}).values():
        total += int(getattr(g, "nbytes", 0))
    return total


class _Entry:
    __slots__ = ("staged", "nbytes", "table_name", "version", "pins", "dead")

    def __init__(self, staged, nbytes, table_name, version):
        self.staged = staged
        self.nbytes = nbytes
        self.table_name = table_name
        self.version = version
        self.pins = 0
        self.dead = False  # superseded while pinned: reap at last unpin


class ResidencyPool:
    """The MeshExecutor's staged-table cache, byte-accounted and pinnable.

    API mirrors what pipeline.py needs: ``get``/``insert``/``items``/
    ``touch``/``clear`` plus the ``pin`` context manager. All methods are
    thread-safe — agents execute fragments on per-query threads, so
    concurrent queries hit one pool."""

    def __init__(
        self,
        cap_entries: Optional[int] = None,
        budget_bytes: Optional[int] = None,
    ):
        import collections

        self._lock = threading.Lock()
        self._entries: "collections.OrderedDict[Any, _Entry]" = (
            collections.OrderedDict()
        )
        # Superseded-while-pinned entries: out of the key table (lookups
        # must miss), bytes still resident until the last unpin.
        self._zombies: list[_Entry] = []
        self._cap_entries = cap_entries
        self._budget_bytes = budget_bytes
        self._used = 0
        self._pinned = 0
        # Device-resident ring windows (r13, serving/resident.py):
        # byte-accounted like staged entries and treated as permanently
        # pinned — never LRU-evicted, never OOM-cleared; only the ring
        # itself releases them (its own depth bound / table expiry).
        self._resident: dict = {}
        # HBM usage sampling (r15): pool state lands in the hbm_usage
        # self-telemetry table at most every hbm_snapshot_interval_s
        # (mutation-driven) plus a forced sample per telemetry flush.
        self._last_usage_ns = 0
        try:
            from pixie_tpu.parallel import profiler

            profiler.register_pool(self)
        except Exception:  # pragma: no cover - recorder is advisory
            pass

    # -- configuration (read per call so flag flips apply live) --------------
    def _cap(self) -> int:
        return (
            self._cap_entries
            if self._cap_entries is not None
            else flags.staged_cache_cap
        )

    def budget_bytes(self) -> int:
        if self._budget_bytes is not None:
            return self._budget_bytes
        return int(flags.hbm_budget_mb) * (1 << 20)

    # -- lookup --------------------------------------------------------------
    def get(self, key) -> Optional[Any]:
        """The staged entry for ``key`` (LRU-touched), or None."""
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                return None
            self._entries.move_to_end(key)
            return e.staged

    def touch(self, key) -> None:
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)

    def items(self) -> list:
        """(key, staged) snapshot in LRU order (superset-reuse scan)."""
        with self._lock:
            return [(k, e.staged) for k, e in self._entries.items()]

    def values(self) -> list:
        with self._lock:
            return [e.staged for e in self._entries.values()]

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._entries

    # -- mutation ------------------------------------------------------------
    def insert(self, key, staged, table_name, version) -> None:
        """Register a staged entry: supersede stale versions of the same
        table, account bytes, then enforce the byte watermark and the
        entry cap (LRU, pinned entries skipped)."""
        nbytes = staged_nbytes(staged)
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._retire_locked(old, reason="replaced")
            # A new version of a table supersedes every older staging of
            # it — queries must not keep hitting pre-write data.
            for k in [
                k
                for k, e in self._entries.items()
                if e.table_name == table_name and e.version != version
            ]:
                self._retire_locked(
                    self._entries.pop(k), reason="version"
                )
            e = _Entry(staged, nbytes, table_name, version)
            self._entries[key] = e
            self._used += nbytes
            budget = self.budget_bytes()
            if budget > 0 and self._used > budget * HIGH_WATERMARK:
                self._evict_to_locked(
                    int(budget * LOW_WATERMARK), protect=key
                )
            cap = self._cap()
            while len(self._entries) > cap:
                victim = self._lru_unpinned_locked(protect=key)
                if victim is None:
                    break  # everything pinned: over cap beats corruption
                self._retire_locked(
                    self._entries.pop(victim), reason="lru"
                )
            self._publish_locked()

    def clear(self, reason: str = "oom") -> None:
        """Drop every entry (the device-OOM clear-and-retry path).
        Pinned entries' bytes stay accounted as zombies until their
        folds unpin — an in-flight fold's blocks are not freed by
        removing our reference to them."""
        with self._lock:
            for k in list(self._entries):
                self._retire_locked(self._entries.pop(k), reason=reason)
            self._publish_locked()

    # -- resident ring windows (r13) -----------------------------------------
    def register_resident(self, key, nbytes: int) -> None:
        """Account a device-resident ring window's bytes: they count as
        used AND pinned (unevictable by any pool policy — the ring owns
        their lifetime), so the byte watermark, /statusz, and admission's
        headroom math all see HBM the rings occupy."""
        with self._lock:
            old = self._resident.pop(key, None)
            if old is not None:
                self._used -= old
                self._pinned -= old
            self._resident[key] = int(nbytes)
            self._used += int(nbytes)
            self._pinned += int(nbytes)
            self._publish_locked()

    def release_resident(self, key) -> None:
        """Free a ring window's accounting (ring rolled past it, or the
        table expired its rows)."""
        with self._lock:
            nbytes = self._resident.pop(key, None)
            if nbytes is not None:
                self._used -= nbytes
                self._pinned -= nbytes
                _EVICTIONS.inc(reason="resident_roll")
                self._publish_locked()

    # -- pinning -------------------------------------------------------------
    class _Pin:
        def __init__(self, pool: "ResidencyPool", key):
            self._pool = pool
            self._key = key
            self._entry: Optional[_Entry] = None

        def __enter__(self):
            self._entry = self._pool._pin(self._key)
            return self

        def __exit__(self, *exc):
            if self._entry is not None:
                self._pool._unpin(self._entry)
                self._entry = None
            return False

    def pin(self, key) -> "ResidencyPool._Pin":
        """Context manager: while held, the entry (if present at enter)
        cannot be evicted — a version bump or OOM clear retires it from
        the key table but its bytes stay accounted until exit. Pinning
        a missing key is a no-op (non-cacheable stagings never enter
        the pool)."""
        return ResidencyPool._Pin(self, key)

    def _pin(self, key) -> Optional[_Entry]:
        with self._lock:
            e = self._entries.get(key)
            if e is not None:
                e.pins += 1
                self._pinned += e.nbytes
                self._publish_locked()
            return e

    def _unpin(self, e: _Entry) -> None:
        with self._lock:
            e.pins -= 1
            self._pinned -= e.nbytes
            if e.pins == 0 and e.dead:
                # Superseded/cleared while this fold ran: reap now.
                self._zombies.remove(e)
                self._used -= e.nbytes
                _EVICTIONS.inc(reason="deferred")
            self._publish_locked()

    # -- internals (call under self._lock) -----------------------------------
    def _lru_unpinned_locked(self, protect=None):
        for k, e in self._entries.items():
            if k == protect:
                continue
            if e.pins > 0:
                if faults.ACTIVE:
                    faults.fires("serving.evict_pinned_attempt")
                _PIN_SKIPS.inc()
                continue
            return k
        return None

    def _evict_to_locked(self, target_bytes: int, protect=None) -> None:
        while self._used > target_bytes:
            victim = self._lru_unpinned_locked(protect=protect)
            if victim is None:
                break  # only pinned entries left; nothing evictable
            self._retire_locked(self._entries.pop(victim), reason="bytes")

    def _retire_locked(self, e: _Entry, reason: str) -> None:
        """Remove an entry already popped from the key table: free its
        accounting immediately when unpinned, else zombie it until the
        last unpin."""
        if e.pins > 0:
            if faults.ACTIVE:
                faults.fires("serving.evict_pinned_attempt")
            _PIN_SKIPS.inc()
            e.dead = True
            self._zombies.append(e)
            return
        self._used -= e.nbytes
        _EVICTIONS.inc(reason=reason)

    def _publish_locked(self) -> None:
        _STAGED_BYTES.set(self._used)
        _PINNED_BYTES.set(self._pinned)
        _ENTRIES.set(len(self._entries))
        if trace.ATTR_ACTIVE:
            self._sample_usage_locked(force=False)

    # -- HBM usage sampling (r15) --------------------------------------------
    def sample_usage(self, force: bool = True) -> None:
        """Take one hbm_usage snapshot (the telemetry flush forces one so
        the table is fresh even on an idle pool)."""
        with self._lock:
            self._sample_usage_locked(force=force)

    def _sample_usage_locked(self, force: bool) -> None:
        import time

        from pixie_tpu.parallel import profiler

        if not profiler.ACTIVE:
            return
        now_ns = time.time_ns()
        interval_ns = int(float(flags.hbm_snapshot_interval_s) * 1e9)
        if not force and now_ns - self._last_usage_ns < interval_ns:
            return
        self._last_usage_ns = now_ns
        # Per-table staged bytes/pins (live entries), per-table ring
        # bytes (resident keys are ("resident", table, window)), plus
        # one pool-scope summary row whose used/pinned match the
        # accounting EXACTLY (zombies included — in-flight folds hold
        # real HBM).
        per_table: dict[str, dict] = {}
        for e in self._entries.values():
            t = per_table.setdefault(
                e.table_name,
                {"used": 0, "pinned": 0, "resident": 0, "entries": 0},
            )
            t["used"] += e.nbytes
            t["pinned"] += e.nbytes if e.pins > 0 else 0
            t["entries"] += 1
        for key, nbytes in self._resident.items():
            name = (
                str(key[1])
                if isinstance(key, tuple) and len(key) >= 2
                else str(key)
            )
            t = per_table.setdefault(
                name, {"used": 0, "pinned": 0, "resident": 0, "entries": 0}
            )
            t["used"] += nbytes
            t["pinned"] += nbytes
            t["resident"] += nbytes
        budget = self.budget_bytes()
        rows = [
            {
                "time_ns": now_ns,
                "scope": "pool",
                "name": "",
                "used_bytes": self._used,
                "pinned_bytes": self._pinned,
                "resident_bytes": sum(self._resident.values()),
                "budget_bytes": budget,
                "entries": len(self._entries),
            }
        ]
        for name, t in sorted(per_table.items()):
            rows.append(
                {
                    "time_ns": now_ns,
                    "scope": "table",
                    "name": name,
                    "used_bytes": t["used"],
                    "pinned_bytes": t["pinned"],
                    "resident_bytes": t["resident"],
                    "budget_bytes": budget,
                    "entries": t["entries"],
                }
            )
        profiler.record_hbm_rows(rows)

    # -- observability -------------------------------------------------------
    def snapshot(self) -> dict:
        """Residency state for /statusz and heartbeat health payloads."""
        with self._lock:
            budget = self.budget_bytes()
            return {
                "entries": len(self._entries),
                "used_bytes": self._used,
                "pinned_bytes": self._pinned,
                "zombie_entries": len(self._zombies),
                "resident_windows": len(self._resident),
                "resident_bytes": sum(self._resident.values()),
                "budget_bytes": budget,
                "headroom_bytes": (
                    max(budget - self._used, 0) if budget > 0 else None
                ),
                "tables": sorted(
                    {e.table_name for e in self._entries.values()}
                ),
            }

    def used_bytes(self) -> int:
        with self._lock:
            return self._used

    def pinned_bytes(self) -> int:
        with self._lock:
            return self._pinned
