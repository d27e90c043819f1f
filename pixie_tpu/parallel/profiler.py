"""Device & HBM resource-attribution recorders (r15).

Ref posture: Google-Wide Profiling (Ren et al., IEEE Micro 2010) —
always-on sampled profiling is affordable when the samples carry
workload attribution — applied to the device side of this engine. Three
ring buffers feed the self-telemetry tables (ingest/self_telemetry.py),
drained the same way finished trace spans are:

  device_programs    one row per compiled device program: the program
                     cache signature (truncated), unit kind (init/fold/
                     merge/fin/decode), XLA cost analysis (flops, bytes
                     accessed) when an AOT compile produced a Compiled,
                     and the measured compile seconds.
  device_dispatches  one row per device dispatch (whole-offload
                     ``fold`` rows from try_execute_fragment, per-window
                     ``stream_fold``/``stream_window`` rows from the
                     streaming stage), stamped with the dispatching
                     thread's ambient (query_id, tenant, phase)
                     attribution (utils/trace.py) — device wall time and
                     staged/decoded bytes become attributable per query.
  hbm_usage          point-in-time residency-pool snapshots (total /
                     pinned / ring bytes, per-table residency), sampled
                     by the pool itself at ``hbm_snapshot_interval_s``
                     cadence plus a forced sample at every telemetry
                     flush.

Design contract (mirrors utils/faults.py and utils/trace.py): call
sites gate on the module-level ``ACTIVE`` bool, synced with the shared
``resource_attribution`` flag — disabled, every hook is one attribute
load + branch, held <1% of the warm fold and transport RTT by
tools/microbench_fault_overhead.py's ``profiler_overhead`` key.
"""

from __future__ import annotations

import collections
import threading
import time
import weakref
from typing import Any, Optional

from pixie_tpu.utils import trace
from pixie_tpu.utils.config import define_flag, flags

define_flag(
    "hbm_snapshot_interval_s",
    1.0,
    help_="Minimum seconds between HBM residency-pool usage snapshots "
    "(hbm_usage self-telemetry rows). Snapshots are taken on pool "
    "mutations at most this often, plus one forced sample at every "
    "self-telemetry flush; 0 samples on every mutation.",
)
define_flag(
    "profiler_buffer_cap",
    8192,
    help_="Ring-buffer capacity per resource-attribution stream "
    "(device_dispatches rows, hbm_usage rows, new device_programs "
    "rows); oldest entries are evicted when telemetry ingestion falls "
    "behind.",
)

# Fast gate, synced with the resource_attribution flag (one attribute
# load + branch per call site when attribution is off).
ACTIVE = False


def refresh() -> None:
    global ACTIVE
    ACTIVE = bool(flags.resource_attribution)


def set_enabled(on: bool) -> None:
    """Flip the recorders AND the thread-attribution plane together —
    they share the ``resource_attribution`` flag."""
    global ACTIVE
    ACTIVE = bool(on)
    trace.set_attribution_enabled(on)


_LOCK = threading.Lock()
_cap = int(flags.profiler_buffer_cap)
# sig -> program row (registry: one row per distinct compiled program;
# re-records update cost/compile fields in place).
_PROGRAMS: dict[str, dict] = {}
# Rows not yet drained into the device_programs table.
_NEW_PROGRAMS: "collections.deque[dict]" = collections.deque(maxlen=_cap)
_DISPATCHES: "collections.deque[dict]" = collections.deque(maxlen=_cap)
_HBM: "collections.deque[dict]" = collections.deque(maxlen=_cap)
# Residency pools that registered for forced flush-time sampling.
_POOLS: "weakref.WeakSet" = weakref.WeakSet()


def program_name(sig: str) -> str:
    """Stable short name for a program signature: the unit kind prefix
    plus a content hash — full fold signatures run to hundreds of chars
    and would bloat every dispatch row."""
    kind = sig.split("|", 1)[0] if "|" in sig else "program"
    import hashlib

    h = hashlib.blake2s(sig.encode(), digest_size=6).hexdigest()
    return f"{kind}:{h}"


def cost_analysis_of(compiled) -> dict:
    """(flops, bytes accessed) from a jax Compiled's XLA cost analysis —
    best-effort across jax versions (dict or [dict] returns, missing
    keys on some backends)."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        ca = dict(ca or {})
        return {
            "flops": float(ca.get("flops", 0.0) or 0.0),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0) or 0.0),
        }
    except Exception:
        return {"flops": 0.0, "bytes_accessed": 0.0}


def record_program(
    sig: str,
    kind: Optional[str] = None,
    compile_s: float = 0.0,
    compiled: Any = None,
) -> None:
    """Register (or enrich) a compiled device program. Called at
    ``_get_program`` cache misses (kind + signature; cost unknown — the
    program is a traced jit, not yet an executable) and again when the
    background AOT worker produces a Compiled (cost analysis + measured
    compile seconds). Each (re-)record emits a row for the
    device_programs table so the series shows when costs became known."""
    if not ACTIVE:
        return
    row = {
        "time_ns": time.time_ns(),
        "program": program_name(sig),
        "kind": kind or (sig.split("|", 1)[0] if "|" in sig else "program"),
        "flops": 0.0,
        "bytes_accessed": 0.0,
        "compile_seconds": float(compile_s),
    }
    if compiled is not None:
        row.update(cost_analysis_of(compiled))
    with _LOCK:
        prev = _PROGRAMS.get(sig)
        if prev is not None:
            # Keep the richest view: an AOT record upgrades the
            # trace-time stub, never the reverse.
            row["flops"] = row["flops"] or prev["flops"]
            row["bytes_accessed"] = (
                row["bytes_accessed"] or prev["bytes_accessed"]
            )
            row["compile_seconds"] = (
                row["compile_seconds"] or prev["compile_seconds"]
            )
        _PROGRAMS[sig] = row
        _NEW_PROGRAMS.append(dict(row))


def record_dispatch(
    kind: str,
    duration_s: float,
    program: str = "",
    rows: int = 0,
    staged_bytes: int = 0,
    wire_bytes: int = 0,
) -> None:
    """One device dispatch, attributed to the ambient thread's
    (query_id, tenant, phase). ``staged_bytes`` is the decoded on-device
    footprint the dispatch covered; ``wire_bytes`` what actually crossed
    host->HBM (codec-compressed)."""
    if not ACTIVE:
        return
    attr = trace.current_attribution() or ("", "", "")
    with _LOCK:
        _DISPATCHES.append(
            {
                "time_ns": time.time_ns(),
                "query_id": attr[0],
                "tenant": attr[1],
                "phase": attr[2],
                "kind": kind,
                "program": program,
                "duration_ns": int(duration_s * 1e9),
                "rows": int(rows),
                "staged_bytes": int(staged_bytes),
                "wire_bytes": int(wire_bytes),
            }
        )


def record_hbm_rows(rows: list[dict]) -> None:
    """Buffer pre-built hbm_usage rows (serving/residency.py builds them
    under its own lock so the snapshot is consistent)."""
    if not ACTIVE or not rows:
        return
    with _LOCK:
        _HBM.extend(rows)


def register_pool(pool) -> None:
    """Track a ResidencyPool for forced sampling at telemetry-flush time
    (weakly — a dropped executor's pool just disappears)."""
    _POOLS.add(pool)


def sample_pools() -> None:
    """Force one usage snapshot from every registered pool (the flush
    path calls this so hbm_usage is fresh even on an idle pool)."""
    if not ACTIVE:
        return
    for pool in list(_POOLS):
        try:
            pool.sample_usage(force=True)
        except Exception:
            pass  # advisory; a sampling failure must never fail a flush


# -- drains (single consumer per process: the self-telemetry flush) ----------
def drain_programs() -> list[dict]:
    with _LOCK:
        out = list(_NEW_PROGRAMS)
        _NEW_PROGRAMS.clear()
    return out


def drain_dispatches() -> list[dict]:
    with _LOCK:
        out = list(_DISPATCHES)
        _DISPATCHES.clear()
    return out


def drain_hbm() -> list[dict]:
    with _LOCK:
        out = list(_HBM)
        _HBM.clear()
    return out


def dispatches_snapshot() -> list[dict]:
    """Copies without draining (the soak harness peeks mid-run)."""
    with _LOCK:
        return [dict(d) for d in _DISPATCHES]


def buffered_counts() -> dict[str, int]:
    with _LOCK:
        return {
            "programs": len(_NEW_PROGRAMS),
            "dispatches": len(_DISPATCHES),
            "hbm": len(_HBM),
        }


def clear() -> None:
    """Drop all buffered rows and the program registry (tests)."""
    with _LOCK:
        _PROGRAMS.clear()
        _NEW_PROGRAMS.clear()
        _DISPATCHES.clear()
        _HBM.clear()


refresh()
