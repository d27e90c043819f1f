"""Incremental materialized views (r20): dashboards read merged
partial-agg state, they don't fold.

Ref: Pixie's design point (PAPER.md) is PxL scripts re-executed on an
interval against an in-memory columnar store — the dominant serving
workload is the SAME aggregation re-folded every few seconds. This
module converts each repeat into a watermark-bounded delta fold plus a
cheap merged read:

    append ──▶ [maintenance tick] fold rows [watermark, end) through
               the view's projection/predicates into a PARTIAL
               StateBatch, merge into the carried state, persist
               (StateBatch wire codec + watermark) to the datastore
    read   ──▶ delta-fold the unflushed tail [watermark, end), merge
               with the carried state, MERGE-finalize under the
               QUERY's output names — bit-identical to folding the
               full table from scratch

Machinery reused rather than rebuilt: the r6 mergeable StateBatch wire
format and PARTIAL/MERGE AggNode stages do the folding and state
persistence; the r15/r16 datastore-backed cron runner
(vizier/cron.py, a views-prefixed CronScriptStore) makes view
definitions restart-surviving; the r7 fold-signature posture (fold
identity excludes output names) becomes a name-erased match key; the
r16 predicate normalizer (parallel/pipeline.predicate_fold_digest)
canonicalizes the predicate suffix by VALUE so dictionary growth never
flips a match.

Bit-identity contract: view-served reads equal the from-scratch fold
exactly for every order-insensitive-exact UDA lane — counts, integer
sums, float sums over exactly-representable values (the telemetry
case: durations, bytes, status codes), HLL register max, count-min
integer adds — because carried-then-delta merge preserves both the
group first-appearance order and the exact arithmetic of a single
pass. Lanes whose value depends on fold grouping (float sums over
arbitrary reals differ in final ulps) keep the same contract the
device/host split already has: test-pinned on exact-representable
data.

Match + serve: ``QueryBroker.execute_script`` probes
``ViewRegistry.try_serve`` BEFORE admission ever queues the query.
The probe is an O(1) dict lookup on the script text in steady state
(first sight of a text pays one compile+match, cached either way);
a hit requires the name-erased signature AND the predicate digest to
agree, then the carried state's out-names are positionally remapped
to the query's names for the finalize. Served queries record a
``view_hit`` rung above ``ring_hit`` on the r18 placement ladder and
stamp freshness on the QueryResult.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import threading
import time
import uuid
from typing import Any, Optional

import numpy as np

from pixie_tpu.compiler.analyzer import substitute
from pixie_tpu.exec.agg_node import AggNode, StateBatch
from pixie_tpu.exec.exec_state import FunctionContext
from pixie_tpu.exec.expression_evaluator import ExpressionEvaluator
from pixie_tpu.parallel.pipeline import (
    match_fragment,
    predicate_fold_digest,
)
from pixie_tpu.plan.operators import AggStage, MemorySinkOp, ResultSinkOp
from pixie_tpu.table.column import DictColumn, StringDictionary
from pixie_tpu.table.row_batch import RowBatch
from pixie_tpu.utils import faults, flags, metrics_registry
from pixie_tpu.vizier.cron import CronScript, CronScriptStore, ScriptRunner
from pixie_tpu.vizier.datastore import Datastore

_M = metrics_registry()
_VIEW_HITS = _M.counter(
    "broker_view_hits_total",
    "Queries answered from a materialized view's merged state before "
    "admission.",
)
_VIEW_MISSES = _M.counter(
    "broker_view_misses_total",
    "View probes that fell through to normal admission, by reason.",
)
_VIEW_STALENESS = _M.gauge(
    "view_staleness_seconds",
    "Seconds since a view's last successful maintenance (set on every "
    "maintenance and on every served read).",
)
_VIEW_MAINTAIN = _M.histogram(
    "view_maintain_seconds",
    "Wall seconds per view maintenance tick (delta fold + merge + "
    "persist).",
)
_VIEW_MAINTAIN_ERRORS = _M.counter(
    "view_maintain_errors_total",
    "View maintenance ticks that failed (the per-view breaker opens "
    "after consecutive failures; an open breaker serves nothing).",
)
_VIEW_REBUILDS = _M.counter(
    "view_rebuilds_total",
    "Carried state discarded because already-folded rows expired from "
    "the table — the from-scratch fold can no longer see them, so "
    "bit-identity demands a rebuild from the new min_row_id.",
)
_VIEW_TAIL_ROUTED = _M.counter(
    "view_tail_folds_routed_total",
    "View-hit tail delta folds attributed to the view's maintain "
    "agent (the tracker pick recorded at registration) instead of "
    "the broker, by view and agent.",
)

_SCRIPT_PREFIX = "/view_scripts/"
_STATE_PREFIX = "/view_state/"
_CHUNK_ROWS = 1 << 16
_BREAKER_THRESHOLD = 3
_PROBE_CACHE_CAP = 512
_READ_MEMO_CAP = 32


class _CaptureStats:
    total_time_ns = 0


class _Capture:
    """Duck-typed ExecNode child: collects emitted batches. Carries a
    zeroed stats shim because the parent's consume_next accounts child
    self-time over ``child.stats.total_time_ns``."""

    def __init__(self):
        self.batches: list = []
        self.stats = _CaptureStats()

    def consume_next(self, exec_state, batch, parent_index=0) -> None:
        self.batches.append(batch)


def _compile_match(broker, script: str):
    """Compile ``script`` and match the maintainable shape: ONE fragment,
    non-streaming all-time MemorySource → (Map|Filter)* → Agg(FULL,
    not windowed) → sink. Raises ValueError with the refusal reason.

    Windowed aggregation needs no special case here: a time-bucket
    group key (a Map expression over time_) is just another composed
    group expression — the view carries one state row per bucket and
    the bucketed read falls out of the ordinary merge."""
    logical = broker.compiler.compile(
        script, broker.table_relations, now_ns=0
    )
    frags = logical.fragments
    if len(frags) != 1:
        raise ValueError("view scripts must compile to one fragment")
    frag = frags[0]
    relations = frag.resolve_relations(
        broker.registry, lambda op: broker.table_relations[op.table_name]
    )
    m = match_fragment(frag, relations)
    if m is None:
        raise ValueError(
            "not a maintainable source→map/filter→agg chain"
        )
    if m.agg_op.stage != AggStage.FULL or m.agg_op.windowed:
        raise ValueError("views maintain FULL non-windowed aggregates")
    if (
        m.source_op.start_time is not None
        or m.source_op.stop_time is not None
    ):
        raise ValueError(
            "time-bounded scripts are not view-maintainable (bucket by "
            "a time key instead)"
        )
    children = frag.children(m.agg_nid)
    if len(children) != 1:
        return_err = "aggregate feeds more than one consumer"
        raise ValueError(return_err)
    sink_op = frag.node(children[0])
    if isinstance(sink_op, ResultSinkOp):
        sink_name = sink_op.table_name
    elif isinstance(sink_op, MemorySinkOp):
        sink_name = sink_op.name
    else:
        raise ValueError("aggregate must feed the result sink directly")
    pre_agg_rel = relations[frag.parents(m.agg_nid)[0]]
    out_rel = relations[m.agg_nid]
    return m, pre_agg_rel, out_rel, sink_name


def _erased_signature(m) -> str:
    """Name-erased fold-unit identity (the r7 ``_fold_signature``
    posture): table + ORDERED composed group exprs + ORDERED
    (uda, composed args, init_args) lanes, with every output name
    erased — two scripts differing only in output naming match the
    same view, and the read positionally remaps state to the query's
    names."""
    groups = [repr(m.col_exprs[g]) for g in m.agg_op.groups]
    lanes = []
    for _out, agg in m.agg_op.values:
        args = tuple(
            repr(substitute(a, m.col_exprs)) for a in agg.args
        )
        lanes.append((agg.name, args, tuple(map(repr, agg.init_args))))
    return "|".join(
        [
            "view",
            m.source_op.table_name,
            "g:" + ";".join(groups),
            "v:" + repr(lanes),
        ]
    )


def _with_flags(sb: StateBatch, eow: bool, eos: bool) -> StateBatch:
    return dataclasses.replace(sb, eow=eow, eos=eos)


_EMPTY_TRIGGER = "empty"


@dataclasses.dataclass
class _ProbeEntry:
    """Per-script-text probe cache entry (hit or remembered miss)."""

    view_id: Optional[str]  # None = miss
    miss_reason: str = ""
    # Hit side: the QUERY's own plan objects for the finalize.
    agg_op: Any = None
    pre_agg_rel: Any = None
    out_rel: Any = None
    sink_name: str = ""
    out_names: tuple = ()
    group_names: tuple = ()


class MaterializedView:
    """One maintained view: compiled match + carried PARTIAL state +
    watermark. All state transitions happen under ``_lock`` (ticks and
    reads serialize per view; reads of DIFFERENT views run freely)."""

    def __init__(self, view_id, name, script, m, pre_agg_rel, out_rel,
                 sink_name, signature, pred_digest, refresh_interval_s,
                 registry, func_ctx):
        self.view_id = view_id
        self.name = name
        self.script = script
        self.m = m
        self.table_name = m.source_op.table_name
        self.pre_agg_rel = pre_agg_rel
        self.out_rel = out_rel
        self.sink_name = sink_name
        self.signature = signature
        self.pred_digest = pred_digest
        self.refresh_interval_s = refresh_interval_s
        self.out_names = tuple(n for n, _a in m.agg_op.values)
        self.group_names = tuple(m.agg_op.groups)
        self._registry = registry
        self._func_ctx = func_ctx
        self.partial_op = dataclasses.replace(
            m.agg_op, stage=AggStage.PARTIAL
        )
        self.partial_rel = self.partial_op.output_relation(
            [pre_agg_rel], registry
        )
        # Projection to pre-agg terms + pre-agg predicates, both in
        # SOURCE terms — the same ExpressionEvaluator the host engine's
        # Map/Filter nodes run, so the folded row set is identical.
        self._proj = ExpressionEvaluator(
            [(c.name, m.col_exprs[c.name]) for c in pre_agg_rel],
            m.source_relation,
            registry,
            func_ctx,
        )
        self._pred_evs = [
            ExpressionEvaluator(
                [("p", p)], m.source_relation, registry, func_ctx
            )
            for p in m.predicates
        ]
        # Carried state (eow/eos normalized False) + coverage.
        self.state: Optional[StateBatch] = None
        self.watermark = 0
        self.base_min: Optional[int] = None
        self.last_refresh = 0.0
        self.hits = 0
        self.maintains = 0
        self.rebuilds = 0
        self.rows_folded = 0
        self.fail_count = 0
        self.breaker_open = False
        self.last_error: Optional[str] = None
        # r21: the maintain agent this view's tail folds route to —
        # the tracker pick recorded at registration (None until the
        # tracker can name an owner; re-resolved lazily on first read).
        self.maintain_agent: Optional[str] = None
        self._lock = threading.RLock()
        self._read_memo: dict = {}

    # -- fold machinery ------------------------------------------------------
    def _new_partial_node(self):
        node = AggNode(self.partial_op, self.partial_rel, 0)
        node.set_input_relation(self.pre_agg_rel, self._registry)
        cap = _Capture()
        node.add_child(cap)
        return node, cap

    def _project(self, batch: RowBatch) -> RowBatch:
        if self._pred_evs:
            mask = None
            for ev in self._pred_evs:
                m2 = ev.evaluate_predicate(batch)
                mask = m2 if mask is None else (mask & m2)
            if not mask.all():
                batch = batch.take(np.nonzero(mask)[0])
        proj = self._proj.evaluate(batch, self.pre_agg_rel)
        proj.eow = False
        proj.eos = False
        return proj

    def _fold_range(self, table, from_row, to_row):
        """PARTIAL-fold table rows [from_row, to_row) through the
        view's predicates + projection. Returns (StateBatch | None,
        rows_seen) — None when no row survived (or none existed)."""
        node, cap = self._new_partial_node()
        fed = False
        row = from_row
        rows = 0
        while row < to_row:
            batch, nxt, _ = table._read_from(row, _CHUNK_ROWS, None, None)
            if batch is None or nxt <= row:
                break
            start_id = nxt - batch.num_rows
            if nxt > to_row:
                batch = batch.slice(0, to_row - start_id)
            row = min(nxt, to_row)
            rows += batch.num_rows
            proj = self._project(batch)
            if proj.num_rows:
                node.consume_next(None, proj, 0)
                fed = True
        if not fed:
            return None, rows
        node.consume_next(
            None,
            RowBatch.with_zero_rows(self.pre_agg_rel, eos=True),
            0,
        )
        return _with_flags(cap.batches[-1], False, False), rows

    def _merge_parts(self, parts):
        """Combine StateBatches through a PARTIAL restage — carried
        FIRST, then deltas, so group first-appearance order matches a
        single pass over the full row stream."""
        parts = [p for p in parts if p is not None]
        if not parts:
            return None
        if len(parts) == 1:
            return parts[0]
        node, cap = self._new_partial_node()
        for sb in parts[:-1]:
            node.consume_next(None, _with_flags(sb, False, False), 0)
        node.consume_next(None, _with_flags(parts[-1], False, True), 0)
        return _with_flags(cap.batches[-1], False, False)

    # -- maintenance ---------------------------------------------------------
    def maintain(self, table) -> dict:
        """One tick: rebuild-if-expired guard, delta fold
        [watermark, end), merge into carried state. Caller persists."""
        t0 = time.time()
        if faults.ACTIVE:
            faults.check("views.maintain")
        with self._lock:
            mn = table.min_row_id()
            if self.base_min is None:
                self.base_min = mn
                self.watermark = mn
            elif mn > self.base_min:
                # Rows the carried state folded have expired; the
                # from-scratch fold can't see them, so neither may we.
                self.state = None
                self.base_min = mn
                self.watermark = mn
                self.rebuilds += 1
                _VIEW_REBUILDS.inc(view=self.name)
            end = table.end_row_id()
            delta, rows = self._fold_range(table, self.watermark, end)
            if delta is not None:
                self.state = self._merge_parts([self.state, delta])
            self.watermark = end
            self.rows_folded += rows
            self.last_refresh = time.time()
            self.fail_count = 0
            self.breaker_open = False
            self.last_error = None
            self._read_memo.clear()
            self.maintains += 1
        dt = time.time() - t0
        _VIEW_MAINTAIN.observe(dt, view=self.name)
        _VIEW_STALENESS.set(0.0, view=self.name)
        return {"rows": rows, "seconds": dt}

    def record_failure(self, err: Exception) -> None:
        with self._lock:
            self.fail_count += 1
            self.last_error = str(err)
            if self.fail_count >= _BREAKER_THRESHOLD:
                self.breaker_open = True
        _VIEW_MAINTAIN_ERRORS.inc(view=self.name)

    # -- persistence ---------------------------------------------------------
    def envelope(self) -> bytes:
        with self._lock:
            # String group-key columns persist their exact (codes,
            # dictionary) pair alongside the StateBatch payload: the
            # generic wire codec rebuilds string keys through a fresh
            # dictionary whose small-array encode path assigns codes in
            # VALUE-sorted order, and the MERGE stage's gid assignment
            # sorts by code — a recovered state would then finalize its
            # groups in a different order than the live one, breaking
            # restart bit-identity. Restoring codes verbatim keeps the
            # recovered merge permutation-identical.
            string_keys = []
            if self.state is not None:
                for col in self.state.key_columns:
                    if isinstance(col, DictColumn):
                        string_keys.append({
                            "codes": np.asarray(col.codes).tolist(),
                            "values": col.dictionary.values(),
                        })
                    else:
                        string_keys.append(None)
            meta = {
                "view_id": self.view_id,
                "name": self.name,
                "signature": self.signature,
                "pred_digest": self.pred_digest,
                "watermark": int(self.watermark),
                "base_min": (
                    int(self.base_min) if self.base_min is not None
                    else None
                ),
                "last_refresh": float(self.last_refresh),
                "string_keys": string_keys,
            }
            body = self.state.to_bytes() if self.state is not None else b""
        return json.dumps(meta).encode() + b"\x00" + body

    def recover(self, raw: bytes) -> bool:
        """Adopt a persisted envelope. False (start cold) when the
        stored signature/digest no longer matches the recompiled
        definition — a changed script must never serve stale state."""
        try:
            head, _sep, body = raw.partition(b"\x00")
            meta = json.loads(head)
            if (
                meta.get("signature") != self.signature
                or meta.get("pred_digest") != self.pred_digest
                or meta.get("base_min") is None
            ):
                return False
            state = StateBatch.from_bytes(body) if body else None
            if state is not None:
                for i, spec in enumerate(meta.get("string_keys") or []):
                    if spec is not None:
                        state.key_columns[i] = DictColumn(
                            np.asarray(spec["codes"], dtype=np.int32),
                            StringDictionary(list(spec["values"])),
                        )
        except Exception:
            return False
        with self._lock:
            self.state = (
                _with_flags(state, False, False)
                if state is not None else None
            )
            self.watermark = int(meta["watermark"])
            self.base_min = int(meta["base_min"])
            self.last_refresh = float(meta.get("last_refresh", 0.0))
        return True

    # -- read ----------------------------------------------------------------
    def _rename(self, sb: StateBatch, out_names, group_names):
        """Positionally remap a carried/delta StateBatch onto the
        QUERY's output and group names (the signature guarantees lane
        order and group order agree)."""
        if sb is None:
            return None
        states = {
            qn: sb.states[vn]
            for qn, vn in zip(out_names, self.out_names)
        }
        arg_dicts = {
            qn: sb.arg_dicts[vn]
            for qn, vn in zip(out_names, self.out_names)
            if vn in sb.arg_dicts
        }
        return StateBatch(
            key_columns=sb.key_columns,
            states=states,
            num_groups=sb.num_groups,
            group_names=tuple(group_names),
            eow=False,
            eos=False,
            arg_dicts=arg_dicts,
        )

    def read(self, table, entry: _ProbeEntry, tail_agent=None,
             tail_wrap=None):
        """Serve one query: carried state ⊕ tail delta fold, MERGE-
        finalized under the query's names. Returns (RowBatch, freshness
        dict) or (None, reason) when the view cannot serve. When
        ``tail_wrap`` is given the tail fold runs through it (r21 view
        admission placement: the registry attributes the fold to the
        view's maintain agent); memo hits never re-enter the wrapper."""
        with self._lock:
            if self.breaker_open:
                return None, "breaker_open"
            if self.maintains == 0 and self.state is None:
                return None, "cold"
            staleness = time.time() - self.last_refresh
            rail = flags.view_max_staleness_s
            if rail and staleness > rail:
                return None, "stale"
            end = table.end_row_id()
            memo_key = (
                self.watermark, end, entry.out_names, entry.group_names,
            )
            memo = self._read_memo.get(memo_key)
            if memo is None:
                def _fold():
                    return self._fold_range(table, self.watermark, end)

                if tail_wrap is not None:
                    tail, tail_rows = tail_wrap(_fold)
                else:
                    tail, tail_rows = _fold()
                carried = self._rename(
                    self.state, entry.out_names, entry.group_names
                )
                tail = self._rename(
                    tail, entry.out_names, entry.group_names
                )
                parts = [p for p in (carried, tail) if p is not None]
                merge_op = dataclasses.replace(
                    entry.agg_op,
                    stage=AggStage.MERGE,
                    pre_agg_relation=entry.pre_agg_rel,
                )
                node = AggNode(merge_op, entry.out_rel, 0)
                node.set_input_relation(
                    merge_op.merge_input_relation(entry.pre_agg_rel),
                    self._registry,
                )
                cap = _Capture()
                node.add_child(cap)
                if not parts:
                    # Zero groups observed: an empty eos StateBatch
                    # still triggers the emit, reproducing the host
                    # engine's empty-input semantics exactly (0 rows
                    # grouped; one identity row group-by-none).
                    node.consume_next(
                        None,
                        StateBatch(
                            key_columns=[], states={}, num_groups=0,
                            group_names=tuple(entry.group_names),
                            eow=False, eos=True,
                        ),
                        0,
                    )
                else:
                    for sb in parts[:-1]:
                        node.consume_next(None, sb, 0)
                    node.consume_next(
                        None, _with_flags(parts[-1], False, True), 0
                    )
                batch = cap.batches[-1]
                memo = (batch, end - self.watermark)
                if len(self._read_memo) >= _READ_MEMO_CAP:
                    self._read_memo.pop(next(iter(self._read_memo)))
                self._read_memo[memo_key] = memo
            batch, tail_rows = memo
            self.hits += 1
            wm = self.watermark
        _VIEW_STALENESS.set(staleness, view=self.name)
        return batch, {
            "view": self.name,
            "view_id": self.view_id,
            "staleness_s": staleness,
            "watermark": int(wm),
            "tail_rows": int(tail_rows),
            "tail_agent": tail_agent,
        }

    def status(self, table=None) -> dict:
        with self._lock:
            return {
                "view_id": self.view_id,
                "name": self.name,
                "table": self.table_name,
                "sink": self.sink_name,
                "signature": self.signature,
                "pred_digest": self.pred_digest,
                "watermark": int(self.watermark),
                "end_row_id": (
                    int(table.end_row_id()) if table is not None else None
                ),
                "groups": int(self.state.num_groups)
                if self.state is not None else 0,
                "staleness_s": (
                    time.time() - self.last_refresh
                    if self.maintains else None
                ),
                "refresh_interval_s": self.refresh_interval_s,
                "hits": self.hits,
                "maintains": self.maintains,
                "rebuilds": self.rebuilds,
                "rows_folded": self.rows_folded,
                "breaker_open": self.breaker_open,
                "fail_count": self.fail_count,
                "last_error": self.last_error,
            }


class ViewRegistry:
    """The broker's materialized-view plane: registration, persisted
    maintenance ticks (datastore-backed cron runner), and the
    pre-admission serve probe.

    In-process placement posture: maintenance folds run in this
    process against the shared TableStore — the agent whose ring
    holds the table (the broker tracker's ownership view, surfaced
    per view in /viewz as ``maintain_agent``) is where that work
    lands in a multi-process deployment."""

    def __init__(self, broker, table_store, datastore=None,
                 owner_fn=None):
        self._broker = broker
        self._tables = table_store
        self._ds = datastore if datastore is not None else Datastore()
        self._registry = broker.registry
        self._func_ctx = FunctionContext(
            table_store=table_store, registry=broker.registry
        )
        self._owner_fn = owner_fn
        self.store = CronScriptStore(self._ds, prefix=_SCRIPT_PREFIX)
        self.runner = ScriptRunner(broker, self.store, executor=self._tick)
        self._lock = threading.RLock()
        self._views: dict[str, MaterializedView] = {}
        self._by_key: dict[tuple, str] = {}  # (signature, digest) -> id
        self._probe_cache: dict[str, _ProbeEntry] = {}
        self.hits = 0
        self.misses = 0

    # -- lifecycle -----------------------------------------------------------
    def attach(self) -> "ViewRegistry":
        """Recover persisted view definitions + state, then start the
        tickers (restart survival: the first read after recovery folds
        only [persisted watermark, end) — never a full refold)."""
        for sid, cs in self.store.all().items():
            try:
                self._ensure_view(sid, cs)
            except Exception:
                # A definition that no longer compiles (schema drift)
                # must not take the registry down; it just won't serve.
                continue
        self.runner.sync()
        return self

    def stop(self) -> None:
        self.runner.stop()

    def _ensure_view(self, view_id: str, cs: CronScript):
        with self._lock:
            view = self._views.get(view_id)
            if view is not None and view.script == cs.script:
                return view
            m, pre_rel, out_rel, sink = _compile_match(
                self._broker, cs.script
            )
            sig = _erased_signature(m)
            digest = predicate_fold_digest(
                m.predicates, m.source_relation, self._registry,
                self._func_ctx,
            )
            if digest is None:
                raise ValueError(
                    "predicates outside the normalizable class cannot "
                    "key a view"
                )
            view = MaterializedView(
                view_id,
                cs.configs.get("name") or view_id,
                cs.script, m, pre_rel, out_rel, sink, sig, digest,
                cs.frequency_s, self._registry, self._func_ctx,
            )
            raw = self._ds.get(_STATE_PREFIX + view_id)
            if raw is not None:
                view.recover(raw)
            # r21: record the maintain-agent pick at registration —
            # tail folds on read route to it (view admission placement).
            view.maintain_agent = self._maintain_agent(view.table_name)
            self._views[view_id] = view
            self._by_key[(sig, digest)] = view_id
            self._probe_cache.clear()
            return view

    # -- registration --------------------------------------------------------
    def register(self, script: str, name: Optional[str] = None,
                 refresh_interval_s: Optional[float] = None) -> str:
        """Validate + persist + schedule a view; runs one synchronous
        maintenance so the view serves immediately. Raises ValueError
        for unsupported shapes. Idempotent: the view id derives from
        the name-erased identity, so re-registering an equivalent
        script upserts."""
        m, _pre, _out, _sink = _compile_match(self._broker, script)
        sig = _erased_signature(m)
        digest = predicate_fold_digest(
            m.predicates, m.source_relation, self._registry,
            self._func_ctx,
        )
        if digest is None:
            raise ValueError(
                "predicates outside the normalizable class cannot key "
                "a view"
            )
        view_id = "view-" + hashlib.sha256(
            (sig + "\x00" + digest).encode()
        ).hexdigest()[:12]
        cs = CronScript(
            view_id,
            script,
            refresh_interval_s
            if refresh_interval_s is not None
            else flags.view_refresh_interval_s,
            {"name": name or view_id},
        )
        self._ensure_view(view_id, cs)
        self.runner.upsert_script(cs)
        self._tick(cs)
        return view_id

    def unregister(self, view_id: str) -> None:
        with self._lock:
            self.runner.delete_script(view_id)
            self._ds.delete(_STATE_PREFIX + view_id)
            view = self._views.pop(view_id, None)
            if view is not None:
                self._by_key.pop(
                    (view.signature, view.pred_digest), None
                )
            self._probe_cache.clear()

    # -- maintenance (ScriptRunner executor) ---------------------------------
    def _tick(self, cs: CronScript) -> None:
        view = self._ensure_view(cs.script_id, cs)
        table = self._tables.get_table(view.table_name)
        if table is None:
            view.record_failure(
                ValueError(f"table {view.table_name!r} not found")
            )
            raise ValueError(f"table {view.table_name!r} not found")
        try:
            view.maintain(table)
            self._ds.set(_STATE_PREFIX + view.view_id, view.envelope())
        except Exception as e:
            view.record_failure(e)
            raise

    # -- serve probe ---------------------------------------------------------
    def _probe_compile(self, query: str) -> _ProbeEntry:
        try:
            m, pre_rel, out_rel, sink = _compile_match(
                self._broker, query
            )
            sig = _erased_signature(m)
            digest = predicate_fold_digest(
                m.predicates, m.source_relation, self._registry,
                self._func_ctx,
            )
        except Exception:
            return _ProbeEntry(None, miss_reason="no_match")
        if digest is None:
            return _ProbeEntry(None, miss_reason="predicates")
        view_id = self._by_key.get((sig, digest))
        if view_id is None:
            reason = (
                "digest_mismatch"
                if any(
                    k[0] == sig for k in self._by_key
                )
                else "no_view"
            )
            return _ProbeEntry(None, miss_reason=reason)
        return _ProbeEntry(
            view_id,
            agg_op=m.agg_op,
            pre_agg_rel=pre_rel,
            out_rel=out_rel,
            sink_name=sink,
            out_names=tuple(n for n, _a in m.agg_op.values),
            group_names=tuple(m.agg_op.groups),
        )

    def try_serve(self, query: str, tenant: str = "default"):
        """The pre-admission probe: O(1) text lookup in steady state.
        Returns a QueryResult (freshness-stamped) or None to fall
        through to normal admission + execution."""
        from pixie_tpu.engine import QueryResult

        with self._lock:
            entry = self._probe_cache.get(query)
            if entry is None:
                entry = self._probe_compile(query)
                if len(self._probe_cache) >= _PROBE_CACHE_CAP:
                    self._probe_cache.pop(next(iter(self._probe_cache)))
                self._probe_cache[query] = entry
            if entry.view_id is None:
                self.misses += 1
                _VIEW_MISSES.inc(reason=entry.miss_reason)
                return None
            view = self._views.get(entry.view_id)
        if view is None:
            self.misses += 1
            _VIEW_MISSES.inc(reason="unregistered")
            return None
        table = self._tables.get_table(view.table_name)
        if table is None:
            self.misses += 1
            _VIEW_MISSES.inc(reason="no_table")
            return None
        t0 = time.perf_counter_ns()
        tail_agent, tail_wrap = self._tail_route(view)
        batch, info = view.read(
            table, entry, tail_agent=tail_agent, tail_wrap=tail_wrap
        )
        if batch is None:
            self.misses += 1
            _VIEW_MISSES.inc(reason=info)
            return None
        self.hits += 1
        _VIEW_HITS.inc(view=view.name, tenant=tenant)
        result = QueryResult(
            query_id=str(uuid.uuid4()),
            tables={entry.sink_name: [batch]},
            exec_stats={},
            compile_time_ns=0,
            exec_time_ns=time.perf_counter_ns() - t0,
        )
        result.view = info
        return result

    # -- tail-fold routing (r21) ---------------------------------------------
    def _tail_route(self, view: MaterializedView):
        """Resolve where a view hit's unflushed-tail delta fold is
        attributed. Returns (agent_id, wrap) — (None, None) when the
        flag is off or no maintain agent is known. The wrap charges
        the fold to the maintain agent's WFQ load / inflight / table
        heat for exactly the duration of the fold, so the rebalancer
        and the placement ladder see the tail work where the r18
        posture says it belongs — never the broker."""
        if not flags.view_tail_placement:
            return None, None
        agent = view.maintain_agent
        if agent is None:
            # Registration may have preceded agent discovery; adopt
            # the tracker's current pick once it can name an owner.
            agent = view.maintain_agent = self._maintain_agent(
                view.table_name
            )
        if agent is None:
            return None, None
        placement = getattr(self._broker, "placement", None)

        def wrap(fold, _agent=agent, _view=view, _placement=placement):
            if _placement is not None:
                _placement.route_view_tail(
                    _agent, frozenset([_view.table_name])
                )
            try:
                return fold()
            finally:
                if _placement is not None:
                    _placement.release(_agent)
                _VIEW_TAIL_ROUTED.inc(view=_view.name, agent=_agent)

        return agent, wrap

    # -- observability -------------------------------------------------------
    def _maintain_agent(self, table_name: str) -> Optional[str]:
        if self._owner_fn is not None:
            try:
                return self._owner_fn(table_name)
            except Exception:
                return None
        try:
            # The r18 posture: maintenance work belongs on the agent
            # whose ring holds the table (failover_view carries the
            # ownership sets the placement ladder ranks on).
            for a in self._broker.tracker.failover_view():
                if table_name in (a.get("tables") or set()):
                    return a.get("agent_id")
        except Exception:
            pass
        return None

    def status(self) -> dict:
        with self._lock:
            views = list(self._views.values())
            hits, misses = self.hits, self.misses
        out = []
        for v in views:
            s = v.status(self._tables.get_table(v.table_name))
            s["maintain_agent"] = (
                v.maintain_agent or self._maintain_agent(v.table_name)
            )
            out.append(s)
        total = hits + misses
        return {
            "enabled": bool(flags.materialized_views),
            "views": out,
            "hits": hits,
            "misses": misses,
            "hit_rate": (hits / total) if total else 0.0,
        }
