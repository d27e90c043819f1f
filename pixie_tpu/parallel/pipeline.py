"""The compiled device pipeline: source→map/filter→aggregate in ONE XLA
program over the mesh.

This is the TPU offload named in BASELINE.json: the exec-graph (host) path
stays the control/fallback engine, while fragments matching the hot shape

    MemorySource → (Map | Filter)* → Agg(FULL, not windowed)

(or a fan-out of that chain into several such aggregations, staged once)
compile into a single jit(shard_map(...)): each device lax.scans its shard
of staged blocks, evaluating the fused projection/predicate expressions and
updating UDA states via masked segment reductions; then one collective per
UDA merges states over ICI (lax.psum/pmax/pmin for elementwise MergeKinds,
all_gather + tree fold for TREE sketches like t-digest). Host work is
limited to dictionary LUTs, gid densification for non-string keys, staging,
and finalize.

Ref mapping: per-device scan ≙ the PEM pre-blocking fragment
(splitter.h:52); the collective ≙ Kelvin's cross-PEM merge
(partial_op_mgr.h:94 + the gRPC data plane it rides in the reference).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import re
import threading
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pixie_tpu.compiler.analyzer import substitute
from pixie_tpu.exec.expression_evaluator import ExpressionEvaluator
from pixie_tpu.exec.group_encoder import GroupEncoder
from pixie_tpu.parallel.staging import (
    DEFAULT_BLOCK_ROWS,
    _pow2_at_least,
    read_columns,
    stage_columns,
    stage_gids,
)
from pixie_tpu.plan.expressions import (
    AggregateExpression,
    ColumnRef,
    Constant,
    FuncCall,
    expr_data_type,
    referenced_columns,
    walk,
)
from pixie_tpu.plan.operators import (
    AggOp,
    AggStage,
    FilterOp,
    JoinOp,
    JoinType,
    LimitOp,
    MapOp,
    MemorySourceOp,
)
from pixie_tpu.plan.plan import PlanFragment
from pixie_tpu.table.column import DictColumn, StringDictionary
from pixie_tpu.table.row_batch import RowBatch
from pixie_tpu.table.table import DEFAULT_COMPACTED_ROWS
from pixie_tpu.types import DataType
from pixie_tpu.types.dtypes import host_dtype
from pixie_tpu.udf.udf import Executor, MergeKind
from pixie_tpu.parallel import profiler as resattr
from pixie_tpu.distributed import mesh as mesh_lib
from pixie_tpu.utils import faults, flags, metrics_registry, trace

_M = metrics_registry()
_OFFLOAD_HITS = _M.counter(
    "device_offload_total", "Fragments executed on the device mesh."
)
_OFFLOAD_MISS = _M.counter(
    "device_offload_unmatched_total",
    "Fragments that did not match the device-offloadable shape.",
)
_OFFLOAD_FALLBACKS = _M.counter(
    "device_offload_fallback_total",
    "Device offload attempts that failed and fell back to the host engine.",
)
_BREAKER_TRIPS = _M.counter(
    "device_offload_fallback_breaker_trips_total",
    "Circuit-breaker trips: N consecutive device failures sent a program "
    "key to the host engine for a cooldown.",
)
_BREAKER_SKIPS = _M.counter(
    "device_offload_fallback_breaker_open_total",
    "Fragments routed straight to the host engine because their program "
    "key's circuit breaker was open.",
)
_PROGRAMS = _M.gauge(
    "device_program_cache_size", "Compiled shard_map programs cached."
)
_AOT_PENDING = _M.gauge(
    "device_aot_pending",
    "Background (AOT) program compiles submitted and not yet finished, "
    "over every executor in the process.",
)
_MESH_DEGRADE = _M.counter(
    "mesh_degrade_events_total",
    "Mesh geometry failures (host loss / hung collective) recovered by "
    "re-planning the fold onto the next degradation rung (r23; the "
    "retried answer is bit-identical by the r21 geometry invariant).",
)
_MESH_CKPT_WINDOWS = _M.counter(
    "mesh_checkpoint_windows_total",
    "Stream-fold windows whose carried UDA state was checkpointed "
    "host-side at the window boundary (flag mesh_fold_checkpoint).",
)
_MESH_RESUMES = _M.counter(
    "mesh_checkpoint_resumes_total",
    "Stream folds resumed from a window checkpoint on a surviving "
    "geometry instead of refolding from scratch.",
)

# One multi-axis collective program in flight per process: two
# concurrent all-device programs interleave their per-device executions
# in different orders and deadlock the rendezvous (observed on the
# 8-virtual-device CPU sim the moment two executors folded at
# hosts:2,d:4 at once). Flat single-axis dispatches carry no cross-host
# rendezvous and never take this lock.
_MESH_COLLECTIVE_LOCK = threading.Lock()

# Persistent-compilation-cache hit counter: jax emits a monitoring event
# per .jax_cache deserialization; the AOT compile thread snapshots it
# around each compile so the ledger's compile_cache_hit key is honest
# (a hit = the bucketed signature reproduced a prior round's HLO).
_PERSISTENT_CACHE_HITS = [0]


def _on_jax_monitoring_event(event, *args, **kwargs):
    if event == "/jax/compilation_cache/cache_hits":
        _PERSISTENT_CACHE_HITS[0] += 1


try:
    jax.monitoring.register_event_listener(_on_jax_monitoring_event)
except Exception:  # pragma: no cover - monitoring API drift
    pass

# Cold-path phase timings live in staging (shared with the transfer
# layer); re-exported here for callers.
from pixie_tpu.parallel.staging import (  # noqa: E402
    COLD_PROFILE,
    count_device_aggs,
    count_key_evals,
    count_read_batches,
    count_read_visits,
    reset_cold_profile,
    timed as _timed,
)


def _f64_as_bits(cols: dict) -> dict:
    """Float64 columns as their int64 bit patterns, for columns the device
    only moves (join payload). The TPU emulates f64 with f32 pairs, so an
    f64 value gathered there came back with its low mantissa bits changed
    (PR 21's chip smoke); int64 bits pass through exactly, and the host
    views them back (_f64_from_bits)."""
    return {
        c: a.view(np.int64)
        if isinstance(a, np.ndarray) and a.dtype == np.float64
        else a
        for c, a in cols.items()
    }


def _f64_from_bits(a: np.ndarray, dt) -> np.ndarray:
    if dt == DataType.FLOAT64:
        return a.astype(np.int64).view(np.float64)
    return a.astype(host_dtype(dt))


@dataclasses.dataclass
class _Match:
    source_nid: int
    agg_nid: int
    source_op: MemorySourceOp
    agg_op: AggOp
    col_exprs: dict[str, Any]   # pre-agg column name -> expr in source terms
    predicates: list            # filter exprs in source terms
    source_relation: Any


@dataclasses.dataclass
class _Branch:
    """One matched aggregation's device plan (MeshExecutor._plan_branch)."""

    m: _Match
    specs: list
    evaluator: Any
    windowed: bool
    key_plan: Any
    n_windows: int
    base_groups: int
    host_any: dict
    device_specs: list
    base_cols: set  # source columns it stages
    cell_cols: dict
    f32_cols: set
    key_sig: str
    cacheable: bool


def match_fragment(fragment: PlanFragment, relations) -> Optional[_Match]:
    """Find the source→(map|filter)*→agg chain, composing expressions into
    source-column terms along the way."""
    agg_nid = None
    for nid in fragment.topo_order():
        op = fragment.node(nid)
        # FULL aggs finalize on device (windowed ones too, r5: the window
        # id becomes a second group axis and each window emits its own
        # batch); PARTIAL aggs (the PEM side of a distributed split) ship
        # raw states to the merge stage — windowed PARTIALs stay on the
        # host, whose eow-driven StateBatch cadence the merge consumes.
        if isinstance(op, AggOp) and (
            op.stage == AggStage.FULL
            or (op.stage == AggStage.PARTIAL and not op.windowed)
        ):
            agg_nid = nid
            break
    if agg_nid is None:
        return None
    path = _path_to_source(fragment, agg_nid)
    if path is None:
        return None
    source_nid, chain = path
    if any(len(fragment.children(n)) != 1 for n in [source_nid, *chain]):
        # Shared with another branch: a fan-out of aggregations is
        # match_fanout's; any other sharing is the host engine's job.
        return None
    if fragment.node(source_nid).streaming:
        return None  # streaming stays with the live host cursor
    return _compose_match(fragment, relations, source_nid, chain, agg_nid)


def _path_to_source(fragment: PlanFragment, agg_nid: int):
    """(source nid, [map/filter nids from the source down]) of the chain
    that feeds ``agg_nid``, or None when it is not such a chain."""
    chain = []
    cur = agg_nid
    while True:
        parents = fragment.parents(cur)
        if len(parents) != 1:
            return None
        cur = parents[0]
        op = fragment.node(cur)
        if isinstance(op, MemorySourceOp):
            return cur, chain[::-1]
        if not isinstance(op, (MapOp, FilterOp)):
            return None
        chain.append(cur)


def _compose_match(fragment, relations, source_nid, chain, agg_nid) -> _Match:
    """Compose the chain's predicates and column expressions into
    source-column terms."""
    source_rel = relations[source_nid]
    mapping = {c.name: ColumnRef(c.name) for c in source_rel}
    preds = []
    for nid in chain:
        op = fragment.node(nid)
        if isinstance(op, FilterOp):
            preds.append(substitute(op.expr, mapping))
        else:
            mapping = {
                name: substitute(e, mapping) for name, e in op.exprs
            }
    return _Match(
        source_nid=source_nid,
        agg_nid=agg_nid,
        source_op=fragment.node(source_nid),
        agg_op=fragment.node(agg_nid),
        col_exprs=mapping,
        predicates=preds,
        source_relation=source_rel,
    )


def match_fanout(fragment: PlanFragment, relations) -> Optional[list[_Match]]:
    """The matches of every aggregation of a fan-out: one non-streaming
    MemorySource whose (Map | Filter)* chain forks into two or more
    branches, each (Map | Filter)* → Agg (not windowed). Every node with
    more than one child must have only children that lead, through maps
    and filters, to one of those aggregations. Any other shape (a branch
    that displays raw rows, a node shared with a join, a streaming
    source, a windowed aggregation) returns None: the host engine runs
    the fragment whole. Each branch's composed terms include the shared
    prefix."""
    paths = {}
    for nid in fragment.topo_order():
        op = fragment.node(nid)
        if not isinstance(op, AggOp) or op.windowed or op.stage not in (
            AggStage.FULL,
            AggStage.PARTIAL,
        ):
            continue
        path = _path_to_source(fragment, nid)
        if path is not None:
            paths[nid] = path
    sources = {src for src, _ in paths.values()}
    if len(paths) < 2 or len(sources) != 1:
        return None
    (source_nid,) = sources
    if fragment.node(source_nid).streaming:
        return None
    on_path = {source_nid, *paths}
    for _, chain in paths.values():
        on_path.update(chain)
    for nid in on_path.difference(paths):
        if any(c not in on_path for c in fragment.children(nid)):
            return None
    return [
        _compose_match(fragment, relations, source_nid, chain, agg_nid)
        for agg_nid, (_, chain) in paths.items()
    ]


# -- predicate normalization (r16; module-level since r20) -------------------
# Lowers conjunctive predicate trees to data terms
# ``(stack, column, op, int_thr, flt_thr, in_vals)``. One normalizer,
# three consumers with the identical refusal class: the predicate-batched
# shared scans (MeshExecutor), the r20 join-side pushdown, and the
# materialized-view predicate digest (serving/views.py).

_CMP_OPS = {
    "equal": 0, "notEqual": 1,
    "lessThan": 2, "lessThanEqual": 3,
    "greaterThan": 4, "greaterThanEqual": 5,
}
# const-on-the-left flips the comparison, not the operands.
_CMP_FLIP = {0: 0, 1: 1, 2: 4, 3: 5, 4: 2, 5: 3}


def normalize_predicates(predicates, evaluator, staged, aux):
    """Lower ``predicates`` to conjunctive data terms
    ``(stack, column, op, int_thr, flt_thr, in_vals)`` — or None
    when any predicate falls outside the normalizable class (the
    query then only shares via the identical-signature ladder).

    The class is a direct comparison of a staged column against a
    constant (either order), a bare boolean column, a conjunction
    (logical_and splits into more terms), and — r18 — an IN-list:
    a logical_or tree whose leaves are all ``equal(same_col,
    const)`` folds into ONE membership term (op 6) whose values
    ride a per-term LUT lane in the batched fold, so IN-heavy
    query families join predicate batches instead of falling back
    to solo folds; and — r22 — a LUT-backed host-func predicate
    (``f(col)`` or ``cmp(f(col), c)`` over a dictionary column,
    via ``_lut_pred_term``), which collapses to the op-6
    membership of the codes the precomputed per-value table
    keeps. Exactness contract per term: int/bool/code
    columns compare in int64 (every staged int value and
    dictionary code fits exactly); float columns compare in
    float64 with the threshold pre-rounded through the column's
    STAGED dtype (an f32-staged column's serial comparison happens
    in f32 — float64(f32(c)) preserves both its equalities and its
    ordering, so the batched mask is bit-equal). Float IN-lists
    are refused (the serial OR-of-equals is exact, but folding it
    through one LUT dtype is not worth proving). String constants
    ride as their dictionary code from the aux table (-1 for
    unseen: equal to nothing, exactly the serial code-compare
    semantics — including inside an IN LUT, where -1 matches no
    row code); columns re-encoded for the cell lane (int_dicts)
    hold codes the serial path would ALSO compare raw, so they are
    refused rather than guessed at."""
    terms = []
    for p in predicates:
        if not _normalize_pred(p, evaluator, staged, aux, terms):
            return None
    return terms


def _normalize_pred(p, evaluator, staged, aux, terms):
    """Normalize one predicate tree into ``terms``. True on
    success; False means the whole attempt is refused."""
    if isinstance(p, ColumnRef):
        if (
            p.name not in staged.blocks
            or p.name in staged.int_dicts
            or np.dtype(staged.blocks[p.name].dtype) != np.bool_
        ):
            return False
        terms.append(("i", p.name, 1, 0, 0.0, ()))  # col != 0
        return True
    if not isinstance(p, FuncCall):
        return False
    if p.name == "logical_and" and len(p.args) == 2:
        # A conjunction is just more terms.
        return _normalize_pred(
            p.args[0], evaluator, staged, aux, terms
        ) and _normalize_pred(
            p.args[1], evaluator, staged, aux, terms
        )
    if p.name == "logical_or" and len(p.args) == 2:
        t = _in_list_term(p, evaluator, staged, aux)
        if t is None:
            return False
        terms.append(t)
        return True
    t = _lut_pred_term(p, evaluator, staged, aux)
    if t is not None:
        terms.append(t)
        return True
    if len(p.args) != 2:
        return False
    op = _CMP_OPS.get(p.name)
    if op is None:
        return False
    a0, a1 = p.args
    if isinstance(a0, ColumnRef) and isinstance(a1, Constant):
        col, const = a0, a1
    elif isinstance(a1, ColumnRef) and isinstance(a0, Constant):
        col, const = a1, a0
        op = _CMP_FLIP[op]
    else:
        return False
    if col.name not in staged.blocks or (
        col.name in staged.int_dicts
    ):
        return False
    resolved = evaluator._resolved.get(id(p))
    if resolved is None:
        return False
    _udf, arg_types = resolved
    t0 = arg_types[0]
    bdt = np.dtype(staged.blocks[col.name].dtype)
    if t0 == DataType.STRING:
        if op > 1:
            return False  # only ==/!= have code-space semantics
        code = aux.get(f"const:{id(const)}")
        if code is None:
            return False
        terms.append(("i", col.name, op, int(code), 0.0, ()))
    elif t0 == DataType.FLOAT64:
        v = const.value
        if not isinstance(
            v, (int, float, np.floating, np.integer)
        ) or isinstance(v, bool):
            return False
        if bdt == np.float32:
            thr = float(np.float64(np.float32(v)))
        elif bdt == np.float64:
            thr = float(v)
        else:
            return False
        terms.append(("f", col.name, op, 0, thr, ()))
    elif t0 in (
        DataType.INT64, DataType.TIME64NS, DataType.BOOLEAN,
    ):
        if bdt.kind == "f":
            return False
        try:
            thr = int(const.value)
        except (TypeError, ValueError):
            return False
        if not (-(1 << 63) <= thr < (1 << 63)):
            return False
        terms.append(("i", col.name, op, thr, 0.0, ()))
    else:
        return False
    return True


def _in_list_term(p, evaluator, staged, aux):
    """Fold a ``logical_or`` tree whose leaves are all
    ``equal(same_col, const)`` into one membership term
    ``("i", col, 6, 0, 0.0, codes)`` — the compiler lowers
    ``col in [a, b, ...]`` to exactly this shape. None refuses."""
    leaves = []
    stack = [p]
    while stack:
        n = stack.pop()
        if (
            isinstance(n, FuncCall)
            and n.name == "logical_or"
            and len(n.args) == 2
        ):
            stack.extend(n.args)
        else:
            leaves.append(n)
    col_name = None
    vals = []
    for leaf in leaves:
        if (
            not isinstance(leaf, FuncCall)
            or leaf.name != "equal"
            or len(leaf.args) != 2
        ):
            return None
        a0, a1 = leaf.args
        if isinstance(a0, ColumnRef) and isinstance(a1, Constant):
            col, const = a0, a1
        elif isinstance(a1, ColumnRef) and isinstance(a0, Constant):
            col, const = a1, a0
        else:
            return None
        if col_name is None:
            col_name = col.name
        elif col.name != col_name:
            return None
        if col.name not in staged.blocks or (
            col.name in staged.int_dicts
        ):
            return None
        resolved = evaluator._resolved.get(id(leaf))
        if resolved is None:
            return None
        _udf, arg_types = resolved
        t0 = arg_types[0]
        if t0 == DataType.STRING:
            code = aux.get(f"const:{id(const)}")
            if code is None:
                return None
            vals.append(int(code))
        elif t0 in (
            DataType.INT64, DataType.TIME64NS, DataType.BOOLEAN,
        ):
            if np.dtype(staged.blocks[col.name].dtype).kind == "f":
                return None
            try:
                v = int(const.value)
            except (TypeError, ValueError):
                return None
            if not (-(1 << 63) <= v < (1 << 63)):
                return None
            vals.append(v)
        else:
            return None  # float IN-lists are refused
    if col_name is None or not vals:
        return None
    # Membership is order/multiplicity-insensitive; sort+dedup so
    # equivalent IN-lists share one slot under the exact-key ladder.
    return ("i", col_name, 6, 0, 0.0, tuple(sorted(set(vals))))


# numpy mirrors of the device comparison ids — x64 is enabled globally
# (pixie_tpu/__init__), so host-numpy and on-device jnp comparisons of
# the same LUT values against the same scalar agree bitwise.
_NP_CMP = {
    0: np.equal, 1: np.not_equal, 2: np.less,
    3: np.less_equal, 4: np.greater, 5: np.greater_equal,
}
# Bound on the op-6 lane width a LUT predicate may demand: a predicate
# keeping more dictionary values than this refuses normalization (the
# query still folds solo) rather than inflating the batched fold's L
# bucket for every co-batched query.
_LUT_PRED_MAX_KEPT = 1024


def _lut_pred_term(p, evaluator, staged, aux):
    """r22 (r18 carry-over): lower a LUT-backed host-func predicate to
    one membership term. Two shapes: a bare boolean host func over one
    dictionary column (``f(col)`` whose aux table ``lut:{id(p)}`` was
    precomputed by ``build_aux``) and a comparison of such a func
    against a numeric constant (``cmp(f(col), c)``, either order).
    Both reduce to the SET OF DICTIONARY CODES the predicate keeps —
    an op-6 membership term over the column's code block. This is
    bit-equal to the solo device path by construction: the solo fold
    gathers the SAME per-code table and masks on (a comparison of) the
    gathered value, so row code ``k`` survives there iff ``lut[k]``
    passes — exactly membership of ``k`` in the kept set (an empty
    kept set keeps nothing on both paths). None refuses: no LUT in
    ``aux`` (host/digest shim, or not dict_compatible), a non-bool LUT
    on the bare shape, string/bool constants, or a kept set wider than
    the op-6 lane cap."""
    op = _CMP_OPS.get(p.name)
    const = None
    if op is not None and len(p.args) == 2:
        a0, a1 = p.args
        if isinstance(a0, FuncCall) and isinstance(a1, Constant):
            f_expr, const = a0, a1
        elif isinstance(a1, FuncCall) and isinstance(a0, Constant):
            f_expr, const = a1, a0
            op = _CMP_FLIP[op]
        else:
            return None
    elif f"lut:{id(p)}" in aux:
        f_expr, op = p, None  # bare boolean func: keep where truthy
    else:
        return None
    lut = aux.get(f"lut:{id(f_expr)}")
    if lut is None:
        return None
    cols = [a for a in f_expr.args if isinstance(a, ColumnRef)]
    if len(cols) != 1:
        return None
    col = cols[0]
    if col.name not in staged.blocks or col.name in staged.int_dicts:
        return None
    lut = np.asarray(lut)
    if lut.ndim != 1 or lut.dtype.kind not in "bif":
        return None
    if op is None:
        # Bare predicate: the solo path ANDs the gathered value into a
        # boolean mask, which only traces for bool LUTs — mirror that.
        if lut.dtype != np.bool_:
            return None
        kept = lut
    else:
        v = const.value
        if not isinstance(
            v, (int, float, np.integer, np.floating)
        ) or isinstance(v, bool):
            return None
        kept = _NP_CMP[op](lut, v)
    codes = np.nonzero(np.asarray(kept, dtype=bool))[0]
    if len(codes) > _LUT_PRED_MAX_KEPT:
        return None
    return ("i", col.name, 6, 0, 0.0, tuple(int(c) for c in codes))


@dataclasses.dataclass
class _HostNormShim:
    """Duck-typed StagedColumns stand-in for normalizing predicates
    WITHOUT a device staging (r20): ``blocks`` carries zero-length
    arrays in each column's HOST dtype (int32 for STRING code
    columns, ``host_dtype`` otherwise) so the normalizer's dtype
    gates resolve exactly as they would against a host-geometry
    staging; no cell-lane re-encoding ever applies."""

    blocks: dict
    int_dicts: dict = dataclasses.field(default_factory=dict)


def host_norm_shim(relation) -> _HostNormShim:
    blocks = {}
    for schema in relation:
        if schema.data_type == DataType.STRING:
            blocks[schema.name] = np.empty(0, dtype=np.int32)
        else:
            blocks[schema.name] = np.empty(
                0, dtype=host_dtype(schema.data_type)
            )
    return _HostNormShim(blocks)


def predicate_fold_digest(predicates, relation, registry, func_ctx=None):
    """Canonical digest of a conjunctive predicate list over
    ``relation``, or None when any predicate falls outside the
    normalizable class. Two suffixes with the same digest keep or
    drop exactly the same rows.

    String constants canonicalize BY VALUE, never by dictionary
    code: codes drift as dictionaries grow (and every unseen
    constant would collide on -1), so the normalizer runs over a
    private value-sorted code assignment whose codes are translated
    back to the string values in the emitted digest. Terms sort —
    a conjunction commutes — so predicate ORDER never splits a
    digest. Consumers: the r20 materialized-view match (a view
    serves a query only when the fold signature AND this digest
    agree) and the join-side pushdown's staging identity."""
    named = [(f"pred{i}", p) for i, p in enumerate(predicates)]
    try:
        evaluator = ExpressionEvaluator(
            named, relation, registry, func_ctx
        )
    except (ValueError, KeyError):
        return None
    svals = sorted(
        {
            e.value
            for _n, p in named
            for e in walk(p)
            if isinstance(e, Constant) and isinstance(e.value, str)
        }
    )
    code_of = {v: i for i, v in enumerate(svals)}
    aux = {}
    for _n, p in named:
        for e in walk(p):
            if isinstance(e, Constant) and isinstance(e.value, str):
                aux[f"const:{id(e)}"] = code_of[e.value]
    shim = host_norm_shim(relation)
    terms = normalize_predicates(predicates, evaluator, shim, aux)
    if terms is None:
        return None
    val_of_code = {c: v for v, c in code_of.items()}
    string_cols = {
        s.name for s in relation if s.data_type == DataType.STRING
    }
    canon = []
    for stack, col, op, ithr, fthr, invals in terms:
        if col in string_cols and op in (0, 1):
            canon.append((col, op, "s", val_of_code[ithr]))
        elif col in string_cols and op == 6:
            canon.append(
                (col, op, "s",
                 tuple(sorted(val_of_code[c] for c in invals)))
            )
        else:
            canon.append((col, op, stack, ithr, fthr, invals))
    return "preds:" + repr(sorted(canon, key=repr))


@dataclasses.dataclass
class _ScanMatch:
    """Source→(Map|Filter)*→Limit chain (no aggregate): the device
    evaluates predicates + projections and returns the first ``limit``
    surviving rows (ref: the reference's hot path includes plain
    filter/map scans, memory_source_node.h:42 → map/filter → limit;
    px/http_data always bounds output with head())."""

    source_nid: int
    limit_nid: int
    source_op: MemorySourceOp
    limit: int
    out_exprs: list  # [(name, expr in source terms)]
    predicates: list
    source_relation: Any
    out_relation: Any


def match_scan_fragment(fragment: PlanFragment, relations) -> Optional[_ScanMatch]:
    """Find MemorySource→(Map|Filter)*→Limit with single-parent/child
    links. Unbounded scans stay on the host: their output is the whole
    selection, and shipping it back row-for-row forfeits the offload."""
    for nid in fragment.topo_order():
        op = fragment.node(nid)
        if not isinstance(op, LimitOp):
            continue
        chain = []
        cur = nid
        source_nid = None
        while True:
            parents = fragment.parents(cur)
            if len(parents) != 1:
                return None
            cur = parents[0]
            pop = fragment.node(cur)
            if len(fragment.children(cur)) != 1:
                return None
            if isinstance(pop, MemorySourceOp):
                if pop.streaming:
                    return None
                source_nid = cur
                break
            if not isinstance(pop, (MapOp, FilterOp)):
                return None
            chain.append(pop)
        chain.reverse()
        source_rel = relations[source_nid]
        mapping = {c.name: ColumnRef(c.name) for c in source_rel}
        preds = []
        for pop in chain:
            if isinstance(pop, FilterOp):
                preds.append(substitute(pop.expr, mapping))
            else:
                mapping = {
                    name: substitute(e, mapping) for name, e in pop.exprs
                }
        out_rel = relations[nid]
        out_exprs = [(c.name, mapping[c.name]) for c in out_rel]
        return _ScanMatch(
            source_nid=source_nid,
            limit_nid=nid,
            source_op=fragment.node(source_nid),
            limit=op.n,
            out_exprs=out_exprs,
            predicates=preds,
            source_relation=source_rel,
            out_relation=out_rel,
        )
    return None


@dataclasses.dataclass
class _JoinAggMatch:
    """Source→(Map|Filter)*→⌐                                  ⌐→Agg
       Source→(Map|Filter)*→┘ INNER Join →(Map|Filter)* ┘

    Device join-aggregate decomposition: the join's PAIRS are never
    materialized. For decomposable aggregates, aggregating over the join
    equals aggregating the LEFT rows with per-row weight w = (number of
    matching RIGHT rows), plus per-key RIGHT statistics gathered by join
    key:  count ≡ Σ_L w;  sum(left x) ≡ Σ_L x·w;
    sum(right y) ≡ Σ_L sumR[y, key];  min/max(right y) ≡ min/max over
    L of minR/maxR[y, key].  The reference's EquijoinNode
    (equijoin_node.h:48) builds hash tables and materializes chunked
    output rows; on TPU the decomposition keeps everything in segment
    reductions over statically-shaped tensors."""

    left_source_nid: int
    right_source_nid: int
    join_nid: int
    agg_nid: int
    left_source_op: MemorySourceOp
    right_source_op: MemorySourceOp
    join_op: JoinOp
    agg_op: AggOp
    left_exprs: dict       # left source-term mapping (pre-join chain)
    right_exprs: dict      # right source-term mapping
    left_preds: list       # pre-join predicates, left source terms
    right_preds: list      # pre-join predicates, right source terms
    left_key_exprs: list   # join keys in left source terms
    right_key_exprs: list  # join keys in right source terms
    post_left_preds: list  # post-join predicates that touch only left side
    post_right_preds: list
    left_relation: Any
    right_relation: Any
    # agg specs rewritten: [(out_name, side, arg_expr_in_side_terms, agg_name)]
    specs: list
    group_exprs: list      # [(group_name, left-side expr)]


def _chain_to_source(fragment, start_nid, relations):
    """Walk (Map|Filter)* up to a non-streaming MemorySource; returns
    (source_nid, mapping, preds) or None."""
    chain = []
    cur = start_nid
    while True:
        op = fragment.node(cur)
        if isinstance(op, MemorySourceOp):
            if op.streaming:
                return None
            source_nid = cur
            break
        if not isinstance(op, (MapOp, FilterOp)):
            return None
        if len(fragment.children(cur)) != 1:
            return None
        chain.append(op)
        parents = fragment.parents(cur)
        if len(parents) != 1:
            return None
        cur = parents[0]
    chain.reverse()
    rel = relations[source_nid]
    mapping = {c.name: ColumnRef(c.name) for c in rel}
    preds = []
    for op in chain:
        if isinstance(op, FilterOp):
            preds.append(substitute(op.expr, mapping))
        else:
            mapping = {n: substitute(e, mapping) for n, e in op.exprs}
    return source_nid, mapping, preds, rel


def _expr_side(expr, left_cols: set, right_cols: set):
    """0 if the expression references only left-output columns, 1 if only
    right, None if mixed/unknown."""
    refs = referenced_columns(expr)
    if refs <= left_cols:
        return 0
    if refs <= right_cols:
        return 1
    return None


def match_join_agg(fragment: PlanFragment, relations) -> Optional[_JoinAggMatch]:
    join_nid = None
    for nid in fragment.topo_order():
        if isinstance(fragment.node(nid), JoinOp):
            join_nid = nid
            break
    if join_nid is None:
        return None
    join_op: JoinOp = fragment.node(join_nid)
    if join_op.how != JoinType.INNER or not join_op.left_on:
        return None
    parents = fragment.parents(join_nid)
    if len(parents) != 2 or len(fragment.children(join_nid)) != 1:
        return None
    left = _chain_to_source(fragment, parents[0], relations)
    right = _chain_to_source(fragment, parents[1], relations)
    if left is None or right is None:
        return None
    lsrc, lmap, lpreds, lrel = left
    rsrc, rmap, rpreds, rrel = right
    if lsrc == rsrc:
        return None  # self-join over one cursor: host engine's job
    # Walk DOWN from the join through (Map|Filter)* to the Agg.
    out_cols = {o: (side, name) for side, name, o in join_op.output_columns}
    post_map = {o: ColumnRef(o) for o in out_cols}
    post_preds = []
    cur = join_nid
    agg_nid = None
    while True:
        children = fragment.children(cur)
        if len(children) != 1:
            return None
        cur = children[0]
        op = fragment.node(cur)
        if isinstance(op, AggOp):
            # FULL only: a PARTIAL stage must emit serialized states for
            # its MERGE consumer, which this decomposition does not build.
            if op.windowed or op.stage != AggStage.FULL:
                return None
            if len(fragment.parents(cur)) != 1:
                return None
            agg_nid = cur
            break
        if isinstance(op, FilterOp):
            post_preds.append(substitute(op.expr, post_map))
        elif isinstance(op, MapOp):
            post_map = {n: substitute(e, post_map) for n, e in op.exprs}
        else:
            return None
    agg_op: AggOp = fragment.node(agg_nid)

    # Rewrite every post-join expression into single-side source terms.
    left_out = {o for o, (s, _) in out_cols.items() if s == 0}
    right_out = {o for o, (s, _) in out_cols.items() if s == 1}

    def rewrite(expr):
        side = _expr_side(expr, left_out, right_out)
        if side is None:
            return None
        src_map = lmap if side == 0 else rmap
        name_map = {
            o: substitute(ColumnRef(out_cols[o][1]), src_map)
            for o in (left_out if side == 0 else right_out)
        }
        return side, substitute(expr, name_map)

    post_left_preds, post_right_preds = [], []
    for p in post_preds:
        rw = rewrite(p)
        if rw is None:
            return None
        (post_left_preds if rw[0] == 0 else post_right_preds).append(rw[1])
    group_exprs = []
    for g in agg_op.groups:
        rw = rewrite(post_map[g] if g in post_map else ColumnRef(g))
        if rw is None or rw[0] != 0:
            return None  # v1: groups must come from the left side
        group_exprs.append((g, rw[1]))
    specs = []
    for out_name, agg in agg_op.values:
        if agg.name not in _JOIN_DECOMPOSABLE:
            return None
        if not agg.args:
            return None
        arg = substitute(agg.args[0], post_map)
        rw = rewrite(arg)
        if rw is None:
            return None
        specs.append((out_name, rw[0], rw[1], agg.name))
    # Join keys are named on each side's JOIN INPUT; map through the
    # pre-join chains into source terms.
    left_key_exprs = [substitute(ColumnRef(k), lmap) for k in join_op.left_on]
    right_key_exprs = [substitute(ColumnRef(k), rmap) for k in join_op.right_on]
    return _JoinAggMatch(
        left_source_nid=lsrc,
        right_source_nid=rsrc,
        join_nid=join_nid,
        agg_nid=agg_nid,
        left_source_op=fragment.node(lsrc),
        right_source_op=fragment.node(rsrc),
        join_op=join_op,
        agg_op=agg_op,
        left_exprs=lmap,
        right_exprs=rmap,
        left_preds=lpreds,
        right_preds=rpreds,
        left_key_exprs=left_key_exprs,
        right_key_exprs=right_key_exprs,
        post_left_preds=post_left_preds,
        post_right_preds=post_right_preds,
        left_relation=lrel,
        right_relation=rrel,
        specs=specs,
        group_exprs=group_exprs,
    )


# Aggregates with a join decomposition (count/sum/mean/min/max).
_JOIN_DECOMPOSABLE = {"count", "sum", "mean", "min", "max"}


@dataclasses.dataclass
class _JoinMatch:
    """Source→(Map|Filter)*→⌐
       Source→(Map|Filter)*→┘ Join(INNER/LEFT/RIGHT/OUTER) → [host suffix]

    Standalone-join decomposition (r19): unlike _JoinAggMatch the pairs
    ARE materialized — on device, by the sort-merge lane — and whatever
    follows the join runs on the host against the spliced batch."""

    left_source_nid: int
    right_source_nid: int
    join_nid: int
    left_source_op: MemorySourceOp
    right_source_op: MemorySourceOp
    join_op: JoinOp
    left_exprs: dict       # left source-term mapping (pre-join chain)
    right_exprs: dict
    left_preds: list       # pre-join predicates, left source terms
    right_preds: list
    left_key_exprs: list   # join keys in left source terms
    right_key_exprs: list
    left_relation: Any
    right_relation: Any
    out_relation: Any      # join output, in output_columns order


def match_join(fragment: PlanFragment, relations) -> Optional[_JoinMatch]:
    """Match a standalone equijoin whose inputs walk to two DISTINCT
    non-streaming sources. All four join types qualify; the suffix below
    the join (map/filter/agg/limit) stays host work on the spliced
    batch."""
    join_nid = None
    for nid in fragment.topo_order():
        if isinstance(fragment.node(nid), JoinOp):
            if join_nid is not None:
                return None  # multi-join plans: host engine's job
            join_nid = nid
    if join_nid is None:
        return None
    join_op: JoinOp = fragment.node(join_nid)
    if not join_op.left_on:
        return None
    parents = fragment.parents(join_nid)
    if len(parents) != 2:
        return None
    left = _chain_to_source(fragment, parents[0], relations)
    right = _chain_to_source(fragment, parents[1], relations)
    if left is None or right is None:
        return None
    lsrc, lmap, lpreds, lrel = left
    rsrc, rmap, rpreds, rrel = right
    if lsrc == rsrc:
        return None  # self-join over one cursor: host engine's job
    if join_op.how in (JoinType.RIGHT, JoinType.OUTER):
        # The host engine interleaves RIGHT/OUTER-unmatched probe rows
        # per probe batch; the device lane emits them after ALL matches.
        # Row order is not a join contract (preserves_time_order=False)
        # — except under a downstream Limit, which materializes the
        # first N rows of whatever order the engine produced. INNER and
        # LEFT device order is identical to the host's, so only the
        # outer-probe variants gate on Limit. (An upstream Limit already
        # fails _chain_to_source.)
        for nid in fragment.topo_order():
            if isinstance(fragment.node(nid), LimitOp):
                return None
    return _JoinMatch(
        left_source_nid=lsrc,
        right_source_nid=rsrc,
        join_nid=join_nid,
        left_source_op=fragment.node(lsrc),
        right_source_op=fragment.node(rsrc),
        join_op=join_op,
        left_exprs=lmap,
        right_exprs=rmap,
        left_preds=lpreds,
        right_preds=rpreds,
        left_key_exprs=[
            substitute(ColumnRef(k), lmap) for k in join_op.left_on
        ],
        right_key_exprs=[
            substitute(ColumnRef(k), rmap) for k in join_op.right_on
        ],
        left_relation=lrel,
        right_relation=rrel,
        out_relation=relations[join_nid],
    )


@dataclasses.dataclass
class _KeyPlan:
    """How group gids materialize. Exactly one of the modes applies:
    device_expr (codes/LUT gather on device) or host_gids (densified on
    host)."""

    device_expr: Optional[Any] = None
    host_gids: Optional[np.ndarray] = None
    num_groups: int = 0
    key_columns: list = dataclasses.field(default_factory=list)


class MeshExecutor:
    """Runs matching fragments on a jax device mesh (ref: the PEM fleet +
    Kelvin pair, collapsed into one SPMD program)."""

    def __init__(
        self,
        mesh: Optional[Mesh] = None,
        block_rows: Optional[int] = None,
        mesh_config: Optional["mesh_lib.MeshConfig"] = None,
    ):
        # Mesh geometry is declarative (distributed/mesh.py): an explicit
        # mesh wins, else mesh_config, else the mesh_axes flag (flat
        # single-host default). The geometry signature is embedded in
        # every compiled-program signature so a geometry change can
        # never silently reuse a stale executable.
        self.mesh, self.mesh_config = mesh_lib.resolve_mesh(mesh, mesh_config)
        mesh = self.mesh
        self.mesh_axes = mesh_lib.data_axes(mesh)
        self._mesh_sig = self.mesh_config.signature()
        # PIXIE_TPU_DEVICE_BLOCK_ROWS overrides; staging.DEFAULT_BLOCK_ROWS
        # is the built-in default.
        self.block_rows = (
            block_rows if block_rows is not None else flags.device_block_rows
        )
        # Compiled-program cache: structurally identical queries reuse the
        # traced+compiled shard_map (aux LUTs/constants are ARGUMENTS, so
        # dictionary growth does not invalidate the executable).
        self._program_cache: dict[str, Any] = {}
        # Fold signature -> the reduction lanes (segment.LANE_COUNTS
        # names) its program was traced with; the device.program span
        # reports them.
        self._program_lanes: dict[str, set] = {}
        # HBM-resident staged-table cache — the device-side cold tier: a
        # table version is staged once and every matching query hits HBM
        # directly (the reference's analogue is the compacted Arrow cold
        # store living next to the CPU; ours lives next to the MXU).
        # r12: a managed residency pool (serving/residency.py) — per-entry
        # byte accounting against hbm_budget_mb with high/low watermark
        # LRU eviction, query-scoped pinning (an in-flight fold's entry
        # is never evicted), and device_staged_bytes gauges; the
        # staged_cache_cap entry count remains the secondary bound.
        import collections

        from pixie_tpu.serving.residency import ResidencyPool

        self._staged_cache = ResidencyPool()
        # Shared scans (r12, flag shared_scans): concurrent queries whose
        # fold signatures match coalesce into one device dispatch; the
        # followers reuse the leader's merged states and run only their
        # own finalize (serving/shared_scan.py).
        from pixie_tpu.serving.shared_scan import SharedScanCoordinator

        self._shared_scans = SharedScanCoordinator()
        # Optional serving/signatures.FoldSignatureStore: successful
        # device aggregations with replayable shapes are recorded per
        # table, and prewarm_table replays them across restarts instead
        # of guessing the canonical count+sum(f64) shape (r12 satellite).
        self.fold_signature_store = None
        # Device-resident incremental ingest (r13, flag resident_ingest):
        # per-table HBM ring windows fed by table appends
        # (serving/resident.py), created lazily on enable so the manager
        # costs nothing when the flag is off.
        self._resident = None
        # Host-densified key plans per (table version, key exprs), LRU.
        self._keyplan_cache: "collections.OrderedDict[tuple, Any]" = (
            collections.OrderedDict()
        )
        self._keyplan_cache_cap = flags.keyplan_cache_cap
        # Offload is best-effort; failures fall back to the host engine but
        # must stay observable (one log per distinct error signature).
        self.fallback_errors: dict[str, str] = {}
        # Streaming-stage failures fall back to MONOLITHIC staging (still
        # on-device), tracked separately so fallback_errors keeps meaning
        # "query left the mesh".
        self.stream_fallback_errors: dict[str, str] = {}
        # (uda set, capacity) -> (finalize modes, packed-output templates).
        self._finmode_cache: dict[tuple, Any] = {}
        # AOT-compiled fold executables (sig -> jax Compiled) + the single
        # background thread that lowers/compiles them while staging
        # streams (the r7 compile/staging overlap). _aot_futures tracks
        # in-flight compiles so a query arriving mid-compile attaches to
        # the running future instead of compiling twice; _prewarmed holds
        # the fold signatures speculatively compiled at table-create time
        # (r8 prewarm_compile) so hits are attributable (prewarm_hit).
        self._aot_compiled: dict[str, Any] = {}
        self._aot_futures: dict[str, Any] = {}
        self._prewarmed: set[str] = set()
        self.prewarm_errors: dict[str, str] = {}
        self._aot_pool = None
        # Host-computed any() representatives, keyed by
        # (table, version, window, key exprs, col); small LRU.
        self._hostany_cache: "collections.OrderedDict[tuple, np.ndarray]" = (
            collections.OrderedDict()
        )
        # Circuit breaker (r9): per program-key [consecutive_failures,
        # open_until_monotonic]. device_breaker_threshold consecutive
        # fold/compile failures trip the key to the host engine for
        # device_breaker_cooldown_s; the first post-cooldown attempt is
        # the half-open trial — one more failure re-opens immediately,
        # a success closes the breaker.
        self._breaker: dict[str, list] = {}
        self._breaker_lock = threading.Lock()
        # Last successful device-fold wall time (ms) for the health plane.
        self.last_fold_ms: "float | None" = None
        # Per-program-key fold-latency reservoir (r11): the health plane
        # publishes live p50/p99 per query shape on every heartbeat, so
        # /statusz shows per-phase percentiles without running a query.
        self._fold_lat: dict[str, "collections.deque"] = {}
        self._fold_lat_lock = threading.Lock()
        # Mesh recovery plane (r23): the geometry degradation ladder
        # (full geometry first, flat last, None = host engine), built
        # meshes cached per rung — restoring a rung reuses the SAME
        # Mesh object, so resident-ring/mesh identity checks hold on
        # recovery — a per-geometry breaker keyed by mesh signature
        # (repeat offenders skip straight to the degraded rung, with
        # half-open recovery back to full geometry), and window-level
        # fold checkpoints keyed by geometry-FREE fold identity (a
        # resume lands on a different rung by construction).
        self._geom_lock = threading.RLock()
        self._full_mesh_config = self.mesh_config
        self._geom_ladder = self.mesh_config.ladder()
        self._rung_meshes = {self._mesh_sig: self.mesh}
        self._geom_breaker: dict[str, list] = {}
        self._fold_ckpt: "collections.OrderedDict[str, dict]" = (
            collections.OrderedDict()
        )
        self._geom_events = {
            "degrade": 0,
            "checkpoint_windows": 0,
            "resumes": 0,
            "recovered_folds": 0,
        }
        # Window accounting of the most recent checkpoint resume
        # (bench config 12 reads the refolded-window fraction here).
        self.last_resume_stats: "dict | None" = None
        # Worst completed multi-axis dispatch wall on this executor,
        # overall and per fold signature (abandoned dispatches report
        # theirs too when they finish): the derived watchdog deadline
        # is built from these, and arms only for a signature that has
        # completed a dispatch here.
        self._dispatch_wall_max = 0.0
        self._sig_wall_max: dict[str, float] = {}

    # -- public -------------------------------------------------------------
    @staticmethod
    def _breaker_key(fragment: PlanFragment) -> str:
        """Structural program key for the circuit breaker: the operator
        chain + table names, NOT the table version — a poisoned fold shape
        must stay tripped across data growth, while a different query
        shape keeps its own healthy breaker. Shared with the broker's
        health plane (plan/program_key.py) so heartbeat-reported breaker
        keys match what planning computes."""
        from pixie_tpu.plan.program_key import fragment_program_key

        return fragment_program_key(fragment)

    def breaker_snapshot(self) -> dict[str, dict]:
        """Per-program-key breaker state for the health plane:
        ``key -> {state: open|half_open|degrading, failures,
        open_remaining_s}``. Healthy keys are absent (success pops the
        entry), so the snapshot is empty on a healthy executor and
        heartbeats stay small."""
        threshold = flags.device_breaker_threshold
        if threshold <= 0:
            return {}
        now = time.monotonic()
        out = {}
        with self._breaker_lock:
            for key, (fails, open_until) in self._breaker.items():
                if open_until > now:
                    state = "open"
                elif open_until > 0:
                    # Cooldown elapsed; the next attempt is the half-open
                    # trial — planners should treat the key as usable.
                    state = "half_open"
                else:
                    state = "degrading"  # failures below the trip threshold
                out[key] = {
                    "state": state,
                    "failures": fails,
                    "open_remaining_s": round(max(0.0, open_until - now), 3),
                }
        return out

    def _record_fold_latency(self, key: str, ms: float) -> None:
        with self._fold_lat_lock:
            dq = self._fold_lat.get(key)
            if dq is None:
                dq = self._fold_lat[key] = collections.deque(maxlen=256)
            dq.append(ms)

    def fold_latency_snapshot(self) -> dict[str, dict]:
        """program_key -> {p50_ms, p99_ms, n} over the recent fold-latency
        reservoir (r11; rides heartbeats into the broker's health plane
        and /statusz)."""
        out = {}
        with self._fold_lat_lock:
            items = [(k, sorted(dq)) for k, dq in self._fold_lat.items()]
        for key, lat in items:
            if not lat:
                continue
            out[key] = {
                "p50_ms": round(lat[len(lat) // 2], 3),
                "p99_ms": round(lat[min(len(lat) - 1,
                                        int(len(lat) * 0.99))], 3),
                "n": len(lat),
            }
        return out

    def pending_compiles(self) -> int:
        """Background (AOT) compiles submitted and not yet finished: 0
        once every speculatively compiled program has landed."""
        return sum(1 for f in list(self._aot_futures.values()) if not f.done())

    def health_snapshot(self) -> dict:
        """Device-executor health riding agent heartbeats (r10): breaker
        state per program key, open keys (what planning matches on),
        pending background compiles, the last device-fold wall time,
        and (r11) per-program-key fold-latency percentiles."""
        snap = self.breaker_snapshot()
        return {
            "breaker": snap,
            "breaker_open": sorted(
                k for k, v in snap.items() if v["state"] == "open"
            ),
            "staging_depth": self.pending_compiles(),
            "last_fold_ms": self.last_fold_ms,
            "fold_latency": self.fold_latency_snapshot(),
            # HBM residency (r12): staged/pinned bytes vs hbm_budget_mb
            # ride heartbeats so the broker's admission controller and
            # /statusz see device residency without touching the device.
            "residency": self._staged_cache.snapshot(),
            # Resident-ingest rings (r13): windows/bytes per hot table.
            "resident_ingest": (
                self._resident.snapshot() if self._resident else {}
            ),
            # Adopted replica rings (r17): per-table window coverage,
            # leader watermark, and lag — the broker's failover ranking
            # prefers agents whose replicas already hold the data.
            "replicas": (
                self._resident.replica_snapshot() if self._resident else {}
            ),
            # Mesh recovery plane (r23): active vs full geometry, the
            # degradation ladder, per-geometry breaker, and the
            # degrade/checkpoint/resume event counts.
            "mesh": self.mesh_recovery_snapshot(),
        }

    # -- device-resident incremental ingest (r13) ----------------------------
    def enable_resident_ingest(self, table):
        """Attach an HBM ring to ``table``'s appends (flag
        ``resident_ingest``; wired from the table store's create
        listener so every new table opts in automatically). Returns the
        ring or None."""
        if not flags.resident_ingest:
            return None
        return self._resident_manager().enable(table)

    def _resident_manager(self):
        if self._resident is None:
            from pixie_tpu.serving.resident import ResidentIngestManager

            self._resident = ResidentIngestManager(
                self.mesh, self.block_rows, self._staged_cache
            )
        return self._resident

    # -- ring replication (r17) ----------------------------------------------
    def set_ring_replication_hook(self, hook) -> None:
        """Leader side: install ``hook(table, k, start_row, rows,
        wire_cols, latest_k)`` on every owned ring (current and future)
        — the agent's replicator ships each staged window's encoded
        payload to follower agents."""
        self._resident_manager().set_replication_hook(hook)

    def adopt_replica_window(
        self, table_name, window_rows, k, start_row, rows, wire_cols,
        latest_k,
    ) -> bool:
        """Follower side: decode one replicated ring window into this
        executor's HBM (byte-accounted in the residency pool). Works
        without ``resident_ingest`` — a follower never owns the
        table's appends."""
        return self._resident_manager().adopt_replica_window(
            table_name, window_rows, k, start_row, rows, wire_cols,
            latest_k,
        )

    def replica_snapshot(self) -> dict:
        return (
            self._resident.replica_snapshot() if self._resident else {}
        )

    def _resident_ring(self, table, src_op):
        """The table's ring when the resident fast path applies: a ring
        exists and the query has no time bounds (the row-id↔window
        alignment the ring serves assumes the cursor returns every
        resident row). With ``resident_ingest`` off, only ADOPTED
        replica rings serve (r17 failover: the follower never observes
        appends, so the flag gating owned ingest does not apply)."""
        if self._resident is None:
            return None
        if self._resident.mesh is not self.mesh:
            # Degraded geometry (r23): ring windows are sharded on the
            # full mesh. They serve again when the breaker's half-open
            # trial restores that rung (same Mesh object, cached).
            return None
        if src_op.start_time is not None or src_op.stop_time is not None:
            return None
        if flags.resident_ingest:
            return self._resident.ring_for(src_op.table_name)
        return self._resident.replica_for(src_op.table_name)

    def _decode_fn(self, plan, cp, cache: dict):
        """Resolve a window decode program: the background-AOT-compiled
        executable when its compile already landed, else the in-line
        jit (first call compiles; an AOT failure is recorded in
        stream_fallback_errors like a fold-compile failure)."""
        from pixie_tpu.ops import codec as _codec

        sig = f"decode|{cp.sig()}|mesh:{self._mesh_sig}"
        fn = cache.get(sig)
        if fn is not None:
            return fn
        fn = _codec.decoder(self.mesh, cp, plan.nblk, plan.b)
        fut = self._aot_futures.get(sig)
        done = self._aot_compiled.get(sig)
        if done is not None:
            fn = done
        elif fut is not None and fut.done():
            try:
                fn = fut.result()
            except Exception as e:
                key = f"decode-aot {type(e).__name__}: {e}"
                if key not in self.stream_fallback_errors:
                    import traceback

                    self.stream_fallback_errors[key] = (
                        traceback.format_exc()
                    )
        cache[sig] = fn
        return fn

    def _kick_decode_aot(self, plan) -> None:
        """Queue the plan's decode programs on the AOT worker so they
        compile concurrently with the first windows' pack/transfer."""
        from pixie_tpu.ops import codec as _codec

        if not flags.aot_compile:
            return
        for cp in plan.codecs.values():
            sig = f"decode|{cp.sig()}|mesh:{self._mesh_sig}"
            if sig in self._aot_compiled or sig in self._aot_futures:
                continue
            try:
                # Own breakdown key (r16): stage_compile stays the FOLD
                # compile signal (the r8 prewarm contract asserts it
                # zero on a prewarm hit — a column codec engaging must
                # not look like a fold recompile).
                self._aot_compile_async(
                    sig,
                    _codec.decoder(self.mesh, cp, plan.nblk, plan.b),
                    _codec.decode_avals(cp, self.mesh),
                    profile_key="decode_compile",
                )
            except Exception:
                pass  # best-effort: the in-line jit path still works

    def _put_window_cols(self, plan, packed, col_names, dec_cache):
        """device_put one window's packed columns: passthrough blocks
        transfer as-is; CodecPayload columns transfer their (much
        smaller) encoded arrays and expand on device (stage_decode).
        Either way the resulting block is bit-identical."""
        from pixie_tpu.ops import codec as _codec

        axis_name = self.mesh_axes  # full axis tuple: dim0 over every mesh axis
        sharding = NamedSharding(self.mesh, P(axis_name))
        dev_cols = {}
        for n2 in col_names:
            p = packed[n2]
            if isinstance(p, _codec.CodecPayload):
                args = _codec.put_payload(self.mesh, p)
                t0 = time.perf_counter()
                dev_cols[n2] = self._decode_fn(plan, p.plan, dec_cache)(
                    *args
                )
                COLD_PROFILE["stage_decode"] = COLD_PROFILE.get(
                    "stage_decode", 0.0
                ) + (time.perf_counter() - t0)
            else:
                dev_cols[n2] = jax.device_put(p, sharding)
        return dev_cols

    def _convert_resident_window(self, plan, rw, col_names):
        """Raw-dtype ring blocks → the plan's block dtypes, ON DEVICE
        (ops/codec.py converters reproduce the host pack transform bit
        for bit). Zero wire bytes: this is the resident-ingest hot
        path."""
        from pixie_tpu.ops import codec as _codec

        t0 = time.perf_counter()
        dev_cols = {}
        for n2 in col_names:
            blk = rw.blocks[n2]
            kind = plan.col_plans[n2][0]
            if kind == "raw" and blk.dtype == plan.block_dtypes[n2]:
                dev_cols[n2] = blk  # identity: serve the ring block itself
                continue
            dev_cols[n2] = _codec.convert_block(
                self.mesh,
                plan.col_plans[n2],
                blk,
                int_dtype=plan.block_dtypes[n2],
            )
        COLD_PROFILE["stage_resident_convert"] = COLD_PROFILE.get(
            "stage_resident_convert", 0.0
        ) + (time.perf_counter() - t0)
        COLD_PROFILE["stage_resident_hits"] = COLD_PROFILE.get(
            "stage_resident_hits", 0.0
        ) + 1.0
        return dev_cols

    def _breaker_is_open(self, key: str) -> bool:
        threshold = flags.device_breaker_threshold
        if threshold <= 0:
            return False
        with self._breaker_lock:
            st = self._breaker.get(key)
            return st is not None and st[1] > time.monotonic()

    def _breaker_record(self, key: str, ok: bool) -> None:
        threshold = flags.device_breaker_threshold
        if threshold <= 0:
            return
        with self._breaker_lock:
            if ok:
                self._breaker.pop(key, None)  # success closes the breaker
                return
            st = self._breaker.setdefault(key, [0, 0.0])
            st[0] += 1
            if st[0] >= threshold:
                # Trip (or re-trip after a failed half-open trial): route
                # this key to the host engine for the cooldown.
                st[1] = time.monotonic() + flags.device_breaker_cooldown_s
                _BREAKER_TRIPS.inc()
                import logging

                logging.getLogger("pixie_tpu.parallel").warning(
                    "device circuit breaker OPEN for %.1fs after %d "
                    "consecutive failures (key %.80s...)",
                    flags.device_breaker_cooldown_s, st[0], key,
                )

    # -- mesh geometry recovery (r23) ----------------------------------------
    def _geom_breaker_open(self, sig: str) -> bool:
        threshold = flags.mesh_breaker_threshold
        if threshold <= 0:
            return False
        with self._geom_lock:
            st = self._geom_breaker.get(sig)
            return st is not None and st[1] > time.monotonic()

    def _geom_breaker_record(self, sig: str, ok: bool) -> None:
        threshold = flags.mesh_breaker_threshold
        if threshold <= 0:
            return
        with self._geom_lock:
            if ok:
                self._geom_breaker.pop(sig, None)  # success closes it
                return
            st = self._geom_breaker.setdefault(sig, [0, 0.0])
            st[0] += 1
            if st[0] >= threshold:
                # Open (or re-open after a failed half-open trial): new
                # folds skip this rung for the cooldown; the first
                # post-cooldown fold is the half-open trial back toward
                # full geometry.
                st[1] = time.monotonic() + flags.mesh_breaker_cooldown_s
                import logging

                logging.getLogger("pixie_tpu.parallel").warning(
                    "mesh geometry breaker OPEN for %.1fs: %s failed %d "
                    "consecutive folds; new folds start on the next "
                    "degradation rung",
                    flags.mesh_breaker_cooldown_s, sig, st[0],
                )

    def mesh_breaker_snapshot(self) -> dict[str, dict]:
        """Per-geometry breaker state (mirrors ``breaker_snapshot``):
        ``mesh_sig -> {state, failures, open_remaining_s}``."""
        if flags.mesh_breaker_threshold <= 0:
            return {}
        now = time.monotonic()
        out = {}
        with self._geom_lock:
            for sig, (fails, open_until) in self._geom_breaker.items():
                if open_until > now:
                    state = "open"
                elif open_until > 0:
                    state = "half_open"
                else:
                    state = "degrading"
                out[sig] = {
                    "state": state,
                    "failures": fails,
                    "open_remaining_s": round(max(0.0, open_until - now), 3),
                }
        return out

    def mesh_recovery_snapshot(self) -> dict:
        """The r23 recovery plane's health section (rides heartbeats and
        /statusz): active vs full geometry, the degradation ladder, the
        per-geometry breaker, and the degrade/checkpoint/resume counts
        that make every recovery auditable."""
        with self._geom_lock:
            full = self._full_mesh_config.signature()
            return {
                "geometry": self._mesh_sig,
                "full_geometry": full,
                "degraded": self._mesh_sig != full,
                "ladder": [
                    c.signature() if c is not None else "host"
                    for c in self._geom_ladder
                ],
                "breaker": self.mesh_breaker_snapshot(),
                "degrade_events": self._geom_events["degrade"],
                "checkpoint_windows": self._geom_events["checkpoint_windows"],
                "checkpoint_resumes": self._geom_events["resumes"],
                "recovered_folds": self._geom_events["recovered_folds"],
                "checkpoints_held": len(self._fold_ckpt),
            }

    def _activate_geometry(self, cfg: "mesh_lib.MeshConfig") -> None:
        """Point the executor at ``cfg``'s mesh. Rung meshes are cached,
        so restoring a rung reuses the ORIGINAL Mesh object (resident
        rings resume serving on mesh identity, not equality). Staged
        cache entries re-place lazily at lookup via the partition-rule
        tree; compiled programs carry the geometry signature, so a
        stale executable can never dispatch on the new mesh."""
        with self._geom_lock:
            sig = cfg.signature()
            if sig == self._mesh_sig:
                return
            mesh = self._rung_meshes.get(sig)
            if mesh is None:
                mesh = cfg.build()
                self._rung_meshes[sig] = mesh
            self.mesh = mesh
            self.mesh_config = cfg
            self.mesh_axes = mesh_lib.data_axes(mesh)
            self._mesh_sig = sig

    def _execute_with_recovery(
        self, fragment, table_store, registry, func_ctx
    ):
        """Walk the geometry degradation ladder (r23): start at the
        first rung whose per-geometry breaker is closed (an expired
        cooldown makes the attempt the half-open trial), and on a
        recoverable ``MeshGeometryError`` (host loss, hung collective)
        re-plan the SAME fold one rung down — the retried answer is
        bit-identical by the r21 invariant, and a window checkpoint
        (flag ``mesh_fold_checkpoint``) lets the stream resume instead
        of refolding. A non-recoverable error or an exhausted ladder
        propagates to the caller's host-engine fallback."""
        rungs = self._geom_ladder
        last_err = None
        for i, cfg in enumerate(rungs):
            if cfg is None:
                break  # past the mesh: host engine
            sig = cfg.signature()
            if self._geom_breaker_open(sig):
                continue
            if sig != self._mesh_sig:
                self._activate_geometry(cfg)
            try:
                out = self._try_execute_fragment(
                    fragment, table_store, registry, func_ctx
                )
                self._geom_breaker_record(sig, ok=True)
                if last_err is not None and out is not None:
                    with self._geom_lock:
                        self._geom_events["recovered_folds"] += 1
                return out
            except mesh_lib.MeshGeometryError as e:
                if not e.recoverable:
                    raise  # signature mismatch etc: host fallback
                self._geom_breaker_record(sig, ok=False)
                _MESH_DEGRADE.inc()
                with self._geom_lock:
                    self._geom_events["degrade"] += 1
                nxt = next(
                    (
                        r.signature()
                        for r in rungs[i + 1:]
                        if r is not None
                    ),
                    "host",
                )
                if trace.ACTIVE:
                    trace.record(
                        "mesh.recover",
                        0,
                        attrs={"kind": e.kind, "from": sig, "to": nxt},
                    )
                import logging

                logging.getLogger("pixie_tpu.parallel").warning(
                    "mesh geometry failure [%s] on %s: re-planning the "
                    "fold on %s",
                    e.kind, sig, nxt,
                )
                last_err = e
        if last_err is not None:
            raise last_err
        return None

    def _watchdog_deadline(self, fold_sig=None) -> "float | None":
        """Collective-watchdog deadline for one sharded dispatch, or
        None (no watchdog). The flag wins when positive; negative
        disables the watchdog. At 0 the deadline comes from this
        executor's own completed walls, and only once ``fold_sig`` has
        completed a dispatch here: a first dispatch may compile inline,
        and no steady-state wall says how long that takes. The 0.25 s
        floor keeps a microsecond-scale fold from tripping on scheduler
        jitter, and 4x the slowest wall of any signature keeps ambient
        load (clients, agents, a second executor on the same cores) from
        reading as a hang: the watchdog hunts hangs, which are
        unbounded."""
        t = float(flags.mesh_dispatch_timeout_s)
        if t > 0:
            return t
        sig_wall = self._sig_wall_max.get(fold_sig)
        if t < 0 or sig_wall is None:
            return None
        return max(
            0.25,
            sig_wall * float(flags.mesh_watchdog_rail_factor),
            self._dispatch_wall_max * 4.0,
        )

    def _mesh_dispatch(self, fn, what: str = "fold", fold_sig=None):
        """Run one synchronizing sharded dispatch under the recovery
        plane (r23): deterministic fault sites first (``mesh.host_loss``
        / ``mesh.collective_timeout`` — from inside one process a dead
        host and a hung collective both look like a dispatch that never
        completes, so both inject here), then the collective watchdog —
        the dispatch runs on a reaper thread and a deadline miss raises
        a detected ``MeshGeometryError`` instead of hanging the query
        (the stuck thread is abandoned; it holds no executor locks,
        only the process-wide collective lock — see _watchdog_run).
        Every multi-axis dispatch serializes on _MESH_COLLECTIVE_LOCK:
        two interleaved all-device collective programs deadlock the
        shared pool. Single-axis meshes have no hosts to lose and no
        cross-host collectives: plain call. The disabled path (flat
        mesh, or no armed site and no deadline) is a handful of
        attribute reads — microbench_fault_overhead holds it under
        1%."""
        if len(self.mesh_config.axes) > 1:
            if faults.ACTIVE:
                if faults.fires("mesh.host_loss"):
                    raise mesh_lib.MeshGeometryError(
                        "host_loss", f"{what} on {self._mesh_sig}"
                    )
                if faults.fires("mesh.collective_timeout"):
                    raise mesh_lib.MeshGeometryError(
                        "collective_timeout", f"{what} on {self._mesh_sig}"
                    )

            def timed():
                # Dispatch is ASYNC even on CPU: fn() returns once the
                # program is enqueued. Block before releasing the lock
                # or the next all-device program overlaps this one's
                # still-running collectives and wedges the rendezvous.
                # On the watchdog's reaper thread the wall is recorded
                # even when the caller already gave up on this dispatch:
                # a false trip (slow-but-healthy collective) raises the
                # walls, so the NEXT deadline clears it.
                t0 = time.perf_counter()
                out = jax.block_until_ready(fn())
                self._note_dispatch_wall(time.perf_counter() - t0, fold_sig)
                return out

            deadline = self._watchdog_deadline(fold_sig)
            if deadline is not None:
                return self._watchdog_run(deadline, timed, what)
            with _MESH_COLLECTIVE_LOCK:
                return timed()
        if len(self._full_mesh_config.axes) > 1:
            # Degraded-rung dispatch of a multi-axis executor: the flat
            # program still rendezvouses every device, so it must not
            # interleave with an abandoned (timed-out) full-geometry
            # program that is draining on the same pool — queue behind
            # it. Executors that were BORN flat never take the lock.
            with _MESH_COLLECTIVE_LOCK:
                return jax.block_until_ready(fn())
        return fn()

    def _note_dispatch_wall(self, wall: float, fold_sig=None) -> None:
        # Called under _MESH_COLLECTIVE_LOCK (see _mesh_dispatch).
        if wall > self._dispatch_wall_max:
            self._dispatch_wall_max = wall
        if fold_sig is not None and wall > self._sig_wall_max.get(
            fold_sig, 0.0
        ):
            self._sig_wall_max[fold_sig] = wall

    def _watchdog_run(self, deadline: float, fn, what: str):
        from pixie_tpu.ops import segment as _segment

        box: dict = {}
        platform = self.mesh.devices.flat[0].platform
        started = threading.Event()
        done = threading.Event()

        def run():
            # The collective lock is taken ON the reaper thread so an
            # abandoned (timed-out) dispatch keeps holding it until its
            # collective actually returns: overlapping a fresh
            # all-device program with a wedged one deadlocks the whole
            # pool, which is strictly worse than queueing behind it.
            with _MESH_COLLECTIVE_LOCK:
                started.set()
                try:
                    # First call may trace: carry the caller's platform
                    # hint onto the reaper thread so lane strategy
                    # stays pinned. block_until_ready: dispatch is
                    # async — the lock must outlive the EXECUTION, not
                    # just the enqueue (see _mesh_dispatch).
                    with _segment.platform_hint(platform):
                        box["value"] = jax.block_until_ready(fn())
                except BaseException as e:  # re-raised on the caller
                    box["error"] = e
                finally:
                    done.set()

        th = threading.Thread(target=run, name="mesh-watchdog", daemon=True)
        th.start()
        # Queue wait is NOT a hang: the deadline times the exclusive
        # execution window only — concurrent dispatches line up on the
        # collective lock, and a deadline from past walls knows nothing
        # about the queue in front of this one.
        started.wait()
        if not done.wait(timeout=deadline):
            raise mesh_lib.MeshGeometryError(
                "collective_timeout",
                f"{what} exceeded the {deadline:.3f}s watchdog deadline "
                f"on {self._mesh_sig}",
            )
        if "error" in box:
            raise box["error"]
        return box["value"]

    def _staged_mesh_ok(self, staged) -> bool:
        """False when a cached staging's shards live on a different mesh
        than the executor's current one (a degradation rung switched
        geometry since it staged)."""
        for a in staged.blocks.values():
            sh = getattr(a, "sharding", None)
            if sh is None:
                return True
            try:
                return sh.mesh == self.mesh or sh.mesh is self.mesh
            except Exception:
                return True
        return True

    def _save_fold_checkpoint(self, key, windows_done, host_state) -> None:
        with self._geom_lock:
            self._fold_ckpt[key] = {
                "windows": int(windows_done),
                "state": host_state,
            }
            self._fold_ckpt.move_to_end(key)
            while len(self._fold_ckpt) > 4:
                self._fold_ckpt.popitem(last=False)
            self._geom_events["checkpoint_windows"] += 1
        _MESH_CKPT_WINDOWS.inc()

    def _load_fold_checkpoint(self, key, leaves, d, sharding):
        """Validated checkpoint state for ``key``, device_put onto the
        CURRENT mesh (bit-exact: the pull was a host copy of per-device
        carry state, and every rung keeps the device count, so shapes
        are unchanged). Returns (flat_state, windows_done) or (None, 0).
        A corrupt checkpoint — injected, or a shape/dtype mismatch
        against the fold's state template — is DISCARDED and the fold
        restarts from scratch: never resurrect bad carry state (r14
        RingSpill posture)."""
        with self._geom_lock:
            ck = self._fold_ckpt.get(key)
        if ck is None:
            return None, 0
        corrupt = faults.ACTIVE and faults.fires("mesh.checkpoint_corrupt")
        if not corrupt:
            st = ck["state"]
            if len(st) != len(leaves):
                corrupt = True
            else:
                for a, leaf in zip(st, leaves):
                    if a.shape != (d,) + tuple(leaf.shape) or (
                        a.dtype != leaf.dtype
                    ):
                        corrupt = True
                        break
        if corrupt:
            import logging

            with self._geom_lock:
                self._fold_ckpt.pop(key, None)
            logging.getLogger("pixie_tpu.parallel").warning(
                "discarding corrupt mesh fold checkpoint (refolding "
                "from scratch, never resuming bad carry state)"
            )
            return None, 0
        state = [jax.device_put(a, sharding) for a in ck["state"]]
        return state, int(ck["windows"])

    def try_execute_fragment(
        self, fragment: PlanFragment, table_store, registry, func_ctx=None
    ) -> Optional[list[tuple[int, RowBatch]]]:
        """If the fragment contains the hot chain, run it on the mesh and
        return [(agg_node_id, finalized agg RowBatch)]; else None —
        including when any stage of device planning/tracing fails
        (host-untraceable expressions, dictionary edge cases): offload is
        an optimization, never a correctness cliff.

        A fan-out (one source chain forking into several aggregations,
        see match_fanout) returns one pair per aggregation, all from one
        staging of the union of their columns; if any branch cannot be
        planned, none is offloaded and the host engine runs the whole
        fragment. The single-chain lanes return a one-element list.

        Circuit breaker (r9): device_breaker_threshold consecutive
        failures for one program key skip the device entirely for
        device_breaker_cooldown_s (no repeated staging/compile churn on a
        poisoned shape), surfaced via the device_offload_fallback metric
        family (..._breaker_trips_total / ..._breaker_open_total)."""
        bkey = self._breaker_key(fragment)
        if self._breaker_is_open(bkey):
            _BREAKER_SKIPS.inc()
            _OFFLOAD_FALLBACKS.inc()
            return None
        try:
            t0 = time.perf_counter_ns()
            # The whole device offload (stage hit/miss + fold + finalize)
            # as one span; its phases (staging.timed) parent to it.
            with trace.span(
                "device.execute", attrs={"program_key": bkey[:120]}
            ) as ex_span:
                # r23: the fold runs under the geometry degradation
                # ladder — a host loss or hung collective re-plans the
                # same fold on the next surviving geometry
                # (bit-identical) before the host engine is considered.
                out = self._execute_with_recovery(
                    fragment, table_store, registry, func_ctx
                )
                ex_span.set(
                    offloaded=out is not None,
                    aggs=0 if out is None else len(out),
                )
            (_OFFLOAD_HITS if out is not None else _OFFLOAD_MISS).inc()
            if out is not None:
                count_device_aggs(len(out))
                self._breaker_record(bkey, ok=True)
                elapsed_ns = time.perf_counter_ns() - t0
                self.last_fold_ms = elapsed_ns / 1e6
                self._record_fold_latency(bkey, self.last_fold_ms)
                if resattr.ACTIVE:
                    # r15: the offload as one attributed dispatch row —
                    # joins device wall time to the ambient
                    # (query_id, tenant) in device_dispatches.
                    resattr.record_dispatch(
                        "fold", elapsed_ns / 1e9, program=bkey[:120]
                    )
            return out
        except Exception as e:
            import logging
            import traceback

            _OFFLOAD_FALLBACKS.inc()
            self._breaker_record(bkey, ok=False)
            key = f"{type(e).__name__}: {e}"
            if key not in self.fallback_errors:
                self.fallback_errors[key] = traceback.format_exc()
                logging.getLogger("pixie_tpu.parallel").warning(
                    "device offload failed, falling back to host engine: %s",
                    key,
                )
            return None

    def _try_execute_fragment(
        self, fragment: PlanFragment, table_store, registry, func_ctx=None
    ) -> Optional[list[tuple[int, RowBatch]]]:
        table_rel = lambda op: table_store.get_relation(op.table_name)
        relations = fragment.resolve_relations(registry, table_rel)
        m = match_fragment(fragment, relations)
        if m is not None:
            out = self._execute_match(m, table_store, registry, func_ctx)
            return None if out is None else [out]
        ms = match_fanout(fragment, relations)
        if ms is not None:
            return self._execute_fanout(ms, table_store, registry, func_ctx)
        # r19: join-agg decomposition first (it never materializes the
        # pairs), then the standalone sort-merge join lane, then the scan.
        for lane in (
            self._try_execute_join_agg,
            self._try_execute_join,
            self._try_execute_scan,
        ):
            out = lane(fragment, relations, table_store, registry, func_ctx)
            if out is not None:
                return [out]
        return None

    def _plan_branch(self, m: _Match, table, registry, func_ctx):
        """One aggregation's device plan (its UDAs, expressions, key plan
        and the columns it stages), or None when it cannot run on the
        device."""
        specs = self._agg_specs(m, registry)
        if specs is None:
            return None
        evaluator = self._make_evaluator(m, specs, registry, func_ctx)
        if evaluator is None:
            return None

        windowed = m.agg_op.windowed and m.agg_op.stage == AggStage.FULL
        # Host-side any() candidates are syntactic (no predicates, bare
        # column): their arg columns never ship to HBM — exclude them from
        # base_cols up front; if planning falls through after the key plan
        # resolves, they rejoin the device path below.
        any_candidates = set()
        if not m.predicates and m.agg_op.stage == AggStage.FULL and (
            not windowed  # reps would need a per-window pass: device path
        ):
            any_candidates = {
                out
                for out, arg_e, uda in specs
                if uda.name == "any"
                and uda.reads_args
                and isinstance(arg_e, ColumnRef)
            }
        # Host: read needed source columns. UDAs that never read their
        # column (count) contribute nothing — staging their arg would ship
        # gigabytes of unread data to HBM.
        base_cols = set()
        for e in m.predicates:
            base_cols |= referenced_columns(e)
        for out, e, uda in specs:
            if uda.reads_args and out not in any_candidates:
                base_cols |= referenced_columns(e)
        with _timed("plan_keys") as sp:
            key_plan = self._plan_keys(
                m, table, registry, func_ctx, base_cols, sp
            )
        if key_plan is None:
            return None
        base_groups = max(key_plan.num_groups, 1)
        n_windows = 1
        if windowed:
            # Window id = one more (leading) group axis: gid' = wid*G+gid,
            # windows cut at the cursor's eow markers — the same
            # boundaries the host AggNode emits on (agg_node.py:242).
            with _timed("windowize"):
                wk = self._windowize_key_plan(
                    m, table, key_plan, base_groups
                )
            if wk is None:
                return None
            key_plan, n_windows = wk
        with _timed("host_any"):
            host_any = (
                self._plan_host_any(m, specs, key_plan, table)
                if any_candidates
                else {}
            )
        for out, e, uda in specs:
            if out in any_candidates and out not in host_any:
                # Host-side plan fell through (no usable gid source):
                # back to the device path — its column must stage.
                base_cols |= referenced_columns(e)
        device_specs = [s for s in specs if s[0] not in host_any]
        capacity_hint, _ = self._pass_plan(device_specs, key_plan.num_groups)
        # The key signature must pin the actual group expressions — two
        # queries over the same table version with different groupbys must
        # not share staged gids.
        key_sig = repr(
            [m.col_exprs[g] for g in m.agg_op.groups]
        ) + (
            ":host" if key_plan.host_gids is not None
            else (":lut" if isinstance(key_plan.device_expr, tuple) else ":dev")
        ) + (f":win{n_windows}" if windowed else "")
        return _Branch(
            m=m,
            specs=specs,
            evaluator=evaluator,
            windowed=windowed,
            key_plan=key_plan,
            n_windows=n_windows,
            base_groups=base_groups,
            host_any=host_any,
            device_specs=device_specs,
            base_cols=base_cols,
            cell_cols=self._cell_cols(m, device_specs, capacity_hint),
            # f32-staged sketch columns participate in the cache identity:
            # an exact f64 aggregation must never reuse a staging narrowed
            # for a sketch-only query (silently f32-truncated sums
            # otherwise).
            f32_cols=self._sketch_f32_cols(m, specs),
            key_sig=key_sig,
            # Staged HOST gids derived from mutable metadata state
            # (needs_ctx UDFs) must never be cached — pod/service
            # mappings churn without table writes. The device-LUT key
            # path is safe: staged blocks hold raw codes and the LUT is
            # recomputed and passed as an argument.
            cacheable=key_plan.host_gids is None or not any(
                _uses_ctx_func(m.col_exprs[g], m.source_relation, registry)
                for g in m.agg_op.groups
            ),
        )

    def _execute_match(self, m: _Match, table_store, registry, func_ctx):
        """(agg nid, batch) of one source→(map|filter)*→agg chain."""
        table = table_store.get_table(m.source_op.table_name)
        if table is None:
            return None
        # Fault site: poison the device fold dispatch for a matched
        # fragment (chaos tests prove the fallback is bit-identical on the
        # host engine and the circuit breaker trips after N hits).
        if faults.ACTIVE:
            faults.check("pipeline.fold")
        b = self._plan_branch(m, table, registry, func_ctx)
        if b is None:
            return None
        specs, evaluator, key_plan = b.specs, b.evaluator, b.key_plan
        device_specs, base_cols = b.device_specs, b.base_cols
        cell_cols, f32_cols, cacheable = b.cell_cols, b.f32_cols, b.cacheable
        windowed = b.windowed
        # Version = (min_row_id, end_row_id): writes bump end_row_id and
        # ring-buffer expiry bumps min_row_id, so either invalidates.
        version = (table.min_row_id(), table.end_row_id())
        cache_key = (
            m.source_op.table_name,
            version,
            tuple(sorted(base_cols)),
            m.source_op.start_time,
            m.source_op.stop_time,
            self.block_rows,
            b.key_sig,
            key_plan.num_groups,
            tuple(sorted(f32_cols)),
            # name AND cardinality bound: two queries with different
            # pass capacities must not share codes staged under a
            # different max_card (their cell-lane segment budgets differ).
            tuple(sorted(cell_cols.items())),
        )
        staged = self._staged_cache.get(cache_key) if cacheable else None
        if staged is None and cacheable:
            # Superset reuse: an entry staged for a wider column set of the
            # SAME table version/window/key plan serves this query directly
            # (the program reads the columns it needs) — re-staging
            # gigabytes for a subset risks doubling HBM residency.
            for k, v in self._staged_cache.items():
                if (
                    k[0] == cache_key[0]
                    and k[1] == cache_key[1]
                    and set(k[2]) >= set(cache_key[2])
                    and k[3:] == cache_key[3:]
                ):
                    cache_key = k
                    staged = v
                    break
        staged = self._staged_on_mesh(staged, cache_key, cacheable, version)
        merged = capacity = None
        if staged is None:
            with _timed("read_columns"):
                cols, n = read_columns(
                    table,
                    sorted(base_cols),
                    m.source_op.start_time,
                    m.source_op.stop_time,
                )
            if key_plan.host_gids is not None and len(key_plan.host_gids) != n:
                return None  # table moved under us; fall back
            if flags.streaming_stage:
                # Streamed double-buffered staging: host pack ∥ HBM
                # transfer ∥ device fold per window. The aggregate is
                # computed as a side effect of staging, and the window
                # blocks concatenate into the warm-path cache entry.
                with _timed("aux"):
                    aux = self._build_aux(
                        evaluator, m, key_plan, table, device_specs
                    )
                with _timed("stage"):
                    stream = self._stream_execute(
                        m, device_specs, evaluator, key_plan, table, cols,
                        n, f32_cols, cell_cols, aux, cacheable,
                        base_row=version[0],
                    )
                if stream is not None:
                    merged, capacity, staged = stream
                    if cacheable and staged is not None:
                        self._staged_insert(
                            cache_key, staged, m.source_op.table_name, version
                        )
            if merged is None:
                int_dicts = self._int_dict_encode(cols, cell_cols)
                staged = self._stage_or_clear(
                    lambda: self._stage(
                        cols, n, key_plan, table, f32_cols, int_dicts
                    )
                )
                if cacheable:
                    self._staged_insert(
                        cache_key, staged, m.source_op.table_name, version
                    )
        # Query-scoped pin (r12): from here until finalize returns, this
        # query's staged entry cannot be evicted underneath its fold —
        # not by a concurrent query's byte-watermark eviction, not by a
        # version bump, not by the OOM clear. Pinning a key absent from
        # the pool (non-cacheable staging) is a no-op.
        with self._staged_cache.pin(cache_key if cacheable else None):
            if merged is None:
                with _timed("aux"):
                    aux = self._build_aux(
                        evaluator, m, key_plan, table, device_specs
                    )
                with _timed("program") as sp:
                    merged, capacity = self._fold(
                        b, staged, aux, cache_key, sp
                    )
            if (
                self.fold_signature_store is not None
                and staged is not None
                and not windowed
            ):
                self._record_fold_shape(
                    m, device_specs, key_plan, staged, capacity, aux
                )
            with _timed("finalize"):
                batch = self._finalize_branch(
                    b, merged, capacity, registry, table
                )
            return m.agg_nid, batch

    def _execute_fanout(self, ms: list, table_store, registry, func_ctx):
        """[(agg nid, batch)] of every branch of a fan-out (match_fanout):
        the union of the branches' columns staged once, under one cache
        entry, and each branch's own predicates, key plan, fold and
        finalize run over it. None (the host engine runs the fragment)
        when any branch cannot be planned."""
        src = ms[0].source_op
        table = table_store.get_table(src.table_name)
        if table is None:
            return None
        if faults.ACTIVE:
            faults.check("pipeline.fold")
        branches = []
        for m in ms:
            b = self._plan_branch(m, table, registry, func_ctx)
            if b is None:
                return None  # never offload half a fan-out
            branches.append(b)
        cols_all = set().union(*(b.base_cols for b in branches))

        def readers(col):
            return [b for b in branches if col in b.base_cols]

        # A column narrows (f32 sketch staging, int-dictionary cell lane)
        # only when every branch that reads it would narrow it alone.
        f32_cols = {
            c
            for c in set().union(*(b.f32_cols for b in branches))
            if all(c in b.f32_cols for b in readers(c))
        }
        cell_cols = {
            c: min(b.cell_cols[c] for b in readers(c))
            for c in set().union(*(b.cell_cols for b in branches))
            if all(c in b.cell_cols for b in readers(c))
        }
        cacheable = all(b.cacheable for b in branches)
        version = (table.min_row_id(), table.end_row_id())
        cache_key = (
            src.table_name,
            version,
            tuple(sorted(cols_all)),
            src.start_time,
            src.stop_time,
            self.block_rows,
            tuple(b.key_sig for b in branches),
            tuple(b.key_plan.num_groups for b in branches),
            tuple(sorted(f32_cols)),
            tuple(sorted(cell_cols.items())),
        )
        staged = self._staged_cache.get(cache_key) if cacheable else None
        staged = self._staged_on_mesh(staged, cache_key, cacheable, version)
        if staged is None:
            with _timed("read_columns"):
                cols, n = read_columns(
                    table, sorted(cols_all), src.start_time, src.stop_time
                )
            if any(
                b.key_plan.host_gids is not None
                and len(b.key_plan.host_gids) != n
                for b in branches
            ):
                return None  # table moved under us; fall back
            int_dicts = self._int_dict_encode(cols, cell_cols)

            def stage():
                union = stage_columns(
                    self.mesh,
                    cols,
                    n,
                    dictionaries=table.dictionaries,
                    block_rows=self.block_rows,
                    f32_cols=f32_cols,
                    int_dicts=int_dicts,
                )
                union.branch_gids = {
                    b.m.agg_nid: stage_gids(
                        self.mesh,
                        b.key_plan.host_gids,
                        n,
                        max(b.key_plan.num_groups, 1),
                        self.block_rows,
                    )
                    for b in branches
                    if b.key_plan.host_gids is not None
                }
                return union

            staged = self._stage_or_clear(stage)
            if cacheable:
                self._staged_insert(cache_key, staged, src.table_name, version)
        out = []
        with self._staged_cache.pin(cache_key if cacheable else None):
            for b in branches:
                nid = b.m.agg_nid
                view = dataclasses.replace(
                    staged,
                    gids=staged.branch_gids.get(nid),
                    num_groups=max(b.key_plan.num_groups, 1),
                    capacity=_pow2_at_least(max(b.key_plan.num_groups, 1)),
                    key_columns=list(b.key_plan.key_columns),
                    branch_gids={},
                )
                with _timed("aux"):
                    aux = self._build_aux(
                        b.evaluator, b.m, b.key_plan, table, b.device_specs
                    )
                with _timed("program") as sp:
                    sp.set(agg=nid)
                    merged, capacity = self._fold(
                        b, view, aux, (cache_key, nid), sp
                    )
                with _timed("finalize") as sp:
                    sp.set(agg=nid)
                    out.append(
                        (
                            nid,
                            self._finalize_branch(
                                b, merged, capacity, registry, table
                            ),
                        )
                    )
        return out

    def _staged_on_mesh(self, staged, cache_key, cacheable, version):
        """``staged`` on the executor's current mesh, LRU-touched."""
        if staged is not None and not self._staged_mesh_ok(staged):
            # Geometry changed since this entry staged (an r23
            # degradation rung, or a half-open recovery back to full):
            # re-place its shards onto the current mesh through the
            # partition-rule tree — same bytes, no host restage. The
            # old entry retires (zombie while a concurrent fold on the
            # old mesh still pins it).
            from pixie_tpu.parallel import staging as _staging_mod

            with _timed("stage_repartition"):
                staged = _staging_mod.repartition_staged(self.mesh, staged)
            if cacheable:
                self._staged_insert(cache_key, staged, cache_key[0], version)
        if staged is not None:
            self._staged_cache.touch(cache_key)
        return staged

    @staticmethod
    def _int_dict_encode(cols: dict, cell_cols: dict) -> dict:
        """Replace each cell-lane column of ``cols`` that fits its bound
        by its small-domain codes; returns {column: value LUT}."""
        from pixie_tpu.parallel.staging import int_dict_encode

        int_dicts = {}
        with _timed("int_dict_encode"):
            for col, max_card in cell_cols.items():
                enc = int_dict_encode(cols[col], max_card)
                if enc is not None:
                    cols[col], int_dicts[col] = enc
        return int_dicts

    def _stage_or_clear(self, stage):
        """``stage()`` under the ``stage`` phase; on a device OOM, drop
        every cached staging and stage once more — better than falling
        back to the host engine for a gigarow table. (Entries pinned by
        concurrent folds survive as accounted zombies; their memory was
        never ours to free.)"""
        try:
            with _timed("stage"):
                return stage()
        except Exception as e:
            if "RESOURCE_EXHAUSTED" not in str(e) and (
                "Out of memory" not in str(e)
            ):
                raise  # deterministic failures must not nuke the cache
            self._staged_cache.clear(reason="oom")
        # Retry OUTSIDE the except block: the in-flight exception's
        # traceback pins the failed attempt's partially allocated device
        # buffers until the handler exits.
        with _timed("stage"):
            return stage()

    def _fold(self, b, staged, aux, cache_key, span):
        """(merged, capacity) of branch ``b``'s fold over ``staged``;
        ``span`` (the ``device.program`` span) gains ``lanes``, the
        reduction lanes of the fold program that ran."""
        if flags.shared_scans:
            # Shared scan (r12): coalesce with any concurrent query whose
            # fold signature + aux values match — one device dispatch,
            # per-query finalize.
            return self._shared_scan_run(
                b.m, b.device_specs, b.evaluator, b.key_plan, staged, aux,
                cache_key, span,
            )
        return self._run_program(
            b.m, b.device_specs, b.evaluator, b.key_plan, staged, aux, span
        )

    def _note_lanes(self, span, fold_sig):
        """Set ``lanes`` on ``span``: the reduction lanes (segment
        LANE_COUNTS names) the fold program ``fold_sig`` was traced
        with."""
        lanes = self._program_lanes.get(fold_sig)
        if span is not None and lanes:
            span.set(lanes=",".join(sorted(lanes)))

    def _finalize_branch(self, b, merged, capacity, registry, table):
        """Branch ``b``'s output: raw states (PARTIAL), one RowBatch per
        window (windowed, eow-cadenced like the host AggNode), or one
        RowBatch."""
        m, key_plan = b.m, b.key_plan
        if m.agg_op.stage == AggStage.PARTIAL:
            return self._partial_state_batch(
                m, b.device_specs, key_plan, merged, table
            )
        if b.windowed:
            return [
                self._finalize(
                    m,
                    b.specs,
                    key_plan,
                    capacity,
                    merged,
                    registry,
                    table,
                    host_any=b.host_any,
                    group_range=(w * b.base_groups, b.base_groups),
                    eow=True,
                    eos=(w == b.n_windows - 1),
                )
                for w in range(b.n_windows)
            ]
        return self._finalize(
            m,
            b.specs,
            key_plan,
            capacity,
            merged,
            registry,
            table,
            host_any=b.host_any,
        )

    # -- device join-aggregate (inner join fused into the agg) ---------------
    def _try_execute_join_agg(
        self, fragment, relations, table_store, registry, func_ctx
    ) -> Optional[tuple[int, RowBatch]]:
        m = match_join_agg(fragment, relations)
        if m is None:
            return None
        lt = table_store.get_table(m.left_source_op.table_name)
        rt = table_store.get_table(m.right_source_op.table_name)
        if lt is None or rt is None:
            return None
        # v1 gates: bare-column join keys; non-string agg args.
        if not all(isinstance(e, ColumnRef) for e in m.left_key_exprs):
            return None
        if not all(isinstance(e, ColumnRef) for e in m.right_key_exprs):
            return None
        for (_, agg), (_o, side, arg_e, _name) in zip(m.agg_op.values, m.specs):
            if len(agg.args) != 1:
                return None  # single-arg decompositions only
            rel = m.left_relation if side == 0 else m.right_relation
            try:
                if expr_data_type(arg_e, rel, registry) == DataType.STRING:
                    return None
            except (KeyError, ValueError):
                return None

        # --- shared join-key id space (host; the 'dense gids' the sorted
        # merge would use — here they index the per-key stat tensors) ------
        def read_keys(table, rel, key_exprs, src_op):
            cols, n = read_columns(
                table,
                sorted({e.name for e in key_exprs}),
                src_op.start_time,
                src_op.stop_time,
            )
            return cols, n

        lcols, nl = read_keys(lt, m.left_relation, m.left_key_exprs, m.left_source_op)
        rcols, nr = read_keys(rt, m.right_relation, m.right_key_exprs, m.right_source_op)
        lkey_arrays, rkey_arrays = [], []
        for le, re_ in zip(m.left_key_exprs, m.right_key_exprs):
            la, ra = lcols[le.name], rcols[re_.name]
            lt_dt = m.left_relation.col(le.name).data_type
            rt_dt = m.right_relation.col(re_.name).data_type
            if lt_dt == DataType.STRING or rt_dt == DataType.STRING:
                if lt_dt != rt_dt:
                    return None
                shared = StringDictionary()
                dl, dr = lt.dictionaries.get(le.name), rt.dictionaries.get(re_.name)
                if dl is None or dr is None:
                    return None
                lut_l = shared.encode(np.asarray(list(dl.values()), dtype=object))
                lut_r = shared.encode(np.asarray(list(dr.values()), dtype=object))
                la = lut_l[la] if len(lut_l) else la
                ra = lut_r[ra] if len(lut_r) else ra
            lkey_arrays.append(np.asarray(la))
            rkey_arrays.append(np.asarray(ra))
        enc = GroupEncoder()
        kl = enc.encode(lkey_arrays) if nl else np.empty(0, np.int32)
        kr = enc.encode(rkey_arrays) if nr else np.empty(0, np.int32)
        K = max(enc.num_groups, 1)
        if K > (1 << 22):
            return None  # stat tensors would be unreasonable

        # --- group-key plan over the LEFT side ---------------------------
        shim = _Match(
            source_nid=m.left_source_nid,
            agg_nid=m.agg_nid,
            source_op=m.left_source_op,
            agg_op=dataclasses.replace(
                m.agg_op, groups=tuple(g for g, _ in m.group_exprs)
            ),
            col_exprs={g: e for g, e in m.group_exprs},
            predicates=[],
            source_relation=m.left_relation,
        )
        base_left = {e.name for e in m.left_key_exprs}
        for p in m.left_preds + m.post_left_preds:
            base_left |= referenced_columns(p)
        for _, side, arg_e, _n in m.specs:
            if side == 0:
                base_left |= referenced_columns(arg_e)
        key_plan = self._plan_keys(shim, lt, registry, func_ctx, base_left)
        if key_plan is None:
            return None
        if m.group_exprs and key_plan.host_gids is None:
            # _plan_keys prefers device key paths (dict codes / LUT); the
            # join-agg program wants host gids — derive them cheaply from
            # the same dictionary structures.
            if isinstance(key_plan.device_expr, ColumnRef):
                cols2, n2 = read_columns(
                    lt,
                    [key_plan.device_expr.name],
                    m.left_source_op.start_time,
                    m.left_source_op.stop_time,
                )
                gids2 = cols2[key_plan.device_expr.name].astype(np.int32)
            elif isinstance(key_plan.device_expr, tuple):
                _, src_col, lut_codes = key_plan.device_expr
                cols2, n2 = read_columns(
                    lt,
                    [src_col],
                    m.left_source_op.start_time,
                    m.left_source_op.stop_time,
                )
                codes = np.maximum(cols2[src_col], 0)
                gids2 = np.asarray(lut_codes)[codes].astype(np.int32)
            else:
                return None
            key_plan = dataclasses.replace(key_plan, host_gids=gids2)
        if key_plan.host_gids is not None and len(key_plan.host_gids) != nl:
            return None
        if key_plan.host_gids is None:
            # Group-by-none: one global group; the program still wants a
            # staged gid lane.
            key_plan = dataclasses.replace(
                key_plan, host_gids=np.zeros(nl, np.int32), num_groups=1
            )
        capacity = _pow2_at_least(max(key_plan.num_groups, 1))
        if capacity > (1 << 20):
            return None

        # --- right-side per-key statistics (device, stays resident) ------
        base_right = set()
        for p in m.right_preds + m.post_right_preds:
            base_right |= referenced_columns(p)
        right_specs = [
            (out, arg_e, name)
            for out, side, arg_e, name in m.specs
            if side == 1
        ]
        for _, arg_e, _n in right_specs:
            base_right |= referenced_columns(arg_e)
        r_named = [
            (f"pred{i}", p)
            for i, p in enumerate(m.right_preds + m.post_right_preds)
        ] + [(f"arg:{o}", e) for o, e, _n in right_specs]
        try:
            r_eval = ExpressionEvaluator(
                r_named, m.right_relation, registry, func_ctx
            )
            l_eval = ExpressionEvaluator(
                [
                    (f"pred{i}", p)
                    for i, p in enumerate(m.left_preds + m.post_left_preds)
                ]
                + [
                    (f"arg:{o}", e)
                    for o, side, e, _n in m.specs
                    if side == 0
                ],
                m.left_relation,
                registry,
                func_ctx,
            )
        except ValueError:
            return None
        # The shared-encoder id space depends on the LEFT side too (left
        # keys are encoded first, so left content changes permute ids):
        # the right staging's identity must pin the whole key space.
        key_space_sig = (
            m.left_source_op.table_name,
            (lt.min_row_id(), lt.end_row_id()),
            repr(m.left_key_exprs) + repr(m.right_key_exprs),
            m.left_source_op.start_time,
            m.left_source_op.stop_time,
        )
        rstats = self._run_right_stats(
            m, rt, rcols_needed=sorted(base_right), kr=kr, nr=nr, K=K,
            evaluator=r_eval, right_specs=right_specs,
            key_space_sig=key_space_sig,
        )
        if rstats is None:
            return None
        # --- left-side weighted aggregation --------------------------------
        left_stage_cols = set()
        for p in m.left_preds + m.post_left_preds:
            left_stage_cols |= referenced_columns(p)
        for _o, side, e, _n in m.specs:
            if side == 0:
                left_stage_cols |= referenced_columns(e)
        out = self._run_left_join_agg(
            m, lt, sorted(left_stage_cols),
            kl, nl, key_plan, capacity, l_eval, rstats, registry,
        )
        if out is None:
            return None
        return m.agg_nid, out

    def _run_right_stats(
        self, m, table, rcols_needed, kr, nr, K, evaluator, right_specs,
        key_space_sig=None, **_
    ):
        """Stage the right side and reduce per-key stats on the mesh:
        nR[K] plus per-right-arg sum/min/max as needed. Outputs are device
        arrays (replicated); nothing is fetched."""
        cache_key = (
            m.right_source_op.table_name,
            (table.min_row_id(), table.end_row_id()),
            tuple(sorted(set(rcols_needed))),
            m.right_source_op.start_time,
            m.right_source_op.stop_time,
            self.block_rows,
            ":joinright:" + repr(key_space_sig),
            K,
            (),
        )
        staged = self._stage_cached(
            cache_key,
            table,
            m.right_source_op,
            rcols_needed,
            _KeyPlan(host_gids=kr.astype(np.int32), num_groups=K),
        )
        if staged is None or staged.num_rows != nr:
            return None
        aux = {}
        for name, e in evaluator.named_exprs:
            aux.update(evaluator.build_aux(e, table.dictionaries))
        col_names = sorted(staged.blocks)
        narrow_names = sorted(staged.narrow_offsets)
        preds = [e for n, e in evaluator.named_exprs if n.startswith("pred")]
        axis = self.mesh_axes  # collectives reduce over the FULL mesh
        ndev = staged.num_devices
        aux_order = list(aux.keys())
        stat_kinds = []  # [(spec out name, kind)] kinds: sum/min/max
        for out, _e, name in right_specs:
            if name in ("sum", "mean"):
                stat_kinds.append((out, "sum"))
            elif name == "min":
                stat_kinds.append((out, "min"))
            elif name == "max":
                stat_kinds.append((out, "max"))
            else:
                return None  # count needs no right stat

        sig = "|".join(
            [
                "joinR",
                ",".join(
                    f"{n2}:{a.shape}:{a.dtype}"
                    for n2, a in sorted(staged.blocks.items())
                ),
                f"narrow:{narrow_names}",
                f"K:{K}",
                "preds:" + ";".join(repr(p) for p in preds),
                "stats:" + ";".join(f"{o}:{k}" for o, k in stat_kinds),
                "aux:" + ",".join(
                    f"{np.shape(v)}:{np.asarray(v).dtype}" for v in aux.values()
                ),
                f"mesh:{self._mesh_sig}",
            ]
        )
        arg_exprs = {o: e for o, e, _n in right_specs}

        if sig not in self._program_cache:

            def right_stats_fn(*arrs):
                i = len(col_names)
                cols = {n: a[0] for n, a in zip(col_names, arrs[:i])}
                mask_all = arrs[i][0]
                jk_all = arrs[i + 1][0]
                i += 2
                end = len(arrs)
                narrow_vec = None
                if narrow_names:
                    narrow_vec = arrs[-1]
                    end -= 1
                aux_v = dict(zip(aux_order, arrs[i:end]))

                def body(carry, xs):
                    from pixie_tpu.ops import segment as _segment

                    counts, sums, mins, maxs = carry
                    blk_cols, blk_mask, blk_jk = xs
                    env = dict(zip(col_names, blk_cols))
                    for ni, nm in enumerate(narrow_names):
                        env[nm] = env[nm].astype(jnp.int64) + narrow_vec[ni]
                    mask = blk_mask
                    for p in preds:
                        mask = mask & evaluator.device_eval(p, env, aux_v)
                    jk = blk_jk.astype(jnp.int32)
                    counts = counts + _segment.seg_sum(
                        mask.astype(jnp.float64), jk, K
                    )
                    new_sums = {}
                    for o, kind in stat_kinds:
                        val = evaluator.device_eval(
                            arg_exprs[o], env, aux_v
                        ).astype(jnp.float64)
                        if kind == "sum":
                            new_sums[o] = sums[o] + _segment.seg_sum(
                                val, jk, K, mask
                            )
                        elif kind == "min":
                            mins[o] = jnp.minimum(
                                mins[o],
                                _segment.seg_min(val, jk, K, mask),
                            )
                        else:
                            maxs[o] = jnp.maximum(
                                maxs[o],
                                _segment.seg_max(val, jk, K, mask),
                            )
                    sums.update(new_sums)
                    return (counts, sums, mins, maxs), None

                init = (
                    jnp.zeros(K, jnp.float64),
                    {o: jnp.zeros(K, jnp.float64) for o, k in stat_kinds if k == "sum"},
                    {o: jnp.full(K, jnp.inf) for o, k in stat_kinds if k == "min"},
                    {o: jnp.full(K, -jnp.inf) for o, k in stat_kinds if k == "max"},
                )
                xs = (
                    tuple(cols[n] for n in col_names),
                    mask_all,
                    jk_all,
                )
                (counts, sums, mins, maxs), _ = jax.lax.scan(body, init, xs)
                if ndev > 1:
                    counts = jax.lax.psum(counts, axis)
                    sums = {o: jax.lax.psum(v, axis) for o, v in sums.items()}
                    mins = {o: jax.lax.pmin(v, axis) for o, v in mins.items()}
                    maxs = {o: jax.lax.pmax(v, axis) for o, v in maxs.items()}
                return (
                    (counts,)
                    + tuple(sums[o] for o, k in stat_kinds if k == "sum")
                    + tuple(mins[o] for o, k in stat_kinds if k == "min")
                    + tuple(maxs[o] for o, k in stat_kinds if k == "max")
                )

            n_sharded = len(col_names) + 2
            n_repl = len(aux_order) + (1 if narrow_names else 0)
            in_specs = tuple([P(axis)] * n_sharded + [P()] * n_repl)
            n_out = 1 + len(stat_kinds)
            program = jax.jit(
                jax.shard_map(
                    right_stats_fn,
                    mesh=self.mesh,
                    in_specs=in_specs,
                    out_specs=tuple([P()] * n_out),
                    check_vma=False,
                )
            )
            self._program_cache[sig] = (program, len(aux_order), None)
            _PROGRAMS.set(len(self._program_cache))
        program = self._program_cache[sig][0]
        args = [staged.blocks[n2] for n2 in col_names]
        args.append(staged.mask)
        args.append(staged.gids)  # join-key ids staged as gids
        args.extend(jnp.asarray(v) for v in aux.values())
        if staged.narrow_offsets:
            args.append(
                jnp.asarray(
                    [staged.narrow_offsets[n2] for n2 in narrow_names],
                    jnp.int64,
                )
            )
        from pixie_tpu.ops import segment as _segment

        with _segment.platform_hint(self.mesh.devices.flat[0].platform):
            outs = program(*args)
        result = {"__n__": outs[0]}
        idx = 1
        for o, k in [(o, k) for o, k in stat_kinds if k == "sum"]:
            result[f"sum:{o}"] = outs[idx]
            idx += 1
        for o, k in [(o, k) for o, k in stat_kinds if k == "min"]:
            result[f"min:{o}"] = outs[idx]
            idx += 1
        for o, k in [(o, k) for o, k in stat_kinds if k == "max"]:
            result[f"max:{o}"] = outs[idx]
            idx += 1
        return result

    def _run_left_join_agg(
        self, m, table, lcols_needed, kl, nl, key_plan, capacity,
        evaluator, rstats, registry,
    ):
        """Scan the LEFT side with per-row join weights gathered from the
        right-key stats; segment-reduce per agg group; fetch one buffer."""
        from pixie_tpu.types.dtypes import host_dtype

        base = set(lcols_needed)
        cache_key = (
            m.left_source_op.table_name,
            (table.min_row_id(), table.end_row_id()),
            tuple(sorted(base)),
            m.left_source_op.start_time,
            m.left_source_op.stop_time,
            self.block_rows,
            ":joinleft:" + repr(m.left_key_exprs) + repr(
                [e for _, e in m.group_exprs]
            ),
            key_plan.num_groups,
            (),
        )
        staged = self._stage_cached(
            cache_key,
            table,
            m.left_source_op,
            base,
            key_plan,
            extra_cols={"__jk__": kl.astype(np.int32)},
        )
        if staged is None or staged.num_rows != nl:
            return None
        aux = {}
        for name, e in evaluator.named_exprs:
            aux.update(evaluator.build_aux(e, table.dictionaries))
        col_names = sorted(staged.blocks)
        narrow_names = sorted(staged.narrow_offsets)
        preds = [e for n, e in evaluator.named_exprs if n.startswith("pred")]
        axis = self.mesh_axes  # collectives reduce over the FULL mesh
        ndev = staged.num_devices
        aux_order = list(aux.keys())
        stat_names = sorted(rstats)
        arg_exprs = {
            o: e for o, side, e, _n in m.specs if side == 0
        }
        spec_plan = [(o, side, name) for o, side, _e, name in m.specs]

        sig = "|".join(
            [
                "joinL",
                ",".join(
                    f"{n2}:{a.shape}:{a.dtype}"
                    for n2, a in sorted(staged.blocks.items())
                ),
                f"narrow:{narrow_names}",
                f"cap:{capacity}",
                "preds:" + ";".join(repr(p) for p in preds),
                "specs:" + ";".join(
                    f"{o}:{s}:{n2}" for o, s, n2 in spec_plan
                ),
                "largs:" + ";".join(
                    f"{o}={e!r}" for o, e in sorted(arg_exprs.items())
                ),
                "stats:" + ",".join(stat_names),
                "aux:" + ",".join(
                    f"{np.shape(v)}:{np.asarray(v).dtype}" for v in aux.values()
                ),
                f"mesh:{self._mesh_sig}",
            ]
        )
        if sig not in self._program_cache:

            def join_left_fn(*arrs):
                from pixie_tpu.ops import segment as _segment

                i = len(col_names)
                cols = {n: a[0] for n, a in zip(col_names, arrs[:i])}
                mask_all = arrs[i][0]
                gids_all = arrs[i + 1][0]
                i += 2
                stats = dict(zip(stat_names, arrs[i : i + len(stat_names)]))
                i += len(stat_names)
                end = len(arrs)
                narrow_vec = None
                if narrow_names:
                    narrow_vec = arrs[-1]
                    end -= 1
                aux_v = dict(zip(aux_order, arrs[i:end]))
                nR = stats["__n__"]

                def body(carry, xs):
                    acc = carry
                    blk_cols, blk_mask, blk_gids = xs
                    env = dict(zip(col_names, blk_cols))
                    for ni, nm in enumerate(narrow_names):
                        env[nm] = env[nm].astype(jnp.int64) + narrow_vec[ni]
                    mask = blk_mask
                    for p in preds:
                        mask = mask & evaluator.device_eval(p, env, aux_v)
                    jk = env["__jk__"].astype(jnp.int32)
                    w = nR[jk]
                    mask = mask & (w > 0)
                    gids = blk_gids.astype(jnp.int32)
                    wm = jnp.where(mask, w, 0.0)
                    new_acc = dict(acc)
                    new_acc["__count__"] = acc["__count__"] + _segment.seg_sum(
                        wm, gids, capacity
                    )
                    for o, side, name in spec_plan:
                        key = f"s:{o}"
                        if name == "count":
                            continue  # __count__ serves every count spec
                        if side == 0:
                            val = evaluator.device_eval(
                                arg_exprs[o], env, aux_v
                            ).astype(jnp.float64)
                            if name in ("sum", "mean"):
                                new_acc[key] = acc[key] + _segment.seg_sum(
                                    val * wm, gids, capacity
                                )
                            elif name == "min":
                                new_acc[key] = jnp.minimum(
                                    acc[key],
                                    _segment.seg_min(val, gids, capacity, mask),
                                )
                            else:
                                new_acc[key] = jnp.maximum(
                                    acc[key],
                                    _segment.seg_max(val, gids, capacity, mask),
                                )
                        else:
                            if name in ("sum", "mean"):
                                g = stats[f"sum:{o}"][jk]
                                new_acc[key] = acc[key] + _segment.seg_sum(
                                    jnp.where(mask, g, 0.0), gids, capacity
                                )
                            elif name == "min":
                                g = stats[f"min:{o}"][jk]
                                new_acc[key] = jnp.minimum(
                                    acc[key],
                                    _segment.seg_min(g, gids, capacity, mask),
                                )
                            else:
                                g = stats[f"max:{o}"][jk]
                                new_acc[key] = jnp.maximum(
                                    acc[key],
                                    _segment.seg_max(g, gids, capacity, mask),
                                )
                    return new_acc, None

                init = {"__count__": jnp.zeros(capacity, jnp.float64)}
                for o, side, name in spec_plan:
                    if name == "count":
                        continue
                    if name in ("sum", "mean"):
                        init[f"s:{o}"] = jnp.zeros(capacity, jnp.float64)
                    elif name == "min":
                        init[f"s:{o}"] = jnp.full(capacity, jnp.inf)
                    else:
                        init[f"s:{o}"] = jnp.full(capacity, -jnp.inf)
                xs = (
                    tuple(cols[n] for n in col_names),
                    mask_all,
                    gids_all,
                )
                acc, _ = jax.lax.scan(body, init, xs)
                if ndev > 1:
                    merged = {}
                    merged["__count__"] = jax.lax.psum(acc["__count__"], axis)
                    for o, side, name in spec_plan:
                        if name == "count":
                            continue
                        k2 = f"s:{o}"
                        if name in ("sum", "mean"):
                            merged[k2] = jax.lax.psum(acc[k2], axis)
                        elif name == "min":
                            merged[k2] = jax.lax.pmin(acc[k2], axis)
                        else:
                            merged[k2] = jax.lax.pmax(acc[k2], axis)
                    acc = merged
                parts = [acc["__count__"]]
                for o, side, name in spec_plan:
                    if name != "count":
                        parts.append(acc[f"s:{o}"])
                return jnp.concatenate(parts)

            n_sharded = len(col_names) + 2
            n_repl = (
                len(stat_names)
                + len(aux_order)
                + (1 if narrow_names else 0)
            )
            in_specs = tuple([P(axis)] * n_sharded + [P()] * n_repl)
            program = jax.jit(
                jax.shard_map(
                    join_left_fn,
                    mesh=self.mesh,
                    in_specs=in_specs,
                    out_specs=P(),
                    check_vma=False,
                )
            )
            self._program_cache[sig] = (program, len(aux_order), None)
            _PROGRAMS.set(len(self._program_cache))
        program = self._program_cache[sig][0]
        args = [staged.blocks[n2] for n2 in col_names]
        args.append(staged.mask)
        args.append(staged.gids)
        args.extend(rstats[n2] for n2 in stat_names)
        args.extend(jnp.asarray(v) for v in aux.values())
        if staged.narrow_offsets:
            args.append(
                jnp.asarray(
                    [staged.narrow_offsets[n2] for n2 in narrow_names],
                    jnp.int64,
                )
            )
        from pixie_tpu.ops import segment as _segment

        with _segment.platform_hint(self.mesh.devices.flat[0].platform):
            buf = np.asarray(program(*args))
        counts = buf[:capacity]
        vals = {}
        off = capacity
        for o, side, name in spec_plan:
            if name != "count":
                vals[o] = buf[off : off + capacity]
                off += capacity
        n = max(key_plan.num_groups, 1) if m.agg_op.groups else 1
        keep = counts[:n] > 0 if m.agg_op.groups else np.ones(1, bool)
        rel = m.agg_op.output_relation(
            [self._join_pre_agg_relation(m, registry)], registry
        )
        out_cols: list = []
        for (g, _e), col in zip(m.group_exprs, key_plan.key_columns):
            out_cols.append(
                col.take(np.nonzero(keep)[0])
                if isinstance(col, DictColumn)
                else np.asarray(col)[keep]
            )
        for out_name, side, _e, name in m.specs:
            schema = rel.col(out_name)
            if name == "count":
                out = counts[:n][keep]
            elif name == "mean":
                out = vals[out_name][:n][keep] / np.maximum(
                    counts[:n][keep], 1.0
                )
            else:
                out = vals[out_name][:n][keep]
            dt = host_dtype(schema.data_type)
            if np.issubdtype(dt, np.integer):
                out = np.round(out).astype(dt)
            else:
                out = out.astype(dt)
            out_cols.append(out)
        return RowBatch(rel, out_cols, eow=True, eos=True)

    def _join_pre_agg_relation(self, m: "_JoinAggMatch", registry):
        """Relation the agg's output resolution expects: group columns (in
        left-source terms) + the post-join arg columns typed per side."""
        from pixie_tpu.types import ColumnSchema, Relation as _Relation

        cols = []
        seen = set()
        for g, e in m.group_exprs:
            cols.append(
                ColumnSchema(
                    g, expr_data_type(e, m.left_relation, registry)
                )
            )
            seen.add(g)
        # Arg columns: the AggOp's value exprs reference post-join names;
        # synthesize a relation typing each referenced column by its side.
        for out_name, agg in m.agg_op.values:
            for ref in referenced_columns(agg):
                if ref in seen:
                    continue
                for _o, side, arg_e, _n in m.specs:
                    if _o == out_name:
                        rel = (
                            m.left_relation if side == 0 else m.right_relation
                        )
                        try:
                            dt = expr_data_type(arg_e, rel, registry)
                        except (KeyError, ValueError):
                            dt = DataType.FLOAT64
                        cols.append(ColumnSchema(ref, dt))
                        seen.add(ref)
                        break
        return _Relation(cols)

    # -- device sort-merge join (r19) ----------------------------------------
    def _try_execute_join(
        self, fragment, relations, table_store, registry, func_ctx
    ) -> Optional[tuple[int, RowBatch]]:
        """Standalone equijoin on the mesh (r19): both sides stage under
        the fold path's geometry (ResidencyPool byte accounting, r13 codec
        on the wire, join-key ids riding the gids lane), the device orders
        the build side with ONE stable packed-key sort — reproducing the
        host EquijoinNode's per-key original row order — merges via
        searchsorted, and gathers match pairs plus compacted unmatched
        rows for the outer variants into statically-capped outputs
        (exact match/unmatched counts come from host bincounts, padded to
        a power of two). Bit-identical to the host JoinNode across all
        four join types; whatever follows the join runs on the host
        against the spliced batch. Returns None on any unsupported shape
        — offload is an optimization, never a correctness cliff."""
        if not flags.device_join:
            return None
        m = match_join(fragment, relations)
        if m is None:
            return None
        lt = table_store.get_table(m.left_source_op.table_name)
        rt = table_store.get_table(m.right_source_op.table_name)
        if lt is None or rt is None:
            return None
        # v1 gates: bare-column keys and outputs. r20 lifts the pre-join
        # predicate refusal: single-table conjunctive predicates from the
        # script suffix lower through the r16 normalizer (the digest pins
        # the staging identity) and filter each side ON THE HOST before
        # staging — boolean-mask selection preserves original row order,
        # so the device merge sees exactly the rows the host engine's
        # pre-join FilterNode keeps, in the same order, and INNER/LEFT
        # row-order bit-identity carries over unchanged. A predicate
        # outside the normalizable class still refuses to the host.
        lpred_digest = rpred_digest = ""
        if m.left_preds:
            lpred_digest = predicate_fold_digest(
                m.left_preds, m.left_relation, registry, func_ctx
            )
            if lpred_digest is None:
                return None
        if m.right_preds:
            rpred_digest = predicate_fold_digest(
                m.right_preds, m.right_relation, registry, func_ctx
            )
            if rpred_digest is None:
                return None
        if not all(
            isinstance(e, ColumnRef)
            for e in m.left_key_exprs + m.right_key_exprs
        ):
            return None
        out_plan = []  # [(side, source col, out name, DataType)]
        for side, in_col, out_name in m.join_op.output_columns:
            src_map = m.left_exprs if side == 0 else m.right_exprs
            e = substitute(ColumnRef(in_col), src_map)
            if not isinstance(e, ColumnRef):
                return None
            dt = m.out_relation.col(out_name).data_type
            if dt == DataType.STRING and (
                (lt if side == 0 else rt).dictionaries.get(e.name) is None
            ):
                return None
            out_plan.append((side, e.name, out_name, dt))
        lneed = {e.name for e in m.left_key_exprs}
        for p in m.left_preds:
            lneed |= referenced_columns(p)
        rneed = {e.name for e in m.right_key_exprs}
        for p in m.right_preds:
            rneed |= referenced_columns(p)
        lcols, nl = read_columns(
            lt,
            sorted(lneed),
            m.left_source_op.start_time,
            m.left_source_op.stop_time,
        )
        rcols, nr = read_columns(
            rt,
            sorted(rneed),
            m.right_source_op.start_time,
            m.right_source_op.stop_time,
        )
        # Host-evaluate each side's predicate mask over the same read the
        # keys came from (one snapshot), then filter keys before encoding;
        # the mask rides into staging as ``row_sel``.
        left_sel = right_sel = None
        if m.left_preds:
            left_sel = self._host_pred_mask(
                m.left_preds, m.left_relation, lt, lcols, registry,
                func_ctx,
            )
            if left_sel is None or len(left_sel) != nl:
                return None
            lcols = {c: np.asarray(a)[left_sel] for c, a in lcols.items()}
            nl = int(np.count_nonzero(left_sel))
        if m.right_preds:
            right_sel = self._host_pred_mask(
                m.right_preds, m.right_relation, rt, rcols, registry,
                func_ctx,
            )
            if right_sel is None or len(right_sel) != nr:
                return None
            rcols = {c: np.asarray(a)[right_sel] for c, a in rcols.items()}
            nr = int(np.count_nonzero(right_sel))
        if nl == 0 or nr == 0:
            return None  # trivial side: the host hash join wins outright
        if nl + nr < flags.device_join_min_rows:
            return None
        # Shared join-key id space over BOTH sides (the join-agg idiom):
        # string keys align through one StringDictionary, then a
        # GroupEncoder densifies; right-only keys get ids the left never
        # uses, so they match nothing.
        lkey_arrays, rkey_arrays = [], []
        for le, re_ in zip(m.left_key_exprs, m.right_key_exprs):
            la, ra = lcols[le.name], rcols[re_.name]
            lt_dt = m.left_relation.col(le.name).data_type
            rt_dt = m.right_relation.col(re_.name).data_type
            if lt_dt == DataType.STRING or rt_dt == DataType.STRING:
                if lt_dt != rt_dt:
                    return None
                shared = StringDictionary()
                dl = lt.dictionaries.get(le.name)
                dr = rt.dictionaries.get(re_.name)
                if dl is None or dr is None:
                    return None
                lut_l = shared.encode(
                    np.asarray(list(dl.values()), dtype=object)
                )
                lut_r = shared.encode(
                    np.asarray(list(dr.values()), dtype=object)
                )
                la = lut_l[la] if len(lut_l) else la
                ra = lut_r[ra] if len(lut_r) else ra
            lkey_arrays.append(np.asarray(la))
            rkey_arrays.append(np.asarray(ra))
        enc = GroupEncoder()
        kl = enc.encode(lkey_arrays)
        kr = enc.encode(rkey_arrays)
        K = max(enc.num_groups, 1)
        if K > (1 << 22):
            return None
        # Exact output cardinalities from host bincounts — they size the
        # static gather caps AND the host-side result slices.
        count_l = np.bincount(kl, minlength=K).astype(np.int64)
        count_r = np.bincount(kr, minlength=K).astype(np.int64)
        how = m.join_op.how
        M = int((count_l * count_r).sum())
        UR = (
            int(count_r[count_l == 0].sum())
            if how in (JoinType.RIGHT, JoinType.OUTER)
            else 0
        )
        UL = (
            int(count_l[count_r == 0].sum())
            if how in (JoinType.LEFT, JoinType.OUTER)
            else 0
        )
        if M + UR + UL > flags.device_join_max_out:
            return None
        cap_m = _pow2_at_least(max(M, 1))
        cap_r = _pow2_at_least(max(UR, 1)) if UR or (
            how in (JoinType.RIGHT, JoinType.OUTER)
        ) else 0
        cap_l = _pow2_at_least(max(UL, 1)) if UL or (
            how in (JoinType.LEFT, JoinType.OUTER)
        ) else 0
        # Fault site: poison the device join dispatch (chaos tests prove
        # the r9 breaker trips and the host JoinNode result is identical).
        if faults.ACTIVE:
            faults.check("device.join_dispatch")
        # Both stagings' identity must pin the WHOLE key space: left keys
        # encode first, so either side's content changes both sides' ids
        # (the r4 ":joinright:" precedent).
        key_space_sig = (
            m.left_source_op.table_name,
            (lt.min_row_id(), lt.end_row_id()),
            m.right_source_op.table_name,
            (rt.min_row_id(), rt.end_row_id()),
            repr(m.left_key_exprs) + repr(m.right_key_exprs),
            m.left_source_op.start_time,
            m.left_source_op.stop_time,
            m.right_source_op.start_time,
            m.right_source_op.stop_time,
            lpred_digest,
            rpred_digest,
        )
        # A side with no output columns still needs mask+gids lanes on
        # device; stage its (cheap, already-read) first key column.
        cols_l = sorted(
            {src for side, src, _o, _dt in out_plan if side == 0}
            or {m.left_key_exprs[0].name}
        )
        cols_r = sorted(
            {src for side, src, _o, _dt in out_plan if side == 1}
            or {m.right_key_exprs[0].name}
        )
        # r21 distributed sort-merge (tentpole): on a multi-axis mesh,
        # range-partition both sides by packed key across the hosts
        # axis and sort+merge locally per shard, instead of replicating
        # the whole key space onto every device. Any refusal falls
        # through to the replicated v1 path below — never to the host.
        if (
            flags.mesh_distributed_join
            and len(self.mesh_axes) > 1
            and int(self.mesh.devices.shape[0]) > 1
        ):
            out = self._try_partitioned_join(
                m, lt, rt, kl, kr, K, count_l, count_r, how,
                out_plan, key_space_sig, cols_l, cols_r,
                left_sel, right_sel, nl, nr,
            )
            if out is not None:
                return m.join_nid, out
        ck_l = (
            m.left_source_op.table_name,
            (lt.min_row_id(), lt.end_row_id()),
            tuple(cols_l),
            m.left_source_op.start_time,
            m.left_source_op.stop_time,
            self.block_rows,
            ":joindevL:" + repr(key_space_sig),
            K,
            (),
        )
        ck_r = (
            m.right_source_op.table_name,
            (rt.min_row_id(), rt.end_row_id()),
            tuple(cols_r),
            m.right_source_op.start_time,
            m.right_source_op.stop_time,
            self.block_rows,
            ":joindevR:" + repr(key_space_sig),
            K,
            (),
        )
        staged_l = self._stage_cached(
            ck_l, lt, m.left_source_op, cols_l,
            _KeyPlan(host_gids=kl.astype(np.int32), num_groups=K),
            row_sel=left_sel,
            f64_bits=True,
        )
        if staged_l is None or staged_l.num_rows != nl:
            return None
        staged_r = self._stage_cached(
            ck_r, rt, m.right_source_op, cols_r,
            _KeyPlan(host_gids=kr.astype(np.int32), num_groups=K),
            row_sel=right_sel,
            f64_bits=True,
        )
        if staged_r is None or staged_r.num_rows != nr:
            return None
        out = self._run_device_join(
            m, lt, rt, staged_l, staged_r, ck_l, ck_r, out_plan,
            M, UR, UL, cap_m, cap_r, cap_l, K,
        )
        if out is None:
            return None
        return m.join_nid, out

    def _host_pred_mask(
        self, preds, relation, table, cols, registry, func_ctx
    ):
        """AND of pre-join predicates evaluated on the host over the
        already-read columns — the same ExpressionEvaluator the host
        FilterNode runs, so the kept-row set (and its order under
        boolean-mask selection) is bit-identical to the host plan's
        pre-join filter. None refuses: missing dictionary, column not
        read, or an unresolvable UDF sends the join back to the host
        engine."""
        from pixie_tpu.types import Relation as _Relation

        needed = set()
        for p in preds:
            needed |= referenced_columns(p)
        if not needed:
            return None  # constant predicates: host engine's job
        schemas, batch_cols = [], []
        for name in sorted(needed):
            arr = cols.get(name)
            if arr is None:
                return None
            schema = relation.col(name)
            if schema.data_type == DataType.STRING:
                d = table.dictionaries.get(name)
                if d is None:
                    return None
                arr = DictColumn(np.asarray(arr).astype(np.int32), d)
            schemas.append(schema)
            batch_cols.append(arr)
        sub_rel = _Relation(schemas)
        batch = RowBatch(sub_rel, batch_cols)
        mask = None
        try:
            for i, p in enumerate(preds):
                ev = ExpressionEvaluator(
                    [(f"p{i}", p)], sub_rel, registry, func_ctx
                )
                m2 = ev.evaluate_predicate(batch)
                mask = m2 if mask is None else (mask & m2)
        except (ValueError, KeyError):
            return None
        return mask

    def _run_device_join(
        self, m, lt, rt, staged_l, staged_r, ck_l, ck_r, out_plan,
        M, UR, UL, cap_m, cap_r, cap_l, K,
    ):
        """Compile-or-reuse the sort-merge join program and dispatch it.
        Output layout per column is three statically-capped sections
        [matched cap_m | probe-unmatched cap_r | build-unmatched cap_l];
        the host slices the exact counts back out. Match pairs are
        probe-row-major with build rows in stable per-key original order —
        exactly the host engine's emission for a single probe batch (and
        a multiset-identical one otherwise; join row order is not a
        contract, preserves_time_order=False)."""
        from pixie_tpu.ops import segment as _segment

        l_names = sorted(staged_l.blocks)
        r_names = sorted(staged_r.blocks)
        l_narrow = sorted(staged_l.narrow_offsets)
        r_narrow = sorted(staged_r.narrow_offsets)
        axis = self.mesh_axes  # collectives reduce over the FULL mesh
        ndev = staged_l.num_devices
        sig = "|".join(
            [
                "join",
                "joinlane:sort_merge",
                f"how:{m.join_op.how.value}",
                "L:" + ",".join(
                    f"{n2}:{a.shape}:{a.dtype}"
                    for n2, a in sorted(staged_l.blocks.items())
                ),
                f"lnarrow:{l_narrow}",
                "R:" + ",".join(
                    f"{n2}:{a.shape}:{a.dtype}"
                    for n2, a in sorted(staged_r.blocks.items())
                ),
                f"rnarrow:{r_narrow}",
                f"caps:{cap_m},{cap_r},{cap_l}",
                "out:" + ";".join(
                    f"{side}:{src}:{dt.name}"
                    for side, src, _o, dt in out_plan
                ),
                f"mesh:{self._mesh_sig}",
            ]
        )
        if sig not in self._program_cache:
            _segment.lane_count("join_sort_merge")

            def join_fn(*arrs):
                i = len(l_names)
                lcols = dict(zip(l_names, arrs[:i]))
                lmask_b, lgids_b = arrs[i], arrs[i + 1]
                i += 2
                rcols = dict(zip(r_names, arrs[i : i + len(r_names)]))
                i += len(r_names)
                rmask_b, rgids_b = arrs[i], arrs[i + 1]
                k_arr = arrs[i + 2]
                i += 3
                lnarrow_vec = rnarrow_vec = None
                if l_narrow:
                    lnarrow_vec = arrs[i]
                    i += 1
                if r_narrow:
                    rnarrow_vec = arrs[i]

                def flatten(a):
                    # Per-device [1, nblk, B] → the GLOBAL row order:
                    # staging packs rows device-contiguously with all
                    # padding at the tail, so all_gather + flatten is the
                    # original cursor order. The merge itself runs
                    # replicated (a join's output is a global ordering; a
                    # distributed merge is future work — the caps gate
                    # keeps the replicated sort affordable).
                    x = a[0].reshape(-1)
                    if ndev > 1:
                        x = jax.lax.all_gather(x, axis).reshape(-1)
                    return x

                lmask = flatten(lmask_b)
                lgid = flatten(lgids_b).astype(jnp.int32)
                rmask = flatten(rmask_b)
                rgid = flatten(rgids_b).astype(jnp.int32)
                kq = k_arr.astype(jnp.int32)
                # Padded rows take per-side sentinels ABOVE every real key
                # id so they can never pair (build pads K, probe pads K+1).
                lkey = jnp.where(lmask, lgid, kq)
                rkey = jnp.where(rmask, rgid, kq + 1)
                build_rows, probe_rows, _fan, ur, ul = (
                    _segment.local_sort_merge(
                        lkey, rkey, lmask, rmask, cap_m, cap_r, cap_l
                    )
                )
                outs = []
                for side, src, _o, dt in out_plan:
                    if side == 0:
                        col = flatten(lcols[src])
                        narrow_v = (
                            lnarrow_vec[l_narrow.index(src)]
                            if src in l_narrow
                            else None
                        )
                        midx, uidx_r, uidx_l = build_rows, None, ul
                    else:
                        col = flatten(rcols[src])
                        narrow_v = (
                            rnarrow_vec[r_narrow.index(src)]
                            if src in r_narrow
                            else None
                        )
                        midx, uidx_r, uidx_l = probe_rows, ur, None
                    nside = col.shape[0]
                    odt = jnp.int64 if narrow_v is not None else col.dtype
                    # Null rows carry the host engine's type defaults:
                    # 0/False for value columns, code -1 for string
                    # columns (decoded to "" host-side).
                    nullv = -1 if dt == DataType.STRING else 0

                    def gath(idx, col=col, narrow_v=narrow_v, nside=nside):
                        g = col[jnp.clip(idx, 0, nside - 1)]
                        if narrow_v is not None:
                            g = g.astype(jnp.int64) + narrow_v
                        return g

                    secs = [gath(midx)]
                    if cap_r:
                        secs.append(
                            gath(uidx_r)
                            if uidx_r is not None
                            else jnp.full(cap_r, nullv, odt)
                        )
                    if cap_l:
                        secs.append(
                            gath(uidx_l)
                            if uidx_l is not None
                            else jnp.full(cap_l, nullv, odt)
                        )
                    outs.append(
                        jnp.concatenate(secs) if len(secs) > 1 else secs[0]
                    )
                return tuple(outs)

            n_sharded = len(l_names) + 2 + len(r_names) + 2
            n_repl = 1 + (1 if l_narrow else 0) + (1 if r_narrow else 0)
            program = jax.jit(
                jax.shard_map(
                    join_fn,
                    mesh=self.mesh,
                    in_specs=tuple([P(axis)] * n_sharded + [P()] * n_repl),
                    out_specs=tuple([P()] * len(out_plan)),
                    check_vma=False,
                )
            )
            self._program_cache[sig] = (program, 0, None)
            _PROGRAMS.set(len(self._program_cache))
        program = self._program_cache[sig][0]
        args = [staged_l.blocks[n2] for n2 in l_names]
        args.append(staged_l.mask)
        args.append(staged_l.gids)
        args += [staged_r.blocks[n2] for n2 in r_names]
        args.append(staged_r.mask)
        args.append(staged_r.gids)
        args.append(jnp.asarray(K, jnp.int32))
        if l_narrow:
            args.append(
                jnp.asarray(
                    [staged_l.narrow_offsets[n2] for n2 in l_narrow],
                    jnp.int64,
                )
            )
        if r_narrow:
            args.append(
                jnp.asarray(
                    [staged_r.narrow_offsets[n2] for n2 in r_narrow],
                    jnp.int64,
                )
            )
        # Pin BOTH staged sides for the dispatch (r12): a concurrent
        # query's watermark eviction must not drop either mid-join.
        with self._staged_cache.pin(ck_l):
            with self._staged_cache.pin(ck_r):
                with _segment.platform_hint(
                    self.mesh.devices.flat[0].platform
                ):
                    outs = program(*args)
        data = {}
        for ci, (side, src, out_name, dt) in enumerate(out_plan):
            arr = np.asarray(outs[ci])
            segs = [arr[:M]]
            off = cap_m
            if cap_r:
                segs.append(arr[off : off + UR])
                off += cap_r
            if cap_l:
                segs.append(arr[off : off + UL])
            a = np.concatenate(segs) if len(segs) > 1 else segs[0]
            if dt == DataType.STRING:
                codes = a.astype(np.int32)
                d2 = (lt if side == 0 else rt).dictionaries.get(src)
                if d2 is None:
                    return None
                if (codes < 0).any():
                    # Outer-null rows decode to "" — the host engine's
                    # type-default padding (join_node._null_batch);
                    # from_pydict re-encodes the object array.
                    vocab = np.asarray(list(d2.values()), dtype=object)
                    vals = np.empty(len(codes), dtype=object)
                    neg = codes < 0
                    vals[~neg] = vocab[codes[~neg]]
                    vals[neg] = ""
                    data[out_name] = vals
                else:
                    data[out_name] = DictColumn(codes, d2)
            else:
                data[out_name] = _f64_from_bits(a, dt)
        return RowBatch.from_pydict(
            m.out_relation, data, eow=True, eos=True
        )

    def _try_partitioned_join(
        self, m, lt, rt, kl, kr, K, count_l, count_r, how,
        out_plan, key_space_sig, cols_l, cols_r,
        left_sel, right_sel, nl, nr,
    ):
        """Distributed sort-merge join over the hosts axis (r21): both
        sides range-partition by packed key id into one contiguous key
        range per host (balanced by per-key join work from the exact
        host bincounts), stage shard-major so every host's devices hold
        only its shard, and each host sorts + merges its shard locally
        (all_gather over the INNER axes only). Shard outputs then
        concatenate over the hosts axis and the host reorders them to
        the engine's emission order — bit-identical to both the v1
        replicated lane and the host EquijoinNode. Returns the spliced
        RowBatch, or None to fall through to the v1 replicated path."""
        H = int(self.mesh.devices.shape[0])
        # Balanced contiguous key ranges: per-key cost = emitted pairs
        # plus the rows that move (both exact).
        work = count_l * count_r + count_l + count_r
        cum = np.cumsum(work)
        total_w = int(cum[-1]) if len(cum) else 0
        if total_w <= 0:
            return None
        targets = (np.arange(1, H, dtype=np.int64) * total_w) // H
        bounds = np.searchsorted(cum, targets, side="left")
        key_shard = np.searchsorted(
            bounds, np.arange(K), side="right"
        ).astype(np.int32)
        shard_l = key_shard[kl]
        shard_r = key_shard[kr]
        # Stable shard-major permutations: original row order survives
        # WITHIN each shard, which is what makes the host-side inverse
        # reorder below exact.
        perm_l = np.argsort(shard_l, kind="stable")
        perm_r = np.argsort(shard_r, kind="stable")
        rows_l = np.bincount(shard_l, minlength=H).astype(np.int64)
        rows_r = np.bincount(shard_r, minlength=H).astype(np.int64)
        # Exact per-shard output counts -> uniform static caps (the
        # max over shards, so one compiled program serves every shard).
        m_s = np.zeros(H, np.int64)
        np.add.at(m_s, key_shard, count_l * count_r)
        ur_s = np.zeros(H, np.int64)
        np.add.at(ur_s, key_shard, np.where(count_l == 0, count_r, 0))
        ul_s = np.zeros(H, np.int64)
        np.add.at(ul_s, key_shard, np.where(count_r == 0, count_l, 0))
        cap_m_s = _pow2_at_least(max(int(m_s.max()), 1))
        cap_r_s = (
            _pow2_at_least(max(int(ur_s.max()), 1))
            if how in (JoinType.RIGHT, JoinType.OUTER)
            else 0
        )
        cap_l_s = (
            _pow2_at_least(max(int(ul_s.max()), 1))
            if how in (JoinType.LEFT, JoinType.OUTER)
            else 0
        )
        staged_l, ck_l = self._stage_partitioned_side(
            lt, m.left_source_op, cols_l, kl, perm_l, rows_l, K,
            left_sel, nl, key_space_sig, H, "L",
        )
        if staged_l is None:
            return None
        staged_r, ck_r = self._stage_partitioned_side(
            rt, m.right_source_op, cols_r, kr, perm_r, rows_r, K,
            right_sel, nr, key_space_sig, H, "R",
        )
        if staged_r is None:
            return None
        outs = self._run_partitioned_join(
            m, staged_l, staged_r, ck_l, ck_r, out_plan, K, H,
            cap_m_s, cap_r_s, cap_l_s,
        )
        if outs is None:
            return None
        # Inverse reorder to the engine's emission order. Matched pairs:
        # the device emits probe-row-major per shard; the engine emits
        # probe-row-major over the ORIGINAL probe order with per-probe
        # build matches contiguous — so a stable argsort of the emitted
        # original probe indices (fanout-repeated) is the exact inverse.
        fan_r = count_l[kr[perm_r]]
        order_m = np.argsort(
            np.repeat(perm_r, fan_r), kind="stable"
        )
        emit_r = perm_r[(count_l[kr] == 0)[perm_r]]
        order_r = np.argsort(emit_r, kind="stable")
        emit_l = perm_l[(count_r[kl] == 0)[perm_l]]
        order_l = np.argsort(emit_l, kind="stable")
        sect = cap_m_s + cap_r_s + cap_l_s
        data = {}
        for ci, (side, src, out_name, dt) in enumerate(out_plan):
            arr = np.asarray(outs[ci]).reshape(H, sect)
            segs = [
                np.concatenate(
                    [arr[h, : m_s[h]] for h in range(H)]
                )[order_m]
            ]
            off = cap_m_s
            if cap_r_s:
                segs.append(
                    np.concatenate(
                        [arr[h, off : off + ur_s[h]] for h in range(H)]
                    )[order_r]
                )
                off += cap_r_s
            if cap_l_s:
                segs.append(
                    np.concatenate(
                        [arr[h, off : off + ul_s[h]] for h in range(H)]
                    )[order_l]
                )
            a = np.concatenate(segs) if len(segs) > 1 else segs[0]
            if dt == DataType.STRING:
                codes = a.astype(np.int32)
                d2 = (lt if side == 0 else rt).dictionaries.get(src)
                if d2 is None:
                    return None
                if (codes < 0).any():
                    vocab = np.asarray(list(d2.values()), dtype=object)
                    vals = np.empty(len(codes), dtype=object)
                    neg = codes < 0
                    vals[~neg] = vocab[codes[~neg]]
                    vals[neg] = ""
                    data[out_name] = vals
                else:
                    data[out_name] = DictColumn(codes, d2)
            else:
                data[out_name] = _f64_from_bits(a, dt)
        return RowBatch.from_pydict(
            m.out_relation, data, eow=True, eos=True
        )

    def _stage_partitioned_side(
        self, table, src_op, cols_needed, kk, perm, rows_s, K,
        sel, n_expect, key_space_sig, H, tag,
    ):
        """Read-filter-permute-stage one join side shard-major, with the
        same residency registration and OOM clear-and-retry policy as
        _stage_cached (which cannot express a reorder: its row_sel is
        an order-preserving boolean mask)."""
        from pixie_tpu.parallel import staging as _staging

        ck = (
            src_op.table_name,
            (table.min_row_id(), table.end_row_id()),
            tuple(cols_needed),
            src_op.start_time,
            src_op.stop_time,
            self.block_rows,
            f":meshjoin{tag}:{H}:" + repr(key_space_sig),
            K,
            (),
        )
        staged = self._staged_lookup(ck)
        if staged is not None and staged.num_rows == n_expect:
            return staged, ck
        cols, n = read_columns(
            table,
            sorted(set(cols_needed)),
            src_op.start_time,
            src_op.stop_time,
        )
        if sel is not None:
            if len(sel) != n:
                return None, None  # table moved under us
            cols = {c: np.asarray(a)[sel] for c, a in cols.items()}
            n = int(np.count_nonzero(sel))
        if n != n_expect or len(kk) != n:
            return None, None  # table moved under us
        cols = _f64_as_bits({c: np.asarray(a)[perm] for c, a in cols.items()})
        gids = kk[perm].astype(np.int32)

        def _do():
            return _staging.stage_partitioned(
                self.mesh, cols, gids, rows_s, K,
                block_rows=self.block_rows,
            )

        try:
            staged = _do()
        except Exception as e:
            if "RESOURCE_EXHAUSTED" not in str(e) and (
                "Out of memory" not in str(e)
            ):
                raise
            self._staged_cache.clear(reason="oom")
            staged = _do()
        self._staged_insert(ck, staged, src_op.table_name, ck[1])
        return staged, ck

    def _run_partitioned_join(
        self, m, staged_l, staged_r, ck_l, ck_r, out_plan, K, H,
        cap_m_s, cap_r_s, cap_l_s,
    ):
        """Compile-or-reuse the partitioned merge program. Identical to
        the v1 program except: flatten gathers over the INNER axes only
        (each host assembles its own shard), caps are per-shard, and
        every output concatenates over the hosts axis — per-host layout
        [matched cap_m_s | probe-unmatched cap_r_s | build-unmatched
        cap_l_s], global shape [H * sect]."""
        from pixie_tpu.ops import segment as _segment

        l_names = sorted(staged_l.blocks)
        r_names = sorted(staged_r.blocks)
        l_narrow = sorted(staged_l.narrow_offsets)
        r_narrow = sorted(staged_r.narrow_offsets)
        axes = self.mesh_axes
        inner = axes[1:]
        sig = "|".join(
            [
                "join",
                "joinlane:partitioned",
                f"how:{m.join_op.how.value}",
                "L:" + ",".join(
                    f"{n2}:{a.shape}:{a.dtype}"
                    for n2, a in sorted(staged_l.blocks.items())
                ),
                f"lnarrow:{l_narrow}",
                "R:" + ",".join(
                    f"{n2}:{a.shape}:{a.dtype}"
                    for n2, a in sorted(staged_r.blocks.items())
                ),
                f"rnarrow:{r_narrow}",
                f"caps:{cap_m_s},{cap_r_s},{cap_l_s}",
                "out:" + ";".join(
                    f"{side}:{src}:{dt.name}"
                    for side, src, _o, dt in out_plan
                ),
                f"mesh:{self._mesh_sig}",
            ]
        )
        if sig not in self._program_cache:
            _segment.lane_count("join_partitioned")

            def partitioned_join_fn(*arrs):
                i = len(l_names)
                lcols = dict(zip(l_names, arrs[:i]))
                lmask_b, lgids_b = arrs[i], arrs[i + 1]
                i += 2
                rcols = dict(zip(r_names, arrs[i : i + len(r_names)]))
                i += len(r_names)
                rmask_b, rgids_b = arrs[i], arrs[i + 1]
                k_arr = arrs[i + 2]
                i += 3
                lnarrow_vec = rnarrow_vec = None
                if l_narrow:
                    lnarrow_vec = arrs[i]
                    i += 1
                if r_narrow:
                    rnarrow_vec = arrs[i]

                def flatten(a):
                    # Per-device [1, nblk, B] -> THIS HOST's shard only:
                    # gather over the inner axes; the hosts axis stays
                    # partitioned (that is the whole point).
                    x = a[0].reshape(-1)
                    if inner:
                        x = jax.lax.all_gather(x, inner).reshape(-1)
                    return x

                lmask = flatten(lmask_b)
                lgid = flatten(lgids_b).astype(jnp.int32)
                rmask = flatten(rmask_b)
                rgid = flatten(rgids_b).astype(jnp.int32)
                kq = k_arr.astype(jnp.int32)
                # Same sentinels as v1: other shards' keys never appear
                # locally, so K / K+1 still top every local real id.
                lkey = jnp.where(lmask, lgid, kq)
                rkey = jnp.where(rmask, rgid, kq + 1)
                build_rows, probe_rows, _fan, ur, ul = (
                    _segment.local_sort_merge(
                        lkey, rkey, lmask, rmask,
                        cap_m_s, cap_r_s, cap_l_s,
                    )
                )
                outs = []
                for side, src, _o, dt in out_plan:
                    if side == 0:
                        col = flatten(lcols[src])
                        narrow_v = (
                            lnarrow_vec[l_narrow.index(src)]
                            if src in l_narrow
                            else None
                        )
                        midx, uidx_r, uidx_l = build_rows, None, ul
                    else:
                        col = flatten(rcols[src])
                        narrow_v = (
                            rnarrow_vec[r_narrow.index(src)]
                            if src in r_narrow
                            else None
                        )
                        midx, uidx_r, uidx_l = probe_rows, ur, None
                    nside = col.shape[0]
                    odt = jnp.int64 if narrow_v is not None else col.dtype
                    nullv = -1 if dt == DataType.STRING else 0

                    def gath(idx, col=col, narrow_v=narrow_v, nside=nside):
                        g = col[jnp.clip(idx, 0, nside - 1)]
                        if narrow_v is not None:
                            g = g.astype(jnp.int64) + narrow_v
                        return g

                    secs = [gath(midx)]
                    if cap_r_s:
                        secs.append(
                            gath(uidx_r)
                            if uidx_r is not None
                            else jnp.full(cap_r_s, nullv, odt)
                        )
                    if cap_l_s:
                        secs.append(
                            gath(uidx_l)
                            if uidx_l is not None
                            else jnp.full(cap_l_s, nullv, odt)
                        )
                    outs.append(
                        jnp.concatenate(secs) if len(secs) > 1 else secs[0]
                    )
                return tuple(outs)

            n_sharded = len(l_names) + 2 + len(r_names) + 2
            n_repl = 1 + (1 if l_narrow else 0) + (1 if r_narrow else 0)
            program = jax.jit(
                jax.shard_map(
                    partitioned_join_fn,
                    mesh=self.mesh,
                    in_specs=tuple(
                        [P(axes)] * n_sharded + [P()] * n_repl
                    ),
                    out_specs=tuple([P(axes[0])] * len(out_plan)),
                    check_vma=False,
                )
            )
            self._program_cache[sig] = (program, 0, None)
            _PROGRAMS.set(len(self._program_cache))
        program = self._program_cache[sig][0]
        args = [staged_l.blocks[n2] for n2 in l_names]
        args.append(staged_l.mask)
        args.append(staged_l.gids)
        args += [staged_r.blocks[n2] for n2 in r_names]
        args.append(staged_r.mask)
        args.append(staged_r.gids)
        args.append(jnp.asarray(K, jnp.int32))
        if l_narrow:
            args.append(
                jnp.asarray(
                    [staged_l.narrow_offsets[n2] for n2 in l_narrow],
                    jnp.int64,
                )
            )
        if r_narrow:
            args.append(
                jnp.asarray(
                    [staged_r.narrow_offsets[n2] for n2 in r_narrow],
                    jnp.int64,
                )
            )
        if faults.ACTIVE:
            faults.check("device.join_dispatch")
        with self._staged_cache.pin(ck_l):
            with self._staged_cache.pin(ck_r):
                with _segment.platform_hint(
                    self.mesh.devices.flat[0].platform
                ):
                    return program(*args)

    # -- device scan (filter/project/limit, no aggregate) --------------------
    def _try_execute_scan(
        self, fragment, relations, table_store, registry, func_ctx
    ) -> Optional[tuple[int, RowBatch]]:
        from pixie_tpu.types.dtypes import host_dtype

        m = match_scan_fragment(fragment, relations)
        if m is None:
            return None
        if m.limit > flags.device_scan_limit_cap:
            return None  # unbounded-ish output: host path wins the fetch
        table = table_store.get_table(m.source_op.table_name)
        if table is None:
            return None
        # String outputs must be bare source columns so codes decode
        # through the table dictionary host-side.
        for name, e in m.out_exprs:
            if m.out_relation.col(name).data_type == DataType.STRING and (
                not isinstance(e, ColumnRef)
            ):
                return None
        named = [(f"pred{i}", p) for i, p in enumerate(m.predicates)]
        named += [(f"out:{name}", e) for name, e in m.out_exprs]
        try:
            evaluator = ExpressionEvaluator(
                named, m.source_relation, registry, func_ctx
            )
        except ValueError:
            return None
        base_cols = set()
        for e in m.predicates:
            base_cols |= referenced_columns(e)
        for _, e in m.out_exprs:
            base_cols |= referenced_columns(e)
        version = (table.min_row_id(), table.end_row_id())
        cache_key = (
            m.source_op.table_name,
            version,
            tuple(sorted(base_cols)),
            m.source_op.start_time,
            m.source_op.stop_time,
            self.block_rows,
            ":scan",
            0,
            (),
        )
        staged = self._stage_cached(
            cache_key, table, m.source_op, base_cols, _KeyPlan(num_groups=0)
        )
        if staged is None:
            return None
        aux = {}
        for name, e in evaluator.named_exprs:
            aux.update(evaluator.build_aux(e, table.dictionaries))
        out_dtypes = []
        for name, e in m.out_exprs:
            schema = m.out_relation.col(name)
            if schema.data_type == DataType.STRING:
                out_dtypes.append(np.dtype(np.int32))  # codes
            else:
                out_dtypes.append(np.dtype(host_dtype(schema.data_type)))
        aux_vals = list(aux.values())
        sig = "|".join(
            [
                "scan",
                ",".join(
                    f"{n2}:{a.shape}:{a.dtype}"
                    for n2, a in sorted(staged.blocks.items())
                ),
                f"narrow:{sorted(staged.narrow_offsets)}",
                f"limit:{m.limit}",
                "preds:" + ";".join(repr(p) for p in m.predicates),
                "outs:" + ";".join(f"{n2}={e!r}" for n2, e in m.out_exprs),
                "aux:" + ",".join(
                    f"{np.shape(v)}:{np.asarray(v).dtype}" for v in aux_vals
                ),
                f"mesh:{self._mesh_sig}",
            ]
        )
        assert f"mesh:{self._mesh_sig}" in sig  # geometry guard (r21)
        entry = self._program_cache.get(sig)
        if entry is None:
            program = self._build_scan_program(
                m, evaluator, staged, list(aux.keys()), out_dtypes
            )
            self._program_cache[sig] = (program, len(aux_vals), None)
            _PROGRAMS.set(len(self._program_cache))
        program = self._program_cache[sig][0]
        args = [staged.blocks[n2] for n2 in sorted(staged.blocks)]
        args.append(staged.mask)
        args.extend(jnp.asarray(v) for v in aux_vals)
        if staged.narrow_offsets:
            args.append(
                jnp.asarray(
                    [
                        staged.narrow_offsets[n2]
                        for n2 in sorted(staged.narrow_offsets)
                    ],
                    jnp.int64,
                )
            )
        from pixie_tpu.ops import segment as _segment

        # Pin the staged entry for the dispatch + prefix fetch (r12): a
        # concurrent query's eviction pass must not drop it mid-scan.
        with self._staged_cache.pin(cache_key):
            with _segment.platform_hint(self.mesh.devices.flat[0].platform):
                outs = program(*args)
        written = np.asarray(outs[0])  # [D]
        cap_out = m.limit + staged.block_rows
        ndev = staged.num_devices
        remaining = m.limit
        col_parts: list[list[np.ndarray]] = [[] for _ in m.out_exprs]
        for d in range(ndev):
            take = min(int(written[d]), remaining)
            if take <= 0:
                continue
            for ci in range(len(m.out_exprs)):
                # Slice on device; fetch only the selected prefix.
                col_parts[ci].append(
                    np.asarray(outs[1 + ci][d * cap_out : d * cap_out + take])
                )
            remaining -= take
        out_cols = []
        for (name, e), dt, parts in zip(m.out_exprs, out_dtypes, col_parts):
            arr = (
                np.concatenate(parts)
                if parts
                else np.empty(0, dt)
            )
            schema = m.out_relation.col(name)
            if schema.data_type == DataType.STRING:
                d2 = table.dictionaries.get(e.name)
                if d2 is None:
                    return None
                out_cols.append(DictColumn(arr.astype(np.int32), d2))
            else:
                out_cols.append(arr.astype(dt))
        batch = RowBatch(m.out_relation, out_cols, eow=True, eos=True)
        return m.limit_nid, batch

    def _staged_lookup(self, cache_key):
        # ResidencyPool.get LRU-touches on hit.
        return self._staged_cache.get(cache_key)

    def _stage_cached(
        self,
        cache_key,
        table,
        src_op,
        cols_needed,
        key_plan,
        extra_cols=None,
        f32_cols=None,
        row_sel=None,
        f64_bits=False,
    ):
        """Cache-or-stage with the shared OOM clear-and-retry policy.
        Returns the StagedColumns (staged.num_rows tells callers what the
        cursor actually saw). One implementation for the scan and join
        paths — three hand-rolled copies drifted in r4 review.

        ``row_sel`` (r20): a boolean mask over the UNFILTERED read —
        the join pushdown's host-evaluated pre-join predicates. The
        selection applies after the read (boolean-mask indexing keeps
        original row order, matching the host FilterNode), the mask
        length doubling as the table-moved race guard; ``key_plan``
        gids are the caller's FILTERED encoding. ``f64_bits`` stages
        float64 columns as their bit patterns (_f64_as_bits)."""
        staged = self._staged_lookup(cache_key)
        if staged is not None:
            return staged
        base_row = table.min_row_id()
        cols, n = read_columns(
            table,
            sorted(set(cols_needed)),
            src_op.start_time,
            src_op.stop_time,
        )
        if row_sel is not None:
            if len(row_sel) != n:
                return None  # table moved under us
            cols = {c: np.asarray(a)[row_sel] for c, a in cols.items()}
            n = int(np.count_nonzero(row_sel))
        for name, arr in (extra_cols or {}).items():
            if len(arr) != n:
                return None  # table moved under us
            cols[name] = arr
        if key_plan.host_gids is not None and len(key_plan.host_gids) != n:
            return None
        if f64_bits:
            cols = _f64_as_bits(cols)
        elif not extra_cols and row_sel is None:
            # Resident-ingest fast path (r13): assemble the staging from
            # HBM ring windows + a compressed cold tail — the scan/join
            # analogue of the stream loop's per-window substitution.
            staged = self._try_resident_assemble(
                table, src_op, cols, n, key_plan, f32_cols, base_row
            )
            if staged is not None:
                self._staged_insert(
                    cache_key, staged, src_op.table_name, cache_key[1]
                )
                return staged
        try:
            staged = self._stage(cols, n, key_plan, table, f32_cols)
        except Exception as e:
            if "RESOURCE_EXHAUSTED" not in str(e) and (
                "Out of memory" not in str(e)
            ):
                raise
            self._staged_cache.clear(reason="oom")
            staged = None
        if staged is None:
            staged = self._stage(cols, n, key_plan, table, f32_cols)
        self._staged_insert(
            cache_key, staged, src_op.table_name, cache_key[1]
        )
        return staged

    def _try_resident_assemble(
        self, table, src_op, cols, n, key_plan, f32_cols, base_row
    ):
        """Build a StagedColumns from HBM-resident ring windows plus a
        compressed cold tail (r13). Returns None whenever the fast path
        does not apply — no ring, misaligned geometry, zero hits — or on
        any failure (recorded like stream fallbacks; the caller stages
        monolithically, still correct)."""
        ring = self._resident_ring(table, src_op)
        if ring is None or n <= 0 or not cols:
            return None
        try:
            from pixie_tpu.parallel import staging as _staging

            plan = _staging.plan_stream(
                self.mesh,
                cols,
                n,
                ring.window_rows,
                block_rows=self.block_rows,
                f32_cols=f32_cols,
                cell_cols=None,
                num_groups=max(key_plan.num_groups, 1),
                has_gids=key_plan.host_gids is not None,
                gids=key_plan.host_gids,
            )
            if plan.window_rows != ring.window_rows or (
                (plan.d, plan.nblk, plan.b)
                != (ring.d, ring.nblk, ring.b)
            ):
                return None
            col_names = sorted(cols)
            hits = {}
            for w in range(plan.n_windows):
                rows_w = min(
                    plan.window_rows, n - w * plan.window_rows
                )
                rw = ring.lookup(
                    base_row + w * plan.window_rows, rows_w, col_names
                )
                if rw is not None:
                    hits[w] = rw
            if not hits:
                return None  # all-cold: monolithic staging is simpler
            if plan.codecs:
                self._kick_decode_aot(plan)
            dec_cache: dict = {}
            gids = key_plan.host_gids
            win_blocks, win_masks, win_gids = [], [], []
            for w in range(plan.n_windows):
                rows_w = min(
                    plan.window_rows, n - w * plan.window_rows
                )
                rows, packed, pgids, nbytes = _staging.pack_stream_window(
                    plan, cols, gids, w, w in hits
                )
                if w in hits:
                    dev_cols = self._convert_resident_window(
                        plan, hits[w], col_names
                    )
                else:
                    dev_cols = self._put_window_cols(
                        plan, packed, col_names, dec_cache
                    )
                win_blocks.append(dev_cols)
                win_masks.append(
                    _staging._build_mask(
                        self.mesh, plan.d, plan.nblk, plan.b, rows
                    )
                )
                win_gids.append(
                    _staging.put_window_gids(
                        self.mesh, pgids, plan.nblk, plan.b
                    )
                )
                COLD_PROFILE["wire_bytes"] = COLD_PROFILE.get(
                    "wire_bytes", 0.0
                ) + float(nbytes)
                COLD_PROFILE["stage_bytes"] = COLD_PROFILE.get(
                    "stage_bytes", 0.0
                ) + float(
                    plan.window_block_nbytes()
                    + _staging.staged_gid_nbytes(pgids)
                )
            return _staging.concat_stream_windows(
                self.mesh, plan, win_blocks, win_masks, win_gids,
                key_plan.num_groups, key_plan.key_columns,
                table.dictionaries,
            )
        except Exception as e:
            import logging
            import traceback

            key = f"resident-assemble {type(e).__name__}: {e}"
            if key not in self.stream_fallback_errors:
                self.stream_fallback_errors[key] = traceback.format_exc()
                logging.getLogger("pixie_tpu.parallel").warning(
                    "resident assembly failed, staging monolithically: %s",
                    key,
                )
            return None

    def _staged_insert(self, cache_key, staged, table_name, version) -> None:
        """Register a staging with the residency pool: version
        supersession, the byte watermark (hbm_budget_mb), and the LRU
        entry cap all happen inside (serving/residency.py). Also records
        the table's observed staged bytes-per-row, which metadata
        admission control uses to estimate a query's staging cost
        BEFORE the cold stage (serving/admission.py, r13)."""
        self._staged_cache.insert(cache_key, staged, table_name, version)
        from pixie_tpu.serving.residency import staged_nbytes

        from pixie_tpu.parallel.staging import record_observed_bpr

        record_observed_bpr(
            table_name, staged_nbytes(staged), staged.num_rows
        )

    def _build_scan_program(
        self, m: _ScanMatch, evaluator, staged, aux_key_order, out_dtypes
    ):
        axis = self.mesh_axes  # collectives reduce over the FULL mesh
        col_names = sorted(staged.blocks)
        narrow_names = sorted(staged.narrow_offsets)
        limit = m.limit
        cap_out = limit + staged.block_rows
        preds = [
            e for n, e in evaluator.named_exprs if n.startswith("pred")
        ]
        outs = [
            (n[len("out:"):], e)
            for n, e in evaluator.named_exprs
            if n.startswith("out:")
        ]
        jdtypes = [jnp.dtype(dt) for dt in out_dtypes]

        def scan_fn(*arrs):
            i = len(col_names)
            cols = {n: a[0] for n, a in zip(col_names, arrs[:i])}
            mask_all = arrs[i][0]
            i += 1
            end = len(arrs)
            narrow_vec = None
            if narrow_names:
                narrow_vec = arrs[-1]
                end -= 1
            aux = dict(zip(aux_key_order, arrs[i:end]))
            nblk = mask_all.shape[0]
            bufs = tuple(jnp.zeros(cap_out, dt) for dt in jdtypes)

            def cond(carry):
                written, blk, _ = carry
                return (written < limit) & (blk < nblk)

            def body(carry):
                written, blk, bufs = carry
                env = {
                    n: jax.lax.dynamic_index_in_dim(
                        cols[n], blk, 0, keepdims=False
                    )
                    for n in col_names
                }
                for ni, nm in enumerate(narrow_names):
                    env[nm] = env[nm].astype(jnp.int64) + narrow_vec[ni]
                mask = jax.lax.dynamic_index_in_dim(
                    mask_all, blk, 0, keepdims=False
                )
                for p in preds:
                    mask = mask & evaluator.device_eval(p, env, aux)
                vals = [
                    evaluator.device_eval(e, env, aux).astype(dt)
                    for (_, e), dt in zip(outs, jdtypes)
                ]
                # Stable compaction: selected rows first, source order kept.
                key = (~mask).astype(jnp.int32)
                sorted_ops = jax.lax.sort(
                    tuple([key] + vals), num_keys=1, is_stable=True
                )
                cnt = jnp.sum(mask).astype(jnp.int32)
                new_bufs = tuple(
                    jax.lax.dynamic_update_slice(buf, sv, (written,))
                    for buf, sv in zip(bufs, sorted_ops[1:])
                )
                return (
                    jnp.minimum(written + cnt, jnp.int32(limit)),
                    blk + 1,
                    new_bufs,
                )

            written, _, bufs = jax.lax.while_loop(
                cond, body, (jnp.int32(0), jnp.int32(0), bufs)
            )
            return (written.reshape(1),) + bufs

        n_sharded = len(col_names) + 1
        n_repl = len(aux_key_order) + (1 if narrow_names else 0)
        in_specs = tuple([P(axis)] * n_sharded + [P()] * n_repl)
        out_specs = tuple([P(axis)] * (1 + len(jdtypes)))
        return jax.jit(
            jax.shard_map(
                scan_fn,
                mesh=self.mesh,
                in_specs=in_specs,
                out_specs=out_specs,
                check_vma=False,
            )
        )

    def _stage(self, cols, n, key_plan, table, f32_cols=None, int_dicts=None):
        return stage_columns(
            self.mesh,
            cols,
            n,
            gids=key_plan.host_gids,
            num_groups=max(key_plan.num_groups, 1),
            key_columns=key_plan.key_columns,
            dictionaries=table.dictionaries,
            block_rows=self.block_rows,
            f32_cols=f32_cols,
            int_dicts=int_dicts,
        )

    def _cell_cols(self, m: _Match, specs, capacity: int) -> dict:
        """Columns eligible for int-dictionary staging + the cell lane:
        INT64, consumed ONLY as the bare arg of cell-capable UDAs, and
        untouched by predicates/keys. Returns {col: max cardinality} —
        bounded so the per-(group, code) histogram einsum stays on the
        MXU's cheap side (capacity * C <= MATMUL_MAX_SEGMENTS)."""
        from pixie_tpu.ops import segment as _segment

        max_card = min(256, _segment.MATMUL_MAX_SEGMENTS // max(capacity, 1))
        if max_card < 2:
            return {}
        pred_refs = set()
        for p in m.predicates:
            pred_refs |= referenced_columns(p)
        key_refs = set()
        for g in m.agg_op.groups:
            key_refs |= referenced_columns(m.col_exprs[g])
        consumers: dict[str, list] = {}
        for _out, arg_e, uda in specs:
            if not uda.reads_args:
                continue
            for col in referenced_columns(arg_e):
                consumers.setdefault(col, []).append((arg_e, uda))
        out = {}
        for col, cons in consumers.items():
            if col in pred_refs or col in key_refs:
                continue
            try:
                if m.source_relation.col(col).data_type != DataType.INT64:
                    continue
            except KeyError:
                continue
            if all(
                isinstance(ae, ColumnRef) and u.cell_update is not None
                for ae, u in cons
            ):
                out[col] = max_card
        return out

    def _windowize_key_plan(
        self, m: _Match, table, key_plan, base_groups: int
    ):
        """(key_plan with gid' = wid*G + gid, n_windows) or None. Needs
        per-row gids host-side; device key plans are materialized the
        same way the join path does."""
        from pixie_tpu.parallel.staging import read_columns_windowed

        _cols, n, wids, n_windows = read_columns_windowed(
            table,
            [],
            m.source_op.start_time,
            m.source_op.stop_time,
        )
        if n_windows * base_groups > (1 << 22):
            return None  # state tensors would be unreasonable
        gids = key_plan.host_gids
        if gids is None:
            if key_plan.device_expr is None:
                gids = np.zeros(n, np.int32)  # group-by-none
            elif isinstance(key_plan.device_expr, ColumnRef):
                cols2, n2 = read_columns(
                    table,
                    [key_plan.device_expr.name],
                    m.source_op.start_time,
                    m.source_op.stop_time,
                )
                if n2 != n:
                    return None
                gids = np.maximum(cols2[key_plan.device_expr.name], 0)
            elif isinstance(key_plan.device_expr, tuple):
                _, src_col, lut_codes = key_plan.device_expr
                cols2, n2 = read_columns(
                    table,
                    [src_col],
                    m.source_op.start_time,
                    m.source_op.stop_time,
                )
                if n2 != n:
                    return None
                codes = np.maximum(cols2[src_col], 0)
                gids = np.asarray(lut_codes)[codes]
            else:
                return None
        if len(gids) != n or len(wids) != n:
            return None
        combined = (
            wids.astype(np.int64) * base_groups + gids.astype(np.int64)
        )
        return (
            dataclasses.replace(
                key_plan,
                host_gids=combined.astype(np.int32),
                device_expr=None,
                num_groups=n_windows * base_groups,
            ),
            n_windows,
        )

    def _plan_host_any(
        self, m: _Match, specs, key_plan, table
    ) -> dict:
        """any() without predicates needs ONE representative value per
        group — computable host-side from the key plan's gids in a single
        vectorized pass, so the device never pays the ~7ns/row scatter a
        segment-max costs (the only non-sum reduction in the hot configs;
        r5). Returns {out_name: per-group np array (codes for strings)},
        cached per (table version, window, keys, col)."""
        if m.predicates or m.agg_op.stage != AggStage.FULL:
            return {}
        cand = [
            (out, arg_e, uda)
            for out, arg_e, uda in specs
            if uda.name == "any"
            and uda.reads_args
            and isinstance(arg_e, ColumnRef)
        ]
        if not cand:
            return {}
        num_groups = max(key_plan.num_groups, 1)
        # Per-row gids host-side: the generic key plan has them; a
        # dictionary-code key IS the gid column.
        gids = key_plan.host_gids
        gid_col = None
        if gids is None:
            if isinstance(key_plan.device_expr, ColumnRef):
                gid_col = key_plan.device_expr.name
            else:
                return {}
        out = {}
        for out_name, arg_e, uda in cand:
            ck = (
                m.source_op.table_name,
                (table.min_row_id(), table.end_row_id()),
                m.source_op.start_time,
                m.source_op.stop_time,
                repr([m.col_exprs[g] for g in m.agg_op.groups]),
                arg_e.name,
            )
            rep = self._hostany_cache.get(ck)
            if rep is not None:
                # Real LRU: a hit refreshes recency (the r5 version was
                # FIFO despite the comment — the hottest entry could be
                # the first evicted).
                self._hostany_cache.move_to_end(ck)
            else:
                want = [arg_e.name] + ([gid_col] if gid_col else [])
                cols, n = read_columns(
                    table,
                    sorted(set(want)),
                    m.source_op.start_time,
                    m.source_op.stop_time,
                )
                g = gids if gids is not None else np.maximum(cols[gid_col], 0)
                if len(g) != n or n == 0 or int(g.max()) >= num_groups:
                    # Table moved under us (new dictionary codes appended
                    # after planning): fall back to the device path, like
                    # the host_gids length guard.
                    return {}
                vals = cols[arg_e.name]
                rep = np.zeros(num_groups, vals.dtype)
                # Reversed assignment: the LAST write per gid wins, which
                # is the FIRST occurrence in row order — one vectorized
                # pass, no sort.
                rep[g[::-1]] = vals[::-1]
                self._hostany_cache[ck] = rep
                while len(self._hostany_cache) > 32:
                    self._hostany_cache.popitem(last=False)
            out[out_name] = rep
        return out

    def _sketch_f32_cols(self, m: _Match, specs) -> set:
        """FLOAT64 source columns eligible for f32 staging: referenced ONLY
        as bare args of f32-state sketch UDAs (t-digest centroids are f32
        regardless), never by predicates, keys, or computed expressions —
        staging them f32 halves their host→HBM bytes at zero end-to-end
        precision change (cold staging is transfer-bound)."""
        from pixie_tpu.types import DataType as _DT

        f64_cols = {
            c.name
            for c in m.source_relation
            if c.data_type == _DT.FLOAT64
        }
        if not f64_cols:
            return set()
        blocked = set()
        for e in m.predicates:
            blocked |= referenced_columns(e)
        for g in m.agg_op.groups:
            blocked |= referenced_columns(m.col_exprs[g])
        out = set()
        for col in f64_cols - blocked:
            consumers = [
                (arg_e, uda)
                for _, arg_e, uda in specs
                if uda.reads_args and col in referenced_columns(arg_e)
            ]
            if consumers and all(
                isinstance(arg_e, ColumnRef) and uda.stage_f32_ok
                for arg_e, uda in consumers
            ):
                out.add(col)
        return out

    # -- compile helpers ----------------------------------------------------
    def _make_evaluator(self, m: _Match, specs, registry, func_ctx):
        named = [(f"pred{i}", p) for i, p in enumerate(m.predicates)]
        for out_name, arg_e, uda in specs:
            if not uda.reads_args:
                continue  # column never read: don't evaluate it either
            named.append((f"arg:{out_name}:0", arg_e))
        for g in m.agg_op.groups:
            named.append((f"key:{g}", m.col_exprs[g]))
        try:
            return ExpressionEvaluator(
                named, m.source_relation, registry, func_ctx
            )
        except ValueError:
            return None

    def _agg_specs(self, m: _Match, registry):
        """[(out_name, source-term arg exprs, uda)] or None if unresolvable."""
        pre_agg_rel_cols = m.col_exprs
        specs = []
        for out_name, agg in m.agg_op.values:
            arg_exprs = [substitute(a, pre_agg_rel_cols) for a in agg.args]
            try:
                types = [
                    expr_data_type(a, m.source_relation, registry)
                    for a in arg_exprs
                ]
            except (KeyError, ValueError):
                return None
            uda = registry.lookup_uda(agg.name, types)
            if uda is None:
                return None
            if not uda.reads_args:
                # Column never read (count): no arg constraints apply.
                specs.append((out_name, arg_exprs[0], uda))
                continue
            if len(arg_exprs) != 1:
                return None  # single-arg UDAs only on the fast path today
            if any(t == DataType.STRING for t in types) and (
                uda.string_args == "values"
            ):
                return None  # needs decoded strings: host engine only
            if types[0] == DataType.STRING and (
                uda.string_args == "hash" or uda.string_state
            ):
                # String identity/decodability requires the table dictionary:
                # only bare source columns qualify; computed string args fall
                # back to the host engine (which latches dictionaries).
                if not isinstance(arg_exprs[0], ColumnRef):
                    return None
            specs.append((out_name, arg_exprs[0], uda))
        return specs

    def _plan_keys(
        self, m: _Match, table, registry, func_ctx, base_cols: set, sp=None
    ) -> Optional[_KeyPlan]:
        """The group-key plan; ``sp`` (the caller's device.plan_keys
        span) gets ``batches`` (cursor batches walked), ``evals`` (key
        evaluations, one per chunk) and ``cached`` (key-plan cache hit)
        when the generic host path runs."""
        groups = m.agg_op.groups
        if not groups:
            return _KeyPlan(device_expr=None, num_groups=1, key_columns=[])
        if len(groups) == 1:
            g = groups[0]
            e = m.col_exprs[g]
            try:
                t = expr_data_type(e, m.source_relation, registry)
            except (KeyError, ValueError):
                return None
            if t == DataType.STRING and isinstance(e, ColumnRef):
                d = table.dictionaries.get(e.name)
                if d is not None:
                    base_cols.add(e.name)
                    return _KeyPlan(
                        device_expr=e,
                        num_groups=len(d),
                        key_columns=[DictColumn(np.arange(len(d), dtype=np.int32), d)],
                    )
            if t == DataType.STRING:
                lut = self._dict_lut_key(e, table, registry, func_ctx)
                if lut is not None:
                    lut_codes, out_dict, src_col = lut
                    base_cols.add(src_col)
                    return _KeyPlan(
                        device_expr=("lut", src_col, lut_codes),
                        num_groups=len(out_dict),
                        key_columns=[
                            DictColumn(
                                np.arange(len(out_dict), dtype=np.int32),
                                out_dict,
                            )
                        ],
                    )
        # Generic host path: evaluate key exprs over the full columns once,
        # then densify (ref: the reference hashes RowTuples per batch; we
        # pay one vectorized pass, cached per table version + key exprs —
        # except when keys depend on mutable metadata state).
        kp_cacheable = not any(
            _uses_ctx_func(m.col_exprs[g], m.source_relation, registry)
            for g in groups
        )
        kp_key = (
            m.source_op.table_name,
            (table.min_row_id(), table.end_row_id()),
            repr([m.col_exprs[g] for g in groups]),
            m.source_op.start_time,
            m.source_op.stop_time,
        )
        cached = self._keyplan_cache.get(kp_key) if kp_cacheable else None
        if cached is not None:
            self._keyplan_cache.move_to_end(kp_key)
            if sp is not None:
                sp.set(batches=0, visits=0, evals=0, cached=True)
            return cached
        key_refs = set()
        for g in groups:
            key_refs |= referenced_columns(m.col_exprs[g])
        sub_names = [
            c for c in m.source_relation.col_names() if c in key_refs
        ]
        sub_rel = m.source_relation.select(sub_names)
        ev = ExpressionEvaluator(
            [(g, m.col_exprs[g]) for g in groups], sub_rel,
            registry, func_ctx,
        )
        out_rel = MapOp(
            tuple((g, m.col_exprs[g]) for g in groups)
        ).output_relation([sub_rel], registry)
        # Chunked first-touch pass: the cursor's batches gather into chunks
        # of exactly DEFAULT_COMPACTED_ROWS rows (the table's compacted
        # batch size), and each chunk's keys are evaluated and densified
        # in one pass. A compacted table's batches are already chunks; a
        # run of small pushes pays one evaluation per chunk, not one per
        # push. Host memory stays bounded by a chunk of key columns — at
        # gigarow scale the monolithic evaluation was the cold-path's
        # host-memory spike, and per-chunk np.unique is cheaper than one
        # giant one (VERDICT r3 weakness 7). The last chunk is padded by
        # repeating its last row, so an eager device UDF (px.bin) sees one
        # shape whatever the span's row count. GroupEncoder assigns stable
        # gids incrementally across chunks by construction.
        chunk_rows = DEFAULT_COMPACTED_ROWS
        enc = GroupEncoder()
        gid_parts: list[np.ndarray] = []
        # Bare string columns keep the table's write-side dictionary, so
        # their codes are chunk-stable. COMPUTED string keys get a fresh
        # dictionary per evaluated chunk — re-encode those through one
        # stable dictionary or chunk codes would be incomparable.
        stable_dicts: dict[str, StringDictionary] = {}
        out_dicts: dict[str, StringDictionary] = {}

        def densify(chunk: RowBatch, keep: int) -> None:
            """Evaluate the keys of one chunk and encode its first
            ``keep`` rows (the rest is padding)."""
            key_batch = ev.evaluate(chunk, out_rel)
            key_cols = []
            for g, col in zip(groups, key_batch.columns):
                if isinstance(col, DictColumn):
                    col = col.slice(0, keep)
                    if isinstance(m.col_exprs[g], ColumnRef):
                        out_dicts[g] = col.dictionary
                    else:
                        d = stable_dicts.setdefault(g, StringDictionary())
                        col = DictColumn(d.encode(col.decode()), d)
                        out_dicts[g] = d
                else:
                    col = col[:keep]
                key_cols.append(col)
            gid_parts.append(enc.encode(key_cols))

        batches = 0
        pending: list[RowBatch] = []
        pending_rows = 0
        cur = table.cursor(m.source_op.start_time, m.source_op.stop_time)
        while not cur.done():
            b = cur.next_batch()
            if b is None:
                break
            if not b.num_rows:
                continue
            batches += 1
            pending.append(b.select(sub_names))
            pending_rows += b.num_rows
            while pending_rows >= chunk_rows:
                buf = pending[0] if len(pending) == 1 else RowBatch.concat(pending)
                densify(buf.slice(0, chunk_rows), chunk_rows)
                rest = buf.slice(chunk_rows, pending_rows)
                pending = [rest] if rest.num_rows else []
                pending_rows = rest.num_rows
        if pending_rows:
            pad = np.minimum(np.arange(chunk_rows), pending_rows - 1)
            densify(RowBatch.concat(pending).take(pad), pending_rows)
        count_read_batches(batches)
        count_read_visits(cur.segments_visited)
        count_key_evals(len(gid_parts))
        if sp is not None:
            sp.set(
                batches=batches, visits=cur.segments_visited,
                evals=len(gid_parts), cached=False,
            )
        gids = (
            np.concatenate(gid_parts) if gid_parts else np.empty(0, np.int32)
        )
        key_arrays = enc.key_arrays()
        key_columns = []
        for g, arr in zip(groups, key_arrays):
            if g in out_dicts:
                key_columns.append(
                    DictColumn(arr.astype(np.int32), out_dicts[g])
                )
            else:
                key_columns.append(arr)
        kp = _KeyPlan(
            host_gids=gids, num_groups=enc.num_groups, key_columns=key_columns
        )
        if kp_cacheable:
            version = (table.min_row_id(), table.end_row_id())
            for k in [
                k for k in self._keyplan_cache
                if k[0] == m.source_op.table_name and k[1] != version
            ]:
                del self._keyplan_cache[k]
            self._keyplan_cache[kp_key] = kp
            while len(self._keyplan_cache) > self._keyplan_cache_cap:
                self._keyplan_cache.popitem(last=False)
        return kp

    def _dict_lut_key(self, e, table, registry, func_ctx=None):
        """String key computed by a dict_compatible host func over one string
        column (the ctx['service'] shape): build per-dictionary-value codes."""
        if not isinstance(e, FuncCall):
            return None
        str_cols = [a for a in e.args if isinstance(a, ColumnRef)]
        if len(str_cols) != 1 or not all(
            isinstance(a, (ColumnRef, Constant)) for a in e.args
        ):
            return None
        src = str_cols[0].name
        d = table.dictionaries.get(src)
        if d is None:
            return None
        arg_types = []
        for a in e.args:
            if isinstance(a, ColumnRef):
                arg_types.append(DataType.STRING)
            else:
                arg_types.append(a.data_type)
        udf = registry.lookup_scalar(e.name, arg_types)
        if udf is None or udf.executor != Executor.HOST or not udf.dict_compatible:
            return None
        values = np.asarray(d.values(), dtype=object)
        fn_args = [
            values if isinstance(a, ColumnRef) else a.value for a in e.args
        ] + list(e.init_args)
        if udf.needs_ctx:
            fn_args = [func_ctx] + fn_args
        per_value = np.asarray(udf.fn(*fn_args), dtype=object)
        out_dict = StringDictionary()
        lut_codes = out_dict.encode(per_value)
        return lut_codes.astype(np.int32), out_dict, src

    def _build_aux(self, evaluator, m, key_plan, table, specs) -> dict:
        # key: exprs are materialized by the key plan (codes / LUT / host
        # gids), never via device_eval aux — only predicates and agg args
        # need LUT/constant-code precomputation.
        aux: dict[str, np.ndarray] = {}
        # Hash-mode string args (sketch UDAs): ship a per-dictionary-value
        # content-hash LUT so the device sees the same dictionary-independent
        # identity the host AggNode does (agg_node._arg_array).
        for out, arg_e, uda in specs:
            if (
                uda.reads_args
                and uda.string_args == "hash"
                and isinstance(arg_e, ColumnRef)
            ):
                d = table.dictionaries.get(arg_e.name)
                if d is not None:
                    aux[f"arghash:{arg_e.name}"] = (
                        d.content_hashes().view(np.int64)
                    )
        for name, e in evaluator.named_exprs:
            if name.startswith("key:"):
                continue
            aux.update(evaluator.build_aux(e, table.dictionaries))
        return aux

    # -- the program --------------------------------------------------------
    def _finalize_modes(self, specs, capacity, force_state: bool = False):
        """Per-spec device-finalization mode + packed-output leaf templates.

        Modes: 'devfin' (UDA supplies a traceable device_finalize — the
        numeric reduction fuses into the program, host only formats),
        'fin' (finalize itself traces — fuse it), 'state' (pack raw state,
        finalize on host). Templates are (treedef, [(shape, dtype)..]) of
        whatever the program will pack for that spec, so the single fetched
        buffer can be split back without guessing."""
        cache_key = (
            tuple((uda.name, uda.arg_types) for _, _, uda in specs),
            capacity,
            force_state,
        )
        cached = self._finmode_cache.get(cache_key)
        if cached is not None:
            return cached
        modes = []
        templates = []
        for _, _, uda in specs:
            state_aval = jax.eval_shape(lambda u=uda: u.init(capacity))
            if force_state:  # PARTIAL stage: raw states cross the bridge
                mode = "state"
                out_aval = state_aval
            elif uda.device_finalize is not None:
                mode = "devfin"
                out_aval = jax.eval_shape(uda.device_finalize, state_aval)
            else:
                try:
                    out_aval = jax.eval_shape(uda.finalize, state_aval)
                    mode = "fin"
                except Exception:
                    mode = "state"
                    out_aval = state_aval
            leaves, treedef = jax.tree.flatten(out_aval)
            modes.append(mode)
            templates.append(
                (treedef, [(tuple(l.shape), l.dtype) for l in leaves])
            )
        self._finmode_cache[cache_key] = (modes, templates)
        return modes, templates

    def _pass_plan(self, specs, num_groups: int) -> tuple[int, int]:
        """(per-pass capacity, n_passes): bound state memory for
        high-cardinality group-bys. Sketch UDAs cost KBs per group slot, so
        1e6 distinct keys would OOM a single-pass program; instead the SAME
        compiled program runs n_passes times over the staged (resident)
        blocks, each pass masking to a contiguous gid range via a gid_base
        argument, and the host concatenates the per-pass outputs (the
        spill/recombine strategy for SURVEY 'Hard parts' #1)."""
        per_group = 8  # presence counter
        for _, _, uda in specs:
            st = jax.eval_shape(lambda u=uda: u.init(1))
            per_group += sum(
                int(np.prod(l.shape)) * l.dtype.itemsize
                for l in jax.tree.leaves(st)
            )
        budget = flags.device_group_state_budget_mb * (1 << 20)
        cap_full = _pow2_at_least(max(num_groups, 1))
        fit = max(budget // per_group, 1)
        max_cap = max(1 << (fit.bit_length() - 1), 8)  # largest pow2 <= fit
        capacity = min(cap_full, max_cap)
        n_passes = (max(num_groups, 1) + capacity - 1) // capacity
        return capacity, n_passes

    def _signature(self, m, specs, key_plan, staged, aux_vals, capacity) -> str:
        """Structural identity of the compiled program: expressions, UDA
        set, key mode, block geometry, capacity, aux shapes."""
        from pixie_tpu.ops import segment as _segment

        modes, _ = self._finalize_modes(
            specs, capacity, m.agg_op.stage == AggStage.PARTIAL
        )
        with _segment.platform_hint(self.mesh.devices.flat[0].platform):
            sortlane = int(_segment.sorted_strategy(staged.mask.shape[-1]))
        parts = [
            f"sortlane:{sortlane}",
            "finmodes:" + ",".join(modes),
            f"stage:{m.agg_op.stage.value}",
            ",".join(f"{n}:{a.shape}:{a.dtype}" for n, a in
                     sorted(staged.blocks.items())),
            f"mask:{staged.mask.shape}",
            f"cap:{capacity}",
            f"narrow:{sorted(staged.narrow_offsets)}",
            f"intdict:{sorted(staged.int_dicts)}",
            f"hostgids:{key_plan.host_gids is not None}",
            "preds:" + ";".join(repr(p) for p in m.predicates),
            "aggs:" + ";".join(
                f"{out}={uda.name}({arg_e!r})" for out, arg_e, uda in specs
            ),
            "key:" + (
                "host" if key_plan.host_gids is not None else (
                    f"lut:{key_plan.device_expr[1]}"
                    if isinstance(key_plan.device_expr, tuple)
                    else repr(key_plan.device_expr)
                )
            ),
            "aux:" + ",".join(
                f"{np.shape(v)}:{np.asarray(v).dtype}" for v in aux_vals
            ),
            f"mesh:{self._mesh_sig}",
        ]
        return "|".join(parts)

    # -- per-lane program decomposition (r7) ---------------------------------
    # The monolithic jit(shard_map(scan+merge+finalize)) recompiled as a
    # whole whenever ANY part of the query changed. Decomposed units are
    # cached under their own signatures: the expensive fold executable is
    # keyed by the scan lane alone (no output names, no finalize modes),
    # so a query that differs only in finalize reuses it and compiles
    # only the small finalize unit; init/merge key on the UDA lane set
    # and are shared across staging geometries entirely.

    def _lane_sig(self, specs) -> str:
        """UDA lane identity WITHOUT output names: two queries whose agg
        lanes differ only in what the outputs are called (or how they
        finalize) share fold/init/merge executables. UDAs that never read
        their column (count) also drop the arg expression and overload
        types — the fold never touches the column, so count('time_') and
        count('latency') are the same lane (this is also what lets
        table-create prewarm guess the count lane without knowing which
        column a future query will point it at)."""
        return ";".join(
            f"{uda.name}{uda.arg_types}({arg_e!r})"
            if uda.reads_args
            else f"{uda.name}()"
            for _out, arg_e, uda in specs
        )

    def _uda_set_sig(self, specs) -> str:
        """Coarser still: the UDA set alone (state shapes + merge kinds
        derive from it) — keys the init and merge units."""
        return ",".join(
            f"{uda.name}{uda.arg_types if uda.reads_args else '()'}"
            for _o, _e, uda in specs
        )

    def _fold_signature(
        self, m, specs, key_plan, staged, aux_vals, capacity,
        preds_repr=None,
    ) -> str:
        """Identity of the FOLD unit alone: scan expressions, UDA update
        lanes, key mode, block geometry, capacity, aux shapes — finalize
        modes, agg stage, and output names are excluded (they key the
        finalize unit). Staging geometry is bucketed (staging
        .block_geometry), so two tables whose padded shapes land in the
        same bucket produce the same string — and share one compiled
        executable in-process plus one .jax_cache entry across runs.

        The sort–compact lane decision (r8) is part of the identity: it
        is made at trace time from the per-block row count, so a flag /
        forced-strategy flip must not reuse a fold traced for the other
        lane.

        ``preds_repr`` (r16) overrides the predicate component: the
        predicate-BATCHED fold erases per-query predicates from its
        identity (they enter as data — per-slot term tables — not as
        traced expressions), so every predicate-compatible query shape
        shares one batched executable per batch-width bucket."""
        from pixie_tpu.ops import segment as _segment

        with _segment.platform_hint(self.mesh.devices.flat[0].platform):
            sortlane = int(_segment.sorted_strategy(staged.mask.shape[-1]))
        parts = [
            f"sortlane:{sortlane}",
            ",".join(f"{n}:{a.shape}:{a.dtype}" for n, a in
                     sorted(staged.blocks.items())),
            f"mask:{staged.mask.shape}",
            f"cap:{capacity}",
            f"narrow:{sorted(staged.narrow_offsets)}",
            f"intdict:{sorted(staged.int_dicts)}",
            f"hostgids:{key_plan.host_gids is not None}",
            "preds:" + (
                preds_repr
                if preds_repr is not None
                else ";".join(repr(p) for p in m.predicates)
            ),
            "lanes:" + self._lane_sig(specs),
            "key:" + (
                "host" if key_plan.host_gids is not None else (
                    f"lut:{key_plan.device_expr[1]}"
                    if isinstance(key_plan.device_expr, tuple)
                    else repr(key_plan.device_expr)
                )
            ),
            "aux:" + ",".join(
                f"{np.shape(v)}:{np.asarray(v).dtype}" for v in aux_vals
            ),
            f"mesh:{self._mesh_sig}",
        ]
        return "|".join(parts)

    def _get_program(self, sig: str, build, n_aux: int = 0):
        """Program-cache lookup-or-build shared by every unit."""
        # Geometry guard: every cached executable was traced against ONE
        # mesh geometry, and every signature must carry that geometry.
        # A lookup whose signature names a different geometry than the
        # executor's mesh means a caller mixed executors/meshes — fail
        # loudly instead of silently reusing a stale compiled program.
        # A mismatch means a caller mixed executors/meshes — a
        # structured MeshGeometryError (r23) that routes through the
        # breaker/fallback ladder to the host engine instead of
        # crashing the query path (it is NOT recoverable by degrading:
        # the geometry itself is fine, the caller's signature is not).
        if f"mesh:{self._mesh_sig}" not in sig:
            raise mesh_lib.MeshGeometryError(
                "signature_mismatch",
                f"program signature {sig!r} does not carry this "
                f"executor's mesh geometry {self._mesh_sig!r}",
            )
        entry = self._program_cache.get(sig)
        if entry is None or entry[1] != n_aux:
            self._program_cache[sig] = (build(), n_aux, None)
            _PROGRAMS.set(len(self._program_cache))
            if resattr.ACTIVE:
                # r15: every distinct program unit enters the
                # device_programs registry at build time; the AOT worker
                # enriches it with XLA cost analysis once a Compiled
                # exists.
                resattr.record_program(sig)
        return self._program_cache[sig][0]

    def _unit_programs(
        self, m, specs, evaluator, key_plan, staged, aux_key_order,
        aux_vals, capacity,
    ):
        """(init_p, fold_p, merge_p, fin_p, fold_sig) for a staging
        geometry — each unit cached under its own signature."""
        treedef, leaves = self._state_template(specs, capacity)
        n_leaves = len(leaves)
        lanes = self._uda_set_sig(specs)
        mesh_s = self._mesh_sig
        col_names = sorted(staged.blocks)
        narrow_names = sorted(staged.narrow_offsets)
        int_dict_names = sorted(staged.int_dicts)
        fold_sig = "fold|" + self._fold_signature(
            m, specs, key_plan, staged, aux_vals, capacity
        )
        init_p = self._get_program(
            f"init|{lanes}|cap:{capacity}|mesh:{mesh_s}",
            lambda: self._build_init(specs, capacity),
        )
        fold_p = self._get_program(
            fold_sig,
            lambda: self._build_fold(
                specs, evaluator, key_plan, col_names, narrow_names,
                int_dict_names, aux_key_order, capacity, n_leaves, treedef,
                self._program_lanes.setdefault(fold_sig, set()),
            ),
            n_aux=len(aux_vals),
        )
        merge_p = self._get_program(
            f"merge|{lanes}|cap:{capacity}|mesh:{mesh_s}",
            lambda: self._build_merge(specs, capacity, n_leaves, treedef),
        )
        force_state = m.agg_op.stage == AggStage.PARTIAL
        fin_p = self._get_program(
            f"fin|{lanes}|cap:{capacity}|state:{force_state}|mesh:{mesh_s}",
            lambda: self._build_fin(specs, capacity, force_state, treedef),
        )
        return init_p, fold_p, merge_p, fin_p, fold_sig

    # -- background AOT compilation (r7) -------------------------------------
    def _aot_lower_compile(self, program, avals):
        """jit -> lowered -> compiled, separated so tests can poison it."""
        return program.lower(*avals).compile()

    def _aot_compile_async(
        self, sig: str, program, avals, profile_key: str = "stage_compile"
    ):
        """Future resolving to the AOT-compiled executable of ``program``
        at ``avals``. The lower+compile runs on a background thread so the
        cold XLA compile overlaps host pack and HBM transfer instead of
        preceding them; results cache in _aot_compiled per signature, and
        in-flight compiles dedup through _aot_futures (a query arriving
        while its prewarmed fold is still compiling attaches to the
        running future instead of compiling twice). COLD_PROFILE gains
        ``profile_key`` seconds (stage_compile for the stream fold,
        warm_compile for the warm/monolithic fold, prewarm_compile at
        table create), compile_cache_hit (persistent .jax_cache
        deserializations observed during the compile), and prewarm_hit
        (query folds served by a table-create prewarm, completed or
        still in flight)."""
        import concurrent.futures

        def record_prewarm_hit():
            if sig in self._prewarmed and profile_key == "stage_compile":
                COLD_PROFILE["prewarm_hit"] = COLD_PROFILE.get(
                    "prewarm_hit", 0.0
                ) + 1.0

        done = self._aot_compiled.get(sig)
        if done is not None:
            record_prewarm_hit()
            fut = concurrent.futures.Future()
            fut.set_result(done)
            return fut
        inflight = self._aot_futures.get(sig)
        if inflight is not None and not (
            inflight.done() and inflight.exception() is not None
        ):
            record_prewarm_hit()
            return inflight
        if self._aot_pool is None:
            self._aot_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="aot-compile"
            )

        def work():
            from pixie_tpu.ops import segment as _segment

            hits0 = _PERSISTENT_CACHE_HITS[0]
            t0 = time.perf_counter()
            # Pin the kernel-strategy hint to the MESH platform: this
            # worker thread has no caller TLS hint, and
            # jax.default_backend() can disagree with the mesh (CPU exec
            # graph on a TPU-attached host) — the trace must pick the
            # same lanes the fold signature assumed.
            with _segment.platform_hint(
                self.mesh.devices.flat[0].platform
            ):
                compiled = self._aot_lower_compile(program, avals)
            compile_s = time.perf_counter() - t0
            COLD_PROFILE[profile_key] = COLD_PROFILE.get(
                profile_key, 0.0
            ) + compile_s
            if _PERSISTENT_CACHE_HITS[0] > hits0:
                COLD_PROFILE["compile_cache_hit"] = COLD_PROFILE.get(
                    "compile_cache_hit", 0.0
                ) + 1.0
            if resattr.ACTIVE:
                # r15: the Compiled carries XLA cost analysis — flops +
                # bytes accessed land in the device_programs registry
                # alongside the measured compile seconds.
                resattr.record_program(
                    sig, compile_s=compile_s, compiled=compiled
                )
            self._aot_compiled[sig] = compiled
            return compiled

        # Workers adopt the submitting query's trace context and
        # resource attribution (r15): compile CPU burned for a query
        # samples under that query's label.
        fut = self._aot_pool.submit(trace.attributed(work, phase="compile"))
        self._aot_futures[sig] = fut
        gauge = _AOT_PENDING
        gauge.inc()
        fut.add_done_callback(lambda _f: gauge.dec())
        return fut

    def _aot_warm_fold(
        self, m, specs, evaluator, key_plan, staged, aux, capacity
    ):
        """Background-AOT the WARM/monolithic fold (r8, second ROADMAP
        cold-path lever): the streamed windows concatenate into the
        staged-cache entry at a DIFFERENT geometry than the stream
        window, so the first warm query used to compile its fold inline.
        Called at the end of a cold stream, this lowers+compiles that
        warm-geometry fold on the AOT worker while the cold query
        finishes — breakdown key ``warm_compile``; a compile or dispatch
        failure falls back to the in-line jit like the stream fold does.
        Returns the warm fold signature (None when already compiled or
        in flight)."""
        aux_vals = list(aux.values())
        aux_key_order = list(aux.keys())
        init_p, fold_p, _merge_p, _fin_p, fold_sig = self._unit_programs(
            m, specs, evaluator, key_plan, staged, aux_key_order,
            aux_vals, capacity,
        )
        if fold_sig in self._aot_compiled or fold_sig in self._aot_futures:
            return None  # single-window stream: warm sig == stream sig
        axis_name = self.mesh_axes  # full axis tuple: dim0 over every mesh axis
        sharded = NamedSharding(self.mesh, P(axis_name))
        repl = NamedSharding(self.mesh, P())
        _treedef, leaves = self._state_template(specs, capacity)
        d = staged.num_devices
        avals = [
            jax.ShapeDtypeStruct(
                (d,) + tuple(l.shape), l.dtype, sharding=sharded
            )
            for l in leaves
        ]
        for n2 in sorted(staged.blocks):
            a = staged.blocks[n2]
            avals.append(
                jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
            )
        avals.append(
            jax.ShapeDtypeStruct(
                staged.mask.shape, staged.mask.dtype,
                sharding=staged.mask.sharding,
            )
        )
        if key_plan.host_gids is not None:
            g = staged.gids
            avals.append(
                jax.ShapeDtypeStruct(g.shape, g.dtype, sharding=g.sharding)
            )
        if isinstance(key_plan.device_expr, tuple):
            lut = np.asarray(key_plan.device_expr[2])
            avals.append(
                jax.ShapeDtypeStruct(lut.shape, lut.dtype, sharding=repl)
            )
        for v in aux_vals:
            v = np.asarray(v)
            avals.append(
                jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=repl)
            )
        if staged.narrow_offsets:
            avals.append(
                jax.ShapeDtypeStruct(
                    (len(staged.narrow_offsets),),
                    np.dtype(np.int64),
                    sharding=repl,
                )
            )
        avals.append(
            jax.ShapeDtypeStruct((), np.dtype(np.int32), sharding=repl)
        )
        self._aot_compile_async(
            fold_sig, fold_p, tuple(avals), profile_key="warm_compile"
        )
        return fold_sig

    # -- table-create compile prewarming (r8) --------------------------------
    def prewarm_table(self, table, registry):
        """Speculatively compile, at table-CREATE time, the fold a
        canonical stats query over this table would need (ROADMAP
        cold-path lever; flag ``prewarm_compile``, default off).

        The canonical shape is groupby(first string column).agg(count,
        sum of every FLOAT64 column) at the standard streamed-window
        bucket geometry — the geometry every cold stream window uses
        once the table exceeds one window, independent of the eventual
        row count. The fold signature is produced by the SAME
        _unit_programs path a real query takes, so a matching first
        query finds its executable in _aot_compiled (or attaches to the
        in-flight compile) and records the ``prewarm_hit`` breakdown
        key; a non-matching query just misses — prewarm is opportunistic
        and never affects correctness. Compile time lands in the
        ``prewarm_compile`` breakdown key at create time, off every
        query's critical path. Returns the prewarmed fold signature, or
        None when gated off / the table has no canonical shape."""
        if not flags.prewarm_compile:
            return None
        try:
            return self._prewarm_table_inner(table, registry)
        except Exception as e:
            import traceback

            key = f"{type(e).__name__}: {e}"
            if key not in self.prewarm_errors:
                self.prewarm_errors[key] = traceback.format_exc()
                import logging

                logging.getLogger("pixie_tpu.parallel").warning(
                    "table-create compile prewarm failed (ignored): %s", key
                )
            return None

    def _prewarm_table_inner(self, table, registry):
        import types as _types

        from pixie_tpu.parallel import staging as _staging

        # r12: when a fold-signature store is wired and holds shapes this
        # table's real queries recorded (serving/signatures.py), replay
        # THEM — bit-identical fold signatures through the same
        # _unit_programs path — instead of guessing the canonical shape.
        # The canonical guess remains the cold-start fallback.
        if self.fold_signature_store is not None:
            sigs = [
                sig
                for sig in (
                    self._prewarm_recorded_shape(table, registry, shape)
                    for shape in self.fold_signature_store.shapes(
                        table.name or ""
                    )
                )
                if sig is not None
            ]
            if sigs:
                return sigs[-1]
        rel = table.relation
        str_cols = [c.name for c in rel if c.data_type == DataType.STRING]
        f64_cols = [c.name for c in rel if c.data_type == DataType.FLOAT64]
        if not str_cols or not f64_cols:
            return None
        key_col = str_cols[0]
        count_uda = registry.lookup_uda("count", [DataType.STRING])
        sum_uda = registry.lookup_uda("sum", [DataType.FLOAT64])
        if count_uda is None or sum_uda is None:
            return None
        # Spec order mirrors the conventional agg listing: count first,
        # then per-column sums. count's arg never enters the fold
        # signature (reads_args=False lanes drop it), so any future
        # count column matches.
        specs = [("pw_n", ColumnRef(key_col), count_uda)]
        for cname in f64_cols:
            specs.append((f"pw_sum_{cname}", ColumnRef(cname), sum_uda))
        named = [
            (f"arg:{out}:0", e) for out, e, uda in specs if uda.reads_args
        ]
        named.append((f"key:{key_col}", ColumnRef(key_col)))
        evaluator = ExpressionEvaluator(named, rel, registry, None)
        # Dictionary-code device key (the string group-by fast path); the
        # capacity floor (8) covers every group-by of <= 8 groups.
        key_plan = _KeyPlan(device_expr=ColumnRef(key_col), num_groups=1)
        capacity, _n_passes = self._pass_plan(specs, 1)
        d = self.mesh.devices.size
        window_rows = max(int(flags.streaming_window_rows), 1)
        b, nblk = _staging.block_geometry(window_rows, d, self.block_rows)
        blocks = {
            # String keys stage as frame-of-reference-narrowed uint8
            # codes while the dictionary stays small (< 256 values).
            key_col: _types.SimpleNamespace(
                shape=(d, nblk, b), dtype=np.dtype(np.uint8)
            )
        }
        for cname in f64_cols:
            blocks[cname] = _types.SimpleNamespace(
                shape=(d, nblk, b), dtype=np.dtype(np.float64)
            )
        shim = _types.SimpleNamespace(
            blocks=blocks,
            mask=_types.SimpleNamespace(shape=(d, nblk, b)),
            narrow_offsets={key_col: 0},
            int_dicts={},
        )
        m_shim = _types.SimpleNamespace(
            predicates=[],
            agg_op=_types.SimpleNamespace(stage=AggStage.FULL),
        )
        _treedef, leaves = self._state_template(specs, capacity)
        _init_p, fold_p, _merge_p, _fin_p, fold_sig = self._unit_programs(
            m_shim, specs, evaluator, key_plan, shim, [], [], capacity
        )
        if fold_sig in self._aot_compiled or fold_sig in self._aot_futures:
            self._prewarmed.add(fold_sig)
            return fold_sig
        axis_name = self.mesh_axes  # full axis tuple: dim0 over every mesh axis
        sharded = NamedSharding(self.mesh, P(axis_name))
        repl = NamedSharding(self.mesh, P())
        avals = [
            jax.ShapeDtypeStruct(
                (d,) + tuple(l.shape), l.dtype, sharding=sharded
            )
            for l in leaves
        ]
        avals += [
            jax.ShapeDtypeStruct(
                (d, nblk, b), blocks[n2].dtype, sharding=sharded
            )
            for n2 in sorted(blocks)
        ]
        avals.append(
            jax.ShapeDtypeStruct(
                (d, nblk, b), np.dtype(np.bool_), sharding=sharded
            )
        )
        # No host gids (device dictionary key), no key LUT, no aux; one
        # narrow offset (the key codes) + the gid_base scalar.
        avals.append(
            jax.ShapeDtypeStruct((1,), np.dtype(np.int64), sharding=repl)
        )
        avals.append(
            jax.ShapeDtypeStruct((), np.dtype(np.int32), sharding=repl)
        )
        self._prewarmed.add(fold_sig)
        self._aot_compile_async(
            fold_sig, fold_p, tuple(avals), profile_key="prewarm_compile"
        )
        return fold_sig

    def _prewarm_recorded_shape(self, table, registry, shape: dict):
        """Replay ONE recorded fold shape (serving/signatures.py) through
        the same _unit_programs path a real query takes: recorded key
        column + agg lanes + capacity + EXACT staged block dtypes and
        geometry reproduce the original fold signature bit-for-bit, so
        the restarted process AOT-compiles (or .jax_cache-deserializes)
        precisely the executables its workload will ask for. Returns the
        fold signature, or None when the shape no longer applies (schema
        drift, mesh resize, missing UDA)."""
        import types as _types

        try:
            d, nblk, b = (int(x) for x in shape["geometry"])
            if d != self.mesh.devices.size:
                return None
            key_col = shape["key_col"]
            rel = table.relation
            specs = []
            for i, (uname, col, argts) in enumerate(shape["lanes"]):
                if col is None:
                    # reads_args=False lane (count): the arg never enters
                    # the fold signature; any resolvable overload works.
                    uda = registry.lookup_uda(uname, [DataType.STRING])
                    if uda is None:
                        return None
                    specs.append((f"pw{i}", ColumnRef(key_col), uda))
                    continue
                uda = registry.lookup_uda(
                    uname, [DataType[t] for t in argts]
                )
                if uda is None:
                    return None
                specs.append((f"pw{i}", ColumnRef(col), uda))
            named = [
                (f"arg:{out}:0", e)
                for out, e, uda in specs
                if uda.reads_args
            ]
            named.append((f"key:{key_col}", ColumnRef(key_col)))
            evaluator = ExpressionEvaluator(named, rel, registry, None)
            key_plan = _KeyPlan(
                device_expr=ColumnRef(key_col), num_groups=1
            )
            capacity = int(shape["capacity"])
            blocks = {
                name: _types.SimpleNamespace(
                    shape=(d, nblk, b), dtype=np.dtype(dt)
                )
                for name, dt in shape["blocks"].items()
            }
            narrow = list(shape.get("narrow") or ())
            shim = _types.SimpleNamespace(
                blocks=blocks,
                mask=_types.SimpleNamespace(shape=(d, nblk, b)),
                narrow_offsets={n2: 0 for n2 in narrow},
                int_dicts={},
            )
            m_shim = _types.SimpleNamespace(
                predicates=[],
                agg_op=_types.SimpleNamespace(stage=AggStage.FULL),
            )
            _treedef, leaves = self._state_template(specs, capacity)
            _i, fold_p, _mg, _f, fold_sig = self._unit_programs(
                m_shim, specs, evaluator, key_plan, shim, [], [], capacity
            )
            self._prewarmed.add(fold_sig)
            if fold_sig in self._aot_compiled or (
                fold_sig in self._aot_futures
            ):
                return fold_sig
            axis_name = self.mesh_axes  # full axis tuple: dim0 over every mesh axis
            sharded = NamedSharding(self.mesh, P(axis_name))
            repl = NamedSharding(self.mesh, P())
            avals = [
                jax.ShapeDtypeStruct(
                    (d,) + tuple(l.shape), l.dtype, sharding=sharded
                )
                for l in leaves
            ]
            avals += [
                jax.ShapeDtypeStruct(
                    (d, nblk, b), blocks[n2].dtype, sharding=sharded
                )
                for n2 in sorted(blocks)
            ]
            avals.append(
                jax.ShapeDtypeStruct(
                    (d, nblk, b), np.dtype(np.bool_), sharding=sharded
                )
            )
            if narrow:
                avals.append(
                    jax.ShapeDtypeStruct(
                        (len(narrow),), np.dtype(np.int64), sharding=repl
                    )
                )
            avals.append(
                jax.ShapeDtypeStruct((), np.dtype(np.int32), sharding=repl)
            )
            self._aot_compile_async(
                fold_sig, fold_p, tuple(avals),
                profile_key="prewarm_compile",
            )
            return fold_sig
        except Exception as e:
            import traceback

            key = f"replay {type(e).__name__}: {e}"
            if key not in self.prewarm_errors:
                self.prewarm_errors[key] = traceback.format_exc()
            return None

    def _make_scan_body(
        self,
        specs,
        evaluator,
        col_names,
        narrow_names,
        int_dict_names,
        preds,
        device_key,
        has_key_lut,
        capacity,
        aux,
        narrow_vec,
        key_lut,
        gid_base,
        use_host_gids,
        pred_batch=None,
    ):
        """The per-block scan body shared by the monolithic program, the
        streaming window-fold program, and (r16, ``pred_batch``) the
        predicate-BATCHED fold. carry = (states tuple, presence);
        xs = (cols tuple, mask, gids).

        With ``pred_batch = (int_cols, flt_cols, term_args)`` the body
        serves B queries at once: carry leaves gain a leading slot axis,
        per-query predicates are evaluated as DATA — a (B, T) table of
        (stack, column index, comparison op, threshold) conjunctive
        terms over two dtype-preserving column stacks (int64 for
        int/bool/code columns, float64 for float columns — both casts
        are exact, so each slot's mask is bit-equal to the serial
        predicate evaluation) — and the per-spec state updates vmap over
        the slot axis with env/gids shared. One scan of the staged
        blocks serves the whole batch."""

        def eval_gids(env, blk_mask):
            if device_key is None:
                # mask always exists; a count-only query may stage NO
                # value columns at all.
                return jnp.zeros_like(blk_mask, dtype=jnp.int32)
            if has_key_lut:
                _, src_col, _ = device_key
                return key_lut[jnp.maximum(env[src_col], 0)]
            return evaluator.device_eval(device_key, env, aux).astype(
                jnp.int32
            )

        def body(carry, xs):
            from pixie_tpu.ops import segment as _segment

            states, presence = carry
            blk_cols, blk_mask, blk_gids = xs
            env = dict(zip(col_names, blk_cols))
            for ni, nm in enumerate(narrow_names):
                # Widen frame-of-reference narrowed columns (VPU cast
                # + add; the transfer savings dwarf this).
                env[nm] = env[nm].astype(jnp.int64) + narrow_vec[ni]
            gids = (
                blk_gids if use_host_gids
                else eval_gids(env, blk_mask)
            )
            # This pass owns groups [gid_base, gid_base + capacity);
            # rows outside it are masked and their updates land on a
            # clipped (masked-out) slot.
            gids = gids.astype(jnp.int32) - gid_base
            gid_ok = (gids >= 0) & (gids < capacity)
            gids = jnp.clip(gids, 0, capacity - 1)

            def eval_col(arg_e, uda):
                col = evaluator.device_eval(arg_e, env, aux)
                hkey = (
                    f"arghash:{arg_e.name}"
                    if uda.string_args == "hash"
                    and isinstance(arg_e, ColumnRef)
                    else None
                )
                if hkey is not None and hkey in aux:
                    lut = aux[hkey]
                    col = lut[jnp.clip(col, 0, lut.shape[0] - 1)]
                return col

            def apply_updates(states, presence, mask):
                # Fused-sum lane: every sum-family UDA contributes f32
                # limb rows to ONE shared one-hot einsum (plus the
                # engine's presence row) — the one-hot generation
                # dominates MXU segment sums, so per-UDA calls pay it
                # k+1 times (r4).
                use_fused = _segment.matmul_strategy(capacity)
                fused_slices: dict[str, tuple[int, int]] = {}
                totals = None
                if use_fused:
                    rows = []
                    for out, arg_e, uda in specs:
                        if uda.fused_rows is None:
                            continue
                        if (
                            uda.cell_update is not None
                            and isinstance(arg_e, ColumnRef)
                            and arg_e.name in int_dict_names
                        ):
                            continue  # cell lane serves it
                        col = (
                            eval_col(arg_e, uda) if uda.reads_args
                            else None
                        )
                        r = uda.fused_rows(col, mask)
                        fused_slices[out] = (len(rows), len(rows) + len(r))
                        rows.extend(r)
                    rows.append(mask.astype(jnp.float32))  # presence
                    totals = _segment.limb_einsum_sums(rows, gids, capacity)
                    presence = presence + totals[-1].astype(presence.dtype)
                else:
                    presence = presence + _segment.seg_count(
                        gids, capacity, mask
                    ).astype(presence.dtype)
                # Cell lane: per-column (group, code) histograms via one
                # MXU einsum each; cell-capable UDAs over int-dictionary
                # columns update per CELL instead of per row (r5).
                hists: dict[str, Any] = {}
                for cname in int_dict_names:
                    lut = aux[f"intdict:{cname}"]
                    C = lut.shape[0]
                    if capacity * C > _segment.MATMUL_MAX_SEGMENTS:
                        # Cache reuse under a bigger pass capacity than
                        # the staging's max_card assumed: histogram would
                        # blow the einsum budget — row path (below) takes
                        # over via a LUT gather instead.
                        continue
                    flat = gids * C + env[cname].astype(jnp.int32)
                    h = _segment.limb_einsum_sums(
                        [mask.astype(jnp.float32)], flat, capacity * C
                    )
                    hists[cname] = h[0].astype(jnp.int64).reshape(
                        capacity, C
                    )
                new_states = []
                for (out, arg_e, uda), st in zip(specs, states):
                    if (
                        uda.cell_update is not None
                        and isinstance(arg_e, ColumnRef)
                        and arg_e.name in int_dict_names
                    ):
                        if arg_e.name in hists:
                            new_states.append(
                                uda.cell_update(
                                    st,
                                    hists[arg_e.name],
                                    aux[f"intdict:{arg_e.name}"],
                                )
                            )
                        else:
                            lut = aux[f"intdict:{arg_e.name}"]
                            vals = lut[env[arg_e.name].astype(jnp.int32)]
                            new_states.append(
                                uda.update(st, gids, vals, mask=mask)
                            )
                        continue
                    if out in fused_slices:
                        a, b = fused_slices[out]
                        new_states.append(uda.fused_apply(st, totals[a:b]))
                        continue
                    if not uda.reads_args:
                        # Column never read; gids is a shape-correct dummy.
                        new_states.append(
                            uda.update(st, gids, gids, mask=mask)
                        )
                        continue
                    new_states.append(
                        uda.update(st, gids, eval_col(arg_e, uda), mask=mask)
                    )
                return tuple(new_states), presence

            if pred_batch is None:
                mask = blk_mask
                for p in preds:
                    mask = mask & evaluator.device_eval(p, env, aux)
                mask = mask & gid_ok
                new_states, presence = apply_updates(
                    states, presence, mask
                )
                return (new_states, presence), None
            # Predicate-batched (r16): per-slot masks from the term
            # table, then the same update logic vmapped over slots.
            int_cols, flt_cols, term_args = pred_batch
            (
                t_stack, t_col_i, t_col_f, t_op,
                t_thr_i, t_thr_f, t_lut_i, t_lut_v,
                t_active, slot_on,
            ) = term_args
            base = blk_mask & gid_ok
            ivals = (
                jnp.stack(
                    [env[c].astype(jnp.int64) for c in int_cols]
                )
                if int_cols
                else jnp.zeros((1,) + blk_mask.shape, jnp.int64)
            )
            fvals = (
                jnp.stack(
                    [env[c].astype(jnp.float64) for c in flt_cols]
                )
                if flt_cols
                else jnp.zeros((1,) + blk_mask.shape, jnp.float64)
            )
            iv = ivals[t_col_i]  # (B, T, rows)
            fv = fvals[t_col_f]

            def cmp_select(op, v, t):
                # op ids: 0 ==, 1 !=, 2 <, 3 <=, 4 >, 5 >=
                return (
                    ((op == 0) & (v == t))
                    | ((op == 1) & (v != t))
                    | ((op == 2) & (v < t))
                    | ((op == 3) & (v <= t))
                    | ((op == 4) & (v > t))
                    | ((op == 5) & (v >= t))
                )

            opb = t_op[:, :, None]
            # r18: op 6 = IN-list membership over the int stack via the
            # per-term LUT lanes — any valid member equal to the row's
            # value. Codes compare in int64 like op 0/1 (an unseen
            # string const rides as -1 and matches no row code), so the
            # batched mask is bit-equal to the serial OR-of-equals.
            in_ok = jnp.any(
                (iv[:, :, None, :] == t_lut_i[:, :, :, None])
                & t_lut_v[:, :, :, None],
                axis=2,
            )
            ci = cmp_select(opb, iv, t_thr_i[:, :, None]) | (
                (opb == 6) & in_ok
            )
            cf = cmp_select(opb, fv, t_thr_f[:, :, None])
            term_ok = jnp.where(t_stack[:, :, None] == 0, ci, cf)
            term_ok = term_ok | ~t_active[:, :, None]
            slot_masks = (
                base[None, :]
                & jnp.all(term_ok, axis=1)
                & slot_on[:, None]
            )
            new_states, presence = jax.vmap(
                apply_updates, in_axes=(0, 0, 0)
            )(states, presence, slot_masks)
            return (new_states, presence), None

        return body

    def _merge_states(self, specs, states, presence, ndev, axis):
        """ICI merge — the collective half of the program tail. One
        collective per UDA (the Kelvin step); on a 1-device mesh every
        collective is the identity — skip them (some PJRT backends only
        lower Sum all-reduces anyway). Returns (merged states, presence),
        replicated across the mesh."""
        if ndev == 1:
            return list(states), presence
        presence = jax.lax.psum(presence, axis)
        merged = []
        for (out, _, uda), st in zip(specs, states):
            if uda.merge_kind == MergeKind.PSUM:
                merged.append(jax.tree.map(
                    lambda x: jax.lax.psum(x, axis), st
                ))
            elif uda.merge_kind == MergeKind.PMAX:
                merged.append(jax.tree.map(
                    lambda x: jax.lax.pmax(x, axis), st
                ))
            elif uda.merge_kind == MergeKind.PMIN:
                merged.append(jax.tree.map(
                    lambda x: jax.lax.pmin(x, axis), st
                ))
            else:  # TREE: all_gather states, fold pairwise
                gathered = jax.tree.map(
                    lambda x: jax.lax.all_gather(x, axis), st
                )
                acc = jax.tree.map(lambda x: x[0], gathered)
                for i2 in range(1, ndev):
                    acc = uda.merge(
                        acc, jax.tree.map(lambda x: x[i2], gathered)
                    )
                merged.append(acc)
        return merged, presence

    def _merge_pack_outputs(self, specs, fin_modes, states, presence, ndev, axis):
        """ICI merge + device finalize + single-buffer pack — the fused
        program tail (_merge_states then _finalize_pack in one trace)."""
        merged, presence = self._merge_states(
            specs, states, presence, ndev, axis
        )
        return self._finalize_pack(specs, fin_modes, merged, presence)

    def _finalize_pack(self, specs, fin_modes, merged, presence):
        # Finalize on device where the UDA allows it, then pack every
        # output/state leaf into ONE f64 buffer (ints ride exactly via
        # bitcast) so the host pays a single device fetch per query —
        # each fetch over a remote link costs ~100ms of round trip, and
        # fusing finalize also kills the state re-upload the host
        # quantile computation used to need.
        outs = []
        for mode, (_, _, uda), st in zip(fin_modes, specs, merged):
            if mode == "devfin":
                outs.append(uda.device_finalize(st))
            elif mode == "fin":
                outs.append(uda.finalize(st))
            else:
                outs.append(st)

        def pack(x):
            # int64 must survive exactly (hash codes use all 64 bits)
            # but TPU bitcast s64<->f64 is broken; split into hi/lo
            # 32-bit halves, each exactly representable in f64.
            if jnp.issubdtype(x.dtype, jnp.integer) or x.dtype == jnp.bool_:
                v = jnp.ravel(x).astype(jnp.int64)
                hi = jnp.floor_divide(v, 1 << 32)
                lo = v - hi * (1 << 32)
                return jnp.concatenate(
                    [hi.astype(jnp.float64), lo.astype(jnp.float64)]
                )
            return jnp.ravel(x).astype(jnp.float64)

        parts = [pack(x) for x in jax.tree.leaves(tuple(outs))]
        parts.append(pack(presence))
        return jnp.concatenate(parts)

    def _build_program(
        self, m, specs, evaluator, key_plan, staged, aux_key_order, capacity
    ):
        axis = self.mesh_axes  # collectives reduce over the FULL mesh
        fin_modes, _ = self._finalize_modes(
            specs, capacity, m.agg_op.stage == AggStage.PARTIAL
        )
        col_names = sorted(staged.blocks)
        narrow_names = sorted(staged.narrow_offsets)
        int_dict_names = sorted(staged.int_dicts)
        has_host_gids = key_plan.host_gids is not None
        has_key_lut = isinstance(key_plan.device_expr, tuple)
        device_key = key_plan.device_expr
        ndev = staged.num_devices
        preds = [
            e for n, e in evaluator.named_exprs if n.startswith("pred")
        ]

        def fold_merge_fn(*arrs):
            # Layout: cols..., mask, [gids], [key_lut], aux...,
            # [narrow_offsets], gid_base. Sharded args arrive as
            # [1, nblk, B]; the rest are replicated; gid_base selects this
            # pass's group window for high-cardinality multi-pass
            # execution; narrow_offsets widen frame-of-reference-encoded
            # int columns back to their logical int64 values per block.
            i = len(col_names)
            cols = {n: a[0] for n, a in zip(col_names, arrs[:i])}
            mask_all = arrs[i][0]
            i += 1
            gids_all = None
            if has_host_gids:
                gids_all = arrs[i][0]
                i += 1
            key_lut = None
            if has_key_lut:
                key_lut = arrs[i]
                i += 1
            gid_base = arrs[-1]
            end = -2 if narrow_names else -1
            narrow_vec = arrs[-2] if narrow_names else None
            aux = dict(zip(aux_key_order, arrs[i:end]))
            body = self._make_scan_body(
                specs, evaluator, col_names, narrow_names, int_dict_names,
                preds, device_key, has_key_lut, capacity, aux, narrow_vec,
                key_lut, gid_base, has_host_gids,
            )
            # Implicit presence counter: the host engine only emits observed
            # groups; without this, dictionary slots whose rows were all
            # filtered out (or expired) would surface as phantom zero rows.
            init_states = (
                tuple(uda.init(capacity) for _, _, uda in specs),
                jnp.zeros(capacity, jnp.int64),
            )
            xs = (
                tuple(cols[n] for n in col_names),
                mask_all,
                gids_all if gids_all is not None else mask_all,
            )
            (states, presence), _ = jax.lax.scan(body, init_states, xs)
            return self._merge_pack_outputs(
                specs, fin_modes, states, presence, ndev, axis
            )

        n_sharded = len(col_names) + 1 + (1 if has_host_gids else 0)
        n_repl = (
            (1 if has_key_lut else 0)
            + len(aux_key_order)
            + (1 if narrow_names else 0)
            + 1  # +gid_base
        )
        in_specs = tuple([P(axis)] * n_sharded + [P()] * n_repl)
        return jax.jit(
            jax.shard_map(
                fold_merge_fn,
                mesh=self.mesh,
                in_specs=in_specs,
                out_specs=P(),
                check_vma=False,
            )
        )

    # -- streamed double-buffered staging (r6) -------------------------------
    # The monolithic path stages the WHOLE table in HBM before the first
    # FLOP; the cold query is therefore pack + transfer + compute in
    # sequence (572s of 613s in stage_transfer for the r5 config-1 shape).
    # The streaming path splits the table into fixed row windows and runs a
    # three-stage software pipeline — window k+2 host-packs on a background
    # thread, window k+1 rides an async device_put, window k folds into the
    # carried UDA states on the mesh — so end-to-end time approaches
    # max(pack, transfer, compute) + one window of fill/drain. The fold
    # reuses the exact per-block scan body of the monolithic program; the
    # finish program applies the same collective-merge/finalize/pack tail.

    def _state_template(self, specs, capacity):
        """(treedef, leaf avals) of the fold carry (states tuple, presence)."""
        avals = jax.eval_shape(
            lambda: (
                tuple(uda.init(capacity) for _, _, uda in specs),
                jnp.zeros(capacity, jnp.int64),
            )
        )
        leaves, treedef = jax.tree.flatten(avals)
        return treedef, leaves

    def _build_init(self, specs, capacity):
        """Identity states created ON the mesh with a leading device axis
        (init == merge identity by UDA contract): each device folds its
        own shard; the merge program combines them over ICI."""
        d = self.mesh.devices.size
        axis_name = self.mesh_axes  # full axis tuple: dim0 over every mesh axis
        sharding = NamedSharding(self.mesh, P(axis_name))

        def init():
            st = (
                tuple(uda.init(capacity) for _, _, uda in specs),
                jnp.zeros(capacity, jnp.int64),
            )
            return [
                jnp.broadcast_to(leaf[None], (d,) + leaf.shape)
                for leaf in jax.tree.leaves(st)
            ]

        return jax.jit(init, out_shardings=sharding)

    def _build_fold(
        self,
        specs,
        evaluator,
        key_plan,
        col_names,
        narrow_names,
        int_dict_names,
        aux_key_order,
        capacity,
        n_state_leaves,
        treedef,
        lanes,
    ):
        """The FOLD unit: scan a set of blocks (one stream window, or the
        whole staged table on the warm path), return the updated
        per-device states. No collectives — those live in the merge unit,
        so every fold dispatch is device-local and async, and the fold
        executable is reused by any query whose scan lane matches
        (_fold_signature), regardless of finalize. Tracing adds the
        reduction lanes it takes to the set ``lanes``."""
        from pixie_tpu.ops import segment as _segment

        axis = self.mesh_axes  # collectives reduce over the FULL mesh
        has_host_gids = key_plan.host_gids is not None
        has_key_lut = isinstance(key_plan.device_expr, tuple)
        device_key = key_plan.device_expr
        preds = [
            e for n, e in evaluator.named_exprs if n.startswith("pred")
        ]

        def fold_fn(*arrs):
            # Layout: state leaves..., cols..., mask, [gids], [key_lut],
            # aux..., [narrow_offsets], gid_base.
            carry = jax.tree.unflatten(
                treedef, [a[0] for a in arrs[:n_state_leaves]]
            )
            i = n_state_leaves
            cols = {
                n: a[0]
                for n, a in zip(col_names, arrs[i : i + len(col_names)])
            }
            i += len(col_names)
            mask_all = arrs[i][0]
            i += 1
            gids_all = None
            if has_host_gids:
                gids_all = arrs[i][0]
                i += 1
            key_lut = None
            if has_key_lut:
                key_lut = arrs[i]
                i += 1
            gid_base = arrs[-1]
            end = -2 if narrow_names else -1
            narrow_vec = arrs[-2] if narrow_names else None
            aux = dict(zip(aux_key_order, arrs[i:end]))
            body = self._make_scan_body(
                specs, evaluator, col_names, narrow_names, int_dict_names,
                preds, device_key, has_key_lut, capacity, aux, narrow_vec,
                key_lut, gid_base, has_host_gids,
            )
            xs = (
                tuple(cols[n] for n in col_names),
                mask_all,
                gids_all if gids_all is not None else mask_all,
            )
            with _segment.lane_sink(lanes):
                carry, _ = jax.lax.scan(body, carry, xs)
            return tuple(leaf[None] for leaf in jax.tree.leaves(carry))

        n_sharded = (
            n_state_leaves + len(col_names) + 1 + (1 if has_host_gids else 0)
        )
        n_repl = (
            (1 if has_key_lut else 0)
            + len(aux_key_order)
            + (1 if narrow_names else 0)
            + 1  # +gid_base
        )
        in_specs = tuple([P(axis)] * n_sharded + [P()] * n_repl)
        out_specs = tuple([P(axis)] * n_state_leaves)
        return jax.jit(
            jax.shard_map(
                fold_fn,
                mesh=self.mesh,
                in_specs=in_specs,
                out_specs=out_specs,
                check_vma=False,
            )
        )

    def _build_merge(self, specs, capacity, n_state_leaves, treedef):
        """The COLLECTIVE-MERGE unit: per-device states in, replicated
        merged states out — one collective per UDA, nothing else. Keyed
        only by (UDA lane set, capacity, mesh), so every query sharing the
        lane set reuses it across staging geometries."""
        axis = self.mesh_axes  # collectives reduce over the FULL mesh
        ndev = self.mesh.devices.size

        def merge_fn(*arrs):
            states, presence = jax.tree.unflatten(
                treedef, [a[0] for a in arrs]
            )
            merged, presence = self._merge_states(
                specs, list(states), presence, ndev, axis
            )
            return tuple(
                jax.tree.leaves((tuple(merged), presence))
            )

        in_specs = tuple([P(axis)] * n_state_leaves)
        out_specs = tuple([P()] * n_state_leaves)
        return jax.jit(
            jax.shard_map(
                merge_fn,
                mesh=self.mesh,
                in_specs=in_specs,
                out_specs=out_specs,
                check_vma=False,
            )
        )

    def _build_fin(self, specs, capacity, force_state, treedef):
        """The FINALIZE unit: replicated merged states -> the single
        packed f64 fetch buffer (device finalize where the UDA allows,
        else raw state). A plain jit — inputs are replicated, no
        shard_map needed — so a changed-finalize query compiles ONLY this
        small unit while reusing the fold and merge executables."""
        fin_modes, _ = self._finalize_modes(specs, capacity, force_state)

        def fin_fn(*leaves):
            states, presence = jax.tree.unflatten(treedef, leaves)
            return self._finalize_pack(
                specs, fin_modes, list(states), presence
            )

        return jax.jit(fin_fn)

    def _stream_execute(
        self, m, specs, evaluator, key_plan, table, cols, n,
        f32_cols, cell_cols, aux, cacheable, base_row=0,
    ):
        """Streamed staging + window fold. Returns (merged, capacity,
        staged_for_cache|None), or None when gated off or on failure (the
        caller then falls back to monolithic staging, still on-device)."""
        try:
            return self._stream_execute_inner(
                m, specs, evaluator, key_plan, table, cols, n,
                f32_cols, cell_cols, aux, cacheable, base_row,
            )
        except mesh_lib.MeshGeometryError:
            # r23: a geometry failure must reach the degradation ladder
            # (re-plan on the surviving geometry, resume from the last
            # window checkpoint) — monolithic staging on the SAME
            # failed geometry would just hit the fault again.
            raise
        except Exception as e:
            import logging
            import traceback

            key = f"{type(e).__name__}: {e}"
            if key not in self.stream_fallback_errors:
                self.stream_fallback_errors[key] = traceback.format_exc()
                logging.getLogger("pixie_tpu.parallel").warning(
                    "streaming stage failed, falling back to monolithic "
                    "staging: %s",
                    key,
                )
            return None

    def _stream_execute_inner(
        self, m, specs, evaluator, key_plan, table, cols, n,
        f32_cols, cell_cols, aux, cacheable, base_row=0,
    ):
        import concurrent.futures
        import types as _types

        from pixie_tpu.ops import segment as _segment
        from pixie_tpu.parallel import staging as _staging

        capacity, n_passes = self._pass_plan(specs, key_plan.num_groups)
        if n_passes != 1:
            # Multi-pass gid windows re-scan the staged blocks once per
            # pass: they need HBM-resident blocks, not a stream.
            return None
        # Resident ingest (r13): when the table has an HBM ring, stream
        # at the RING's window size so plan window w covers exactly ring
        # window (base_row + w·W)/W — a hit substitutes device-resident
        # blocks for the whole pack+transfer of that window.
        ring = self._resident_ring(table, m.source_op)
        window_rows = flags.streaming_window_rows
        if ring is not None:
            window_rows = ring.window_rows
        plan = _staging.plan_stream(
            self.mesh,
            cols,
            n,
            window_rows,
            block_rows=self.block_rows,
            f32_cols=f32_cols,
            cell_cols=cell_cols,
            num_groups=max(key_plan.num_groups, 1),
            has_gids=key_plan.host_gids is not None,
            gids=key_plan.host_gids,
        )
        if ring is not None and (
            plan.window_rows != ring.window_rows
            or (plan.d, plan.nblk, plan.b) != (ring.d, ring.nblk, ring.b)
        ):
            ring = None  # clamped geometry (small table): no aligned hits
        aux = dict(aux)  # int-dict LUTs are stream-local; keep caller's aux clean
        for n2 in sorted(plan.int_dicts):
            aux[f"intdict:{n2}"] = np.asarray(plan.int_dicts[n2])
        aux_vals = list(aux.values())
        aux_key_order = list(aux.keys())
        col_names = sorted(cols)
        narrow_names = sorted(plan.narrow_offsets)
        # Program identity: the bucketed WINDOW geometry (every window
        # shares it by construction, and so does every table whose padded
        # size lands in the same bucket).
        shim = _types.SimpleNamespace(
            blocks={
                name: _types.SimpleNamespace(
                    shape=(plan.d, plan.nblk, plan.b),
                    dtype=plan.block_dtypes[name],
                )
                for name in col_names
            },
            mask=_types.SimpleNamespace(shape=(plan.d, plan.nblk, plan.b)),
            narrow_offsets=plan.narrow_offsets,
            int_dicts=plan.int_dicts,
        )
        treedef, leaves = self._state_template(specs, capacity)
        init_p, fold_p, merge_p, fin_p, fold_sig = self._unit_programs(
            m, specs, evaluator, key_plan, shim, aux_key_order,
            aux_vals, capacity,
        )
        _, templates = self._finalize_modes(
            specs, capacity, m.agg_op.stage == AggStage.PARTIAL
        )

        # Window-level fold checkpointing (r23, flag mesh_fold_checkpoint,
        # multi-axis-CONFIGURED executors only — gated on the FULL
        # geometry, not the current rung, because a resume lands on a
        # DIFFERENT (often flat) degradation rung by construction): the
        # fold's identity is keyed geometry-FREE, and every rung keeps
        # the total device count, so the padded window geometry (and
        # with it the carried state's shape) is invariant across rungs.
        ckpt_key = None
        start_w = 0
        if flags.mesh_fold_checkpoint and len(self._full_mesh_config.axes) > 1:
            ckpt_key = "|".join(
                (
                    re.sub(r"mesh:[^|]*", "mesh:*", fold_sig),
                    f"rows:{n}",
                    f"win:{plan.window_rows}",
                    f"base:{base_row}",
                    m.source_op.table_name,
                )
            )

        axis_name = self.mesh_axes  # full axis tuple: dim0 over every mesh axis
        sharding = NamedSharding(self.mesh, P(axis_name))
        repl = NamedSharding(self.mesh, P())
        has_host_gids = key_plan.host_gids is not None
        # Constant across windows: key LUT, aux, narrow offsets. Committed
        # replicated so they match the AOT-compiled executable's shardings.
        extra_args = []
        if isinstance(key_plan.device_expr, tuple):
            extra_args.append(
                jax.device_put(np.asarray(key_plan.device_expr[2]), repl)
            )
        extra_args.extend(
            jax.device_put(np.asarray(v), repl) for v in aux_vals
        )
        if plan.narrow_offsets:
            extra_args.append(
                jax.device_put(
                    np.asarray(
                        [plan.narrow_offsets[n2] for n2 in narrow_names],
                        np.int64,
                    ),
                    repl,
                )
            )
        gid_base = jax.device_put(np.int32(0), repl)  # single pass
        gids = key_plan.host_gids

        # Background AOT compile (r7): lower+compile the fold program on
        # a worker thread while pack/transfer stream — the 200s-class XLA
        # compile overlaps the staging instead of preceding it. Fold
        # dispatches are deferred (windows keep transferring) until the
        # compile future resolves; a compile failure falls back to the
        # in-line jit path, recorded in stream_fallback_errors.
        fold_fn = None
        fut_c = None
        if flags.aot_compile:
            avals = [
                jax.ShapeDtypeStruct(
                    (plan.d,) + tuple(l.shape), l.dtype, sharding=sharding
                )
                for l in leaves
            ]
            avals += [
                jax.ShapeDtypeStruct(
                    (plan.d, plan.nblk, plan.b),
                    plan.block_dtypes[n2],
                    sharding=sharding,
                )
                for n2 in col_names
            ]
            avals.append(
                jax.ShapeDtypeStruct(
                    (plan.d, plan.nblk, plan.b), np.bool_, sharding=sharding
                )
            )
            if has_host_gids:
                avals.append(
                    jax.ShapeDtypeStruct(
                        (plan.d, plan.nblk, plan.b),
                        plan.gid_dtype,
                        sharding=sharding,
                    )
                )
            avals += [
                jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
                for a in extra_args
            ]
            avals.append(
                jax.ShapeDtypeStruct((), gid_base.dtype, sharding=repl)
            )
            fut_c = self._aot_compile_async(fold_sig, fold_p, tuple(avals))
        else:
            fold_fn = fold_p

        def prof(key, dt):
            # Per-window sums: the stream as a whole is the caller's
            # device.stage span, so the span count stays per query.
            COLD_PROFILE[key] = COLD_PROFILE.get(key, 0.0) + dt

        def resolve_fold(block: bool) -> bool:
            """Bind fold_fn once the AOT compile is available (or failed).
            With block=False this is a non-blocking poll; the final call
            blocks — by then every window has transferred, so the wait is
            exactly the non-overlapped compile remainder."""
            nonlocal fold_fn
            if fold_fn is not None:
                return True
            if not block and not fut_c.done():
                return False
            t0 = time.perf_counter()
            try:
                fold_fn = fut_c.result()
            except Exception as e:
                import logging
                import traceback

                key = f"aot-compile {type(e).__name__}: {e}"
                if key not in self.stream_fallback_errors:
                    self.stream_fallback_errors[key] = traceback.format_exc()
                    logging.getLogger("pixie_tpu.parallel").warning(
                        "background AOT compile failed, falling back to "
                        "in-line jit: %s",
                        key,
                    )
                fold_fn = fold_p
            prof("stage_compile_wait", time.perf_counter() - t0)
            return True

        win_blocks: list = []
        win_masks: list = []
        win_gids: list = []
        deferred: list = []  # transferred windows awaiting the compile
        inflight: "collections.deque" = collections.deque()
        flat_state = None

        # Resident-window hits: plan windows whose rows are already in
        # HBM (full ring windows only). Their pack is gids-only and
        # their blocks come from a device-side raw→plan convert.
        hits: dict[int, Any] = {}
        if ring is not None:
            for w0 in range(plan.n_windows):
                rows_w = min(
                    plan.window_rows, plan.num_rows - w0 * plan.window_rows
                )
                rw = ring.lookup(
                    base_row + w0 * plan.window_rows, rows_w, col_names
                )
                if rw is not None:
                    hits[w0] = rw
        # Decode programs compile on the AOT worker while the first
        # windows pack/transfer; in-line jit remains the fallback.
        if plan.codecs:
            self._kick_decode_aot(plan)
        dec_cache: dict = {}

        windows_folded = [0]  # dispatches this attempt (resume-aware)

        def dispatch_fold(dev_cols, mask, dev_g):
            nonlocal flat_state
            args = list(flat_state)
            args.extend(dev_cols[n2] for n2 in col_names)
            args.append(mask)
            if has_host_gids:
                args.append(dev_g)
            args.extend(extra_args)
            args.append(gid_base)
            t0 = time.perf_counter()
            # r23: the sharded dispatch runs under the recovery plane —
            # fault sites + collective watchdog; a geometry failure
            # raises out to the degradation ladder.
            flat_state = list(
                self._mesh_dispatch(
                    lambda: fold_fn(*args),
                    what="stream_fold",
                    fold_sig=fold_sig,
                )
            )
            dt = time.perf_counter() - t0
            prof("stage_stream_dispatch", dt)
            if resattr.ACTIVE:
                resattr.record_dispatch(
                    "stream_fold", dt,
                    program=resattr.program_name(fold_sig),
                )
            # Double-buffer backpressure: block on window k-2's fold so
            # at most two windows are in flight (one transferring, one
            # packing) — bounds host-pinned buffers and the device
            # transfer queue.
            inflight.append(flat_state[-1])
            if len(inflight) > 2:
                t0 = time.perf_counter()
                jax.block_until_ready(inflight.popleft())
                prof(
                    "stage_stream_compute_wait",
                    time.perf_counter() - t0,
                )
            windows_folded[0] += 1
            if ckpt_key is not None:
                # Window-boundary checkpoint (r23): pull the carried
                # per-device UDA state host-side, bit-exact (numpy copy
                # of the device buffers — no re-merge, no re-order). The
                # pull synchronizes the window, trading the double-buffer
                # overlap for mid-stream resumability; that is the
                # flag's documented cost, and it only applies on
                # multi-axis meshes.
                t0 = time.perf_counter()
                self._save_fold_checkpoint(
                    ckpt_key,
                    start_w + windows_folded[0],
                    [np.asarray(x) for x in flat_state],
                )
                prof("stage_stream_ckpt", time.perf_counter() - t0)

        t_wall0 = time.perf_counter()
        pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="stream-pack"
        )
        try:
            with _segment.platform_hint(self.mesh.devices.flat[0].platform):
                if ckpt_key is not None:
                    # Resume (r23): a prior attempt on a failed geometry
                    # checkpointed its carry state at window boundaries;
                    # adopt it on THIS mesh and refold only the windows
                    # after the last checkpoint. Merge order is
                    # untouched — the carry state is the same per-device
                    # partial the unfaulted fold would hold here, so
                    # sketches and group order stay bit-identical.
                    flat_state, start_w = self._load_fold_checkpoint(
                        ckpt_key, leaves, plan.d, sharding
                    )
                    if start_w:
                        _MESH_RESUMES.inc()
                        with self._geom_lock:
                            self._geom_events["resumes"] += 1
                if flat_state is None:
                    flat_state = list(init_p())
                # Pack workers adopt the query's trace context and
                # attribution (r15): host CPU burned packing windows
                # samples under this query's label, not as anonymous
                # pool-thread time.
                pack_fn = trace.attributed(
                    _staging.pack_stream_window, phase="pack"
                )
                fut = pool.submit(pack_fn, plan, cols, gids, 0, 0 in hits)
                for w in range(plan.n_windows):
                    t0 = time.perf_counter()
                    rows, packed, pgids, nbytes = fut.result()
                    prof("stage_stream_pack_wait", time.perf_counter() - t0)
                    if w + 1 < plan.n_windows:
                        # Window w+1 packs on the background thread while
                        # window w transfers and folds.
                        fut = pool.submit(
                            pack_fn, plan, cols, gids, w + 1,
                            (w + 1) in hits,
                        )
                    t0 = time.perf_counter()
                    if w in hits:
                        # Resident-ingest hit: the window's columns are
                        # already in HBM — convert raw→plan dtypes on
                        # device; only the (tiny) gids traveled.
                        dev_cols = self._convert_resident_window(
                            plan, hits[w], col_names
                        )
                    else:
                        dev_cols = self._put_window_cols(
                            plan, packed, col_names, dec_cache
                        )
                    mask = _staging._build_mask(
                        self.mesh, plan.d, plan.nblk, plan.b, rows
                    )
                    dev_g = _staging.put_window_gids(
                        self.mesh, pgids, plan.nblk, plan.b
                    )
                    dt_put = time.perf_counter() - t0
                    prof("stage_stream_put", dt_put)
                    wbytes = plan.window_block_nbytes() + (
                        _staging.staged_gid_nbytes(pgids)
                    )
                    prof("stage_bytes", float(wbytes))
                    prof("wire_bytes", float(nbytes))
                    if resattr.ACTIVE:
                        # r15: per-window staging row — staged (decoded
                        # HBM) vs wire (codec-compressed) bytes become
                        # attributable per query/tenant.
                        resattr.record_dispatch(
                            "stream_window", dt_put,
                            program=resattr.program_name(fold_sig),
                            rows=rows, staged_bytes=wbytes,
                            wire_bytes=nbytes,
                        )
                    if cacheable:
                        win_blocks.append(dev_cols)
                        win_masks.append(mask)
                        win_gids.append(dev_g)
                    if w < start_w:
                        # Resumed fold (r23): windows below the
                        # checkpoint are already in the adopted carry
                        # state — transferred for the warm-cache concat,
                        # never refolded.
                        continue
                    if not resolve_fold(block=False):
                        # Compile still running: keep streaming transfers
                        # (the windows land in HBM, where the cacheable
                        # path keeps them anyway) and fold later. Cap
                        # in-flight transfers at two windows so host
                        # buffers pinned by async device_put stay bounded.
                        deferred.append((dev_cols, mask, dev_g))
                        if len(deferred) >= 2:
                            t0 = time.perf_counter()
                            jax.block_until_ready(
                                list(deferred[-2][0].values())
                            )
                            prof(
                                "stage_stream_transfer_wait",
                                time.perf_counter() - t0,
                            )
                        continue
                    for d_args in deferred:
                        dispatch_fold(*d_args)
                    deferred.clear()
                    dispatch_fold(dev_cols, mask, dev_g)
                # Every window is transferred; if the compile is STILL in
                # flight, this wait is the only non-overlapped compile
                # time (stage_compile_wait in the breakdown).
                resolve_fold(block=True)
                for d_args in deferred:
                    dispatch_fold(*d_args)
                deferred.clear()
                t0 = time.perf_counter()
                # The final cross-host merge is a sharded dispatch too:
                # same recovery plane as the per-window folds (r23).
                merged_flat = self._mesh_dispatch(
                    lambda: merge_p(*flat_state),
                    what="stream_merge",
                    fold_sig=fold_sig,
                )
                buf = fin_p(*merged_flat)
                merged = self._unpack_outputs(templates, capacity, buf)
                prof("stage_stream_drain", time.perf_counter() - t0)
        finally:
            pool.shutdown(wait=True)
            prof("stage_overlap", time.perf_counter() - t_wall0)
            prof("stream_windows", float(plan.n_windows))
        if ckpt_key is not None:
            # Success: the fold's answer is out; the checkpoint must not
            # outlive it (a LATER fold of the same identity starts clean).
            with self._geom_lock:
                self._fold_ckpt.pop(ckpt_key, None)
            if start_w:
                self.last_resume_stats = {
                    "resumed_from_window": int(start_w),
                    "refolded_windows": int(plan.n_windows - start_w),
                    "total_windows": int(plan.n_windows),
                }
        staged_for_cache = None
        if cacheable:
            # Concatenate the windows into one monolithic staging so warm
            # queries hit HBM directly (same contract as stage_columns).
            with _timed("stage_concat"):
                staged_for_cache = _staging.concat_stream_windows(
                    self.mesh, plan, win_blocks, win_masks, win_gids,
                    key_plan.num_groups, key_plan.key_columns,
                    table.dictionaries,
                )
            if flags.aot_compile:
                # r8: AOT-compile the WARM fold (the concat geometry —
                # different from the stream window's) on the background
                # thread NOW, so the first warm query over this staging
                # dispatches a ready executable instead of compiling
                # inline. Best-effort: failures fall back to the in-line
                # jit path, recorded like stream compile failures.
                try:
                    self._aot_warm_fold(
                        m, specs, evaluator, key_plan, staged_for_cache,
                        aux, capacity,
                    )
                except Exception as e:
                    import logging
                    import traceback

                    key = f"warm-aot {type(e).__name__}: {e}"
                    if key not in self.stream_fallback_errors:
                        self.stream_fallback_errors[key] = (
                            traceback.format_exc()
                        )
                        logging.getLogger("pixie_tpu.parallel").warning(
                            "warm-fold AOT compile setup failed, first "
                            "warm query will jit inline: %s",
                            key,
                        )
        return merged, capacity, staged_for_cache

    @staticmethod
    def _unpack_outputs(templates, capacity, buf):
        """Split the single fetched f64 buffer back into per-spec values
        (finalized arrays or raw state pytrees, per the build-time
        templates) + the presence counts. Integer leaves were bitcast, so
        the int64 bit patterns round-trip exactly."""
        buf = np.asarray(buf)
        off = 0

        def unpack_int(size):
            nonlocal off
            hi = buf[off : off + size].astype(np.int64)
            lo = buf[off + size : off + 2 * size].astype(np.int64)
            off += 2 * size
            return (hi << 32) + lo

        values = []
        for treedef, leaves in templates:
            out_leaves = []
            for shape, dtype in leaves:
                size = int(np.prod(shape)) if shape else 1
                if np.issubdtype(dtype, np.integer) or dtype == np.bool_:
                    arr = unpack_int(size).astype(dtype).reshape(shape)
                else:
                    arr = buf[off : off + size].astype(dtype).reshape(shape)
                    off += size
                out_leaves.append(arr)
            values.append(jax.tree.unflatten(treedef, out_leaves))
        presence = unpack_int(capacity)
        return values, presence

    def _shared_scan_run(
        self, m, specs, evaluator, key_plan, staged, aux, cache_key,
        span=None,
    ):
        """Run the fold through the shared-scan coordinator (r12, flag
        ``shared_scans``): concurrent queries whose coalescing key
        matches share ONE dispatch and each runs only its own finalize.

        The EXACT key is everything the merged states depend on: the
        staged entry's IDENTITY (same arrays, via the cache key + object
        id), the fold signature (predicates, UDA lanes, key mode,
        geometry, aux shapes — output names and finalize modes excluded,
        so queries differing only there coalesce), the agg stage (a
        PARTIAL query's packed buffer holds raw states, a FULL query's
        holds finalized arrays — they must not share an unpack), and a
        content digest of the replicated aux values + key LUT (equal
        shapes with different values must not share).

        r16 widens the compatibility ladder: when this query's
        predicates normalize to data-driven comparison terms
        (``normalize_predicates``), a second predicate-ERASED key is
        offered to the coordinator — queries matching on everything BUT
        their predicates assemble into one batched dispatch
        (``_run_program_batched``) whose per-slot mask lanes evaluate
        each participant's predicates inside a single scan of the staged
        blocks."""
        from pixie_tpu.serving.shared_scan import aux_digest

        aux2 = dict(aux)
        for n2 in sorted(staged.int_dicts):
            aux2[f"intdict:{n2}"] = np.asarray(staged.int_dicts[n2])
        aux_vals = list(aux2.values())
        capacity, _n_passes = self._pass_plan(specs, key_plan.num_groups)
        fold_sig = self._fold_signature(
            m, specs, key_plan, staged, aux_vals, capacity
        )
        digest_vals = list(aux_vals)
        if isinstance(key_plan.device_expr, tuple):
            digest_vals.append(np.asarray(key_plan.device_expr[2]))
        stage = m.agg_op.stage.value
        key = (
            cache_key, fold_sig, stage, aux_digest(digest_vals),
            id(staged),
        )
        batch_key = terms = compute_batch = None
        if flags.shared_scan_predicate_batching:
            terms = normalize_predicates(
                m.predicates, evaluator, staged, aux2
            )
        if terms is not None:
            # Shared (predicate-independent) aux: the predicate consts/
            # LUTs ride the term table as data, so they leave both the
            # batched program's argument list and the compatibility key.
            pred_keys: set = set()
            for name, e in evaluator.named_exprs:
                if name.startswith("pred"):
                    pred_keys |= set(
                        evaluator.build_aux(e, staged.dictionaries)
                    )
            shared_aux = {
                k: v for k, v in aux.items() if k not in pred_keys
            }
            shared2 = dict(shared_aux)
            for n2 in sorted(staged.int_dicts):
                shared2[f"intdict:{n2}"] = np.asarray(
                    staged.int_dicts[n2]
                )
            shared_vals = list(shared2.values())
            erased = self._fold_signature(
                m, specs, key_plan, staged, shared_vals, capacity,
                preds_repr="<batched>",
            )
            sdigest = list(shared_vals)
            if isinstance(key_plan.device_expr, tuple):
                sdigest.append(np.asarray(key_plan.device_expr[2]))
            batch_key = (
                cache_key, erased, stage, aux_digest(sdigest),
                id(staged),
            )
            compute_batch = (
                lambda slot_terms: self._run_program_batched(
                    m, specs, evaluator, key_plan, staged, shared_aux,
                    slot_terms,
                )
            )
            if flags.aot_compile:
                # r17 satellite: compile the B=2 bucket's batched fold
                # in the background NOW — the first real batched
                # dispatch finds it ready instead of jitting inline.
                self._kick_batched_fold_aot(
                    m, specs, evaluator, key_plan, staged, shared_aux,
                    terms,
                )
        out = self._shared_scans.run(
            key,
            lambda: self._run_program(
                m, specs, evaluator, key_plan, staged, aux
            ),
            batch_key=batch_key,
            terms=terms,
            compute_batch=compute_batch,
        )
        self._note_lanes(span, "fold|" + fold_sig)
        return out

    # -- predicate-batched shared scans (r16) --------------------------------
    # Crescando/SharedDB posture: concurrent queries whose fold shapes
    # agree on everything except their predicates share ONE scan of the
    # staged blocks. The batched fold stacks per-query partial-agg state
    # lanes on a leading slot axis, evaluates each slot's predicates as
    # DATA (a (B, T) table of comparison terms over dtype-exact column
    # stacks), and fans finalize out per query — so the compiled
    # executable is keyed by a predicate-ERASED signature plus pow2
    # batch-width/term buckets, and batch composition changes never
    # recompile.

    def _pred_stacks(self, staged):
        """The two dtype-preserving predicate column stacks: int64 for
        int/bool/code blocks (incl. narrowed columns, which the scan
        body widens to int64 before stacking), float64 for float
        blocks. Cell-lane code columns are excluded (normalization
        refuses them). Derived from the staged geometry alone, so the
        stack layout is part of the predicate-erased signature."""
        int_cols, flt_cols = [], []
        for c in sorted(staged.blocks):
            if c in staged.int_dicts:
                continue
            k = np.dtype(staged.blocks[c].dtype).kind
            if k in "iub":
                int_cols.append(c)
            elif k == "f":
                flt_cols.append(c)
        return int_cols, flt_cols

    @staticmethod
    def _bucket_pow2(n: int, floor: int = 1) -> int:
        c = max(floor, 1)
        while c < n:
            c <<= 1
        return c

    def _build_batched_init(self, specs, capacity, batch):
        """Batched identity states: one init per (UDA set, capacity,
        batch width) — the r7 init unit with a slot axis between the
        device axis and the state."""
        d = self.mesh.devices.size
        axis_name = self.mesh_axes  # full axis tuple: dim0 over every mesh axis
        sharding = NamedSharding(self.mesh, P(axis_name))

        def init():
            st = (
                tuple(uda.init(capacity) for _, _, uda in specs),
                jnp.zeros(capacity, jnp.int64),
            )
            return [
                jnp.broadcast_to(
                    leaf[None, None], (d, batch) + leaf.shape
                )
                for leaf in jax.tree.leaves(st)
            ]

        return jax.jit(init, out_shardings=sharding)

    # term-table argument count of the batched fold (t_stack, t_col_i,
    # t_col_f, t_op, t_thr_i, t_thr_f, t_lut_i, t_lut_v, t_active,
    # slot_on). t_lut_i/t_lut_v are the r18 per-term IN-list LUT lanes:
    # (B, T, L) member values + validity, consulted when t_op == 6.
    _N_TERM_ARGS = 10

    def _build_batched_fold(
        self,
        specs,
        evaluator,
        key_plan,
        col_names,
        narrow_names,
        int_dict_names,
        aux_key_order,
        capacity,
        n_state_leaves,
        treedef,
        int_cols,
        flt_cols,
    ):
        """The batched FOLD unit (r16): same contract as _build_fold —
        device-local, no collectives, per-device states in and out —
        but carry leaves have a leading slot axis and the per-query
        predicate term tables ride as replicated args after the aux
        lane. One compiled executable serves every predicate-compatible
        batch at this (geometry, lanes, batch, terms) bucket."""
        axis = self.mesh_axes  # collectives reduce over the FULL mesh
        has_host_gids = key_plan.host_gids is not None
        has_key_lut = isinstance(key_plan.device_expr, tuple)
        device_key = key_plan.device_expr
        n_term = self._N_TERM_ARGS

        def batched_fold_fn(*arrs):
            # Layout: state leaves..., cols..., mask, [gids], [key_lut],
            # aux..., [narrow_offsets], term table (8), gid_base.
            carry = jax.tree.unflatten(
                treedef, [a[0] for a in arrs[:n_state_leaves]]
            )
            i = n_state_leaves
            cols = {
                n: a[0]
                for n, a in zip(col_names, arrs[i : i + len(col_names)])
            }
            i += len(col_names)
            mask_all = arrs[i][0]
            i += 1
            gids_all = None
            if has_host_gids:
                gids_all = arrs[i][0]
                i += 1
            key_lut = None
            if has_key_lut:
                key_lut = arrs[i]
                i += 1
            gid_base = arrs[-1]
            term_args = arrs[-(n_term + 1) : -1]
            if narrow_names:
                narrow_vec = arrs[-(n_term + 2)]
                aux_end = -(n_term + 2)
            else:
                narrow_vec = None
                aux_end = -(n_term + 1)
            aux = dict(zip(aux_key_order, arrs[i:aux_end]))
            body = self._make_scan_body(
                specs, evaluator, col_names, narrow_names,
                int_dict_names, [], device_key, has_key_lut, capacity,
                aux, narrow_vec, key_lut, gid_base, has_host_gids,
                pred_batch=(int_cols, flt_cols, term_args),
            )
            xs = (
                tuple(cols[n] for n in col_names),
                mask_all,
                gids_all if gids_all is not None else mask_all,
            )
            carry, _ = jax.lax.scan(body, carry, xs)
            return tuple(leaf[None] for leaf in jax.tree.leaves(carry))

        n_sharded = (
            n_state_leaves + len(col_names) + 1
            + (1 if has_host_gids else 0)
        )
        n_repl = (
            (1 if has_key_lut else 0)
            + len(aux_key_order)
            + (1 if narrow_names else 0)
            + n_term
            + 1  # +gid_base
        )
        in_specs = tuple([P(axis)] * n_sharded + [P()] * n_repl)
        out_specs = tuple([P(axis)] * n_state_leaves)
        return jax.jit(
            jax.shard_map(
                batched_fold_fn,
                mesh=self.mesh,
                in_specs=in_specs,
                out_specs=out_specs,
                check_vma=False,
            )
        )

    def _batched_fold_program(
        self, m, specs, evaluator, key_plan, staged, aux_key_order,
        aux_vals, capacity, B, T, L=1,
    ):
        """The batched FOLD unit for one (erased-sig, B, T, L) bucket
        plus the abstract argument shapes its AOT compile needs (L is
        the r18 IN-list LUT lane width). Shared by the dispatch path
        and the speculative kick so both resolve the SAME signature
        (one compile per bucket, in-flight dedup via _aot_futures)."""
        int_cols, flt_cols = self._pred_stacks(staged)
        erased = self._fold_signature(
            m, specs, key_plan, staged, aux_vals, capacity,
            preds_repr="<batched>",
        )
        bsig = f"bfold|{erased}|batch:{B}|terms:{T}|inlist:{L}"
        treedef, leaves = self._state_template(specs, capacity)
        col_names = sorted(staged.blocks)
        narrow_names = sorted(staged.narrow_offsets)
        int_dict_names = sorted(staged.int_dicts)
        fold_p = self._get_program(
            bsig,
            lambda: self._build_batched_fold(
                specs, evaluator, key_plan, col_names, narrow_names,
                int_dict_names, aux_key_order, capacity, len(leaves),
                treedef, int_cols, flt_cols,
            ),
            n_aux=len(aux_vals),
        )
        axis_name = self.mesh_axes  # full axis tuple: dim0 over every mesh axis
        sharded = NamedSharding(self.mesh, P(axis_name))
        repl = NamedSharding(self.mesh, P())
        d = staged.num_devices
        avals = [
            jax.ShapeDtypeStruct(
                (d, B) + tuple(l.shape), l.dtype, sharding=sharded
            )
            for l in leaves
        ]
        for n2 in col_names:
            a = staged.blocks[n2]
            avals.append(
                jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
            )
        avals.append(
            jax.ShapeDtypeStruct(
                staged.mask.shape, staged.mask.dtype,
                sharding=staged.mask.sharding,
            )
        )
        if key_plan.host_gids is not None:
            g = staged.gids
            avals.append(
                jax.ShapeDtypeStruct(g.shape, g.dtype, sharding=g.sharding)
            )
        if isinstance(key_plan.device_expr, tuple):
            lut = np.asarray(key_plan.device_expr[2])
            avals.append(
                jax.ShapeDtypeStruct(lut.shape, lut.dtype, sharding=repl)
            )
        for v in aux_vals:
            v = np.asarray(v)
            avals.append(
                jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=repl)
            )
        if staged.narrow_offsets:
            avals.append(
                jax.ShapeDtypeStruct(
                    (len(staged.narrow_offsets),), np.dtype(np.int64),
                    sharding=repl,
                )
            )
        # The 10-term table (t_stack..t_thr_f, the (B, T, L) IN LUT
        # lanes, t_active, slot_on) + gid_base.
        for dt in (
            np.int32, np.int32, np.int32, np.int32, np.int64,
            np.float64,
        ):
            avals.append(
                jax.ShapeDtypeStruct((B, T), np.dtype(dt), sharding=repl)
            )
        avals.append(
            jax.ShapeDtypeStruct((B, T, L), np.dtype(np.int64), sharding=repl)
        )
        avals.append(
            jax.ShapeDtypeStruct((B, T, L), np.dtype(np.bool_), sharding=repl)
        )
        avals.append(
            jax.ShapeDtypeStruct((B, T), np.dtype(np.bool_), sharding=repl)
        )
        avals.append(
            jax.ShapeDtypeStruct((B,), np.dtype(np.bool_), sharding=repl)
        )
        avals.append(
            jax.ShapeDtypeStruct((), np.dtype(np.int32), sharding=repl)
        )
        return bsig, fold_p, tuple(avals)

    def _kick_batched_fold_aot(
        self, m, specs, evaluator, key_plan, staged, shared_aux, terms
    ) -> None:
        """Speculative background compile of the batched fold at the
        B=2 bucket (the soak's p50 batch width) whenever a query's
        predicates normalize: by the time two predicate-compatible
        queries actually coalesce, their bucket's executable is
        compiled (or compiling) on the AOT worker instead of jitting
        inline under the batch's leader. Best-effort and deduped per
        bucket — a kick that never gets used costs one background
        compile, once."""
        try:
            aux = dict(shared_aux)
            for n2 in sorted(staged.int_dicts):
                aux[f"intdict:{n2}"] = np.asarray(staged.int_dicts[n2])
            capacity, _n_passes = self._pass_plan(
                specs, key_plan.num_groups
            )
            bsig, fold_p, avals = self._batched_fold_program(
                m, specs, evaluator, key_plan, staged,
                list(aux.keys()), list(aux.values()), capacity,
                2, self._bucket_pow2(max(len(terms), 1)),
                self._bucket_pow2(
                    max([len(t[5]) for t in terms] + [1])
                ),
            )
            self._aot_compile_async(
                bsig, fold_p, avals, profile_key="batched_compile"
            )
        except Exception:
            import logging

            logging.getLogger("pixie_tpu.parallel").warning(
                "batched-fold AOT kick failed (ignored)", exc_info=True
            )

    def _run_program_batched(
        self, m, specs, evaluator, key_plan, staged, aux, slot_terms
    ):
        """Execute ONE batched fold dispatch serving ``len(slot_terms)``
        predicate-compatible queries, and fan the results out per slot.
        The slot/term axes pad to pow2 buckets so compiled programs are
        reused across batch compositions; the merge and finalize units
        are the EXACT r7 executables the serial path uses, applied to
        each slot's state slice — per-query results are bit-identical
        to serial execution by construction of the mask lanes."""
        aux = dict(aux)
        for n2 in sorted(staged.int_dicts):
            aux[f"intdict:{n2}"] = np.asarray(staged.int_dicts[n2])
        aux_vals = list(aux.values())
        aux_key_order = list(aux.keys())
        capacity, n_passes = self._pass_plan(specs, key_plan.num_groups)
        int_cols, flt_cols = self._pred_stacks(staged)
        i_idx = {c: i for i, c in enumerate(int_cols)}
        f_idx = {c: i for i, c in enumerate(flt_cols)}
        nslots = len(slot_terms)
        B = self._bucket_pow2(nslots)
        T = self._bucket_pow2(max([len(t) for t in slot_terms] + [1]))
        # r18: IN-list LUT lane width — the longest member list across
        # every slot's op-6 terms, pow2-bucketed so the executable is
        # shared across IN-list lengths within a bucket.
        L = self._bucket_pow2(
            max([len(t[5]) for terms in slot_terms for t in terms] + [1])
        )
        t_stack = np.zeros((B, T), np.int32)
        t_col_i = np.zeros((B, T), np.int32)
        t_col_f = np.zeros((B, T), np.int32)
        t_op = np.zeros((B, T), np.int32)
        t_thr_i = np.zeros((B, T), np.int64)
        t_thr_f = np.zeros((B, T), np.float64)
        t_lut_i = np.zeros((B, T, L), np.int64)
        t_lut_v = np.zeros((B, T, L), np.bool_)
        t_active = np.zeros((B, T), np.bool_)
        slot_on = np.zeros((B,), np.bool_)
        for s, terms in enumerate(slot_terms):
            slot_on[s] = True
            for t, (stack, cname, op, thr_i, thr_f, in_vals) in (
                enumerate(terms)
            ):
                t_active[s, t] = True
                t_op[s, t] = op
                if stack == "i":
                    t_col_i[s, t] = i_idx[cname]
                    t_thr_i[s, t] = thr_i
                    if op == 6:
                        t_lut_i[s, t, : len(in_vals)] = in_vals
                        t_lut_v[s, t, : len(in_vals)] = True
                else:
                    t_stack[s, t] = 1
                    t_col_f[s, t] = f_idx[cname]
                    t_thr_f[s, t] = thr_f
        bsig, fold_p, avals = self._batched_fold_program(
            m, specs, evaluator, key_plan, staged, aux_key_order,
            aux_vals, capacity, B, T, L,
        )
        # AOT lane (ROADMAP r16 follow-on): resolve the batched fold
        # through the background compiler like the warm fold — the
        # executable caches per (erased-sig, B, T) bucket (and in the
        # persistent .jax_cache), a speculative kick at predicate-
        # normalization time usually has it compiling already, and a
        # compile failure falls back to the in-line jit recorded in
        # stream_fallback_errors.
        fold_fn = fold_p
        if flags.aot_compile:
            try:
                fold_fn = self._aot_compile_async(
                    bsig, fold_p, avals, profile_key="batched_compile"
                ).result()
            except Exception as e:
                import logging
                import traceback

                key = f"batched-aot {type(e).__name__}: {e}"
                if key not in self.stream_fallback_errors:
                    self.stream_fallback_errors[key] = (
                        traceback.format_exc()
                    )
                    logging.getLogger("pixie_tpu.parallel").warning(
                        "batched-fold AOT compile failed, falling back "
                        "to in-line jit: %s", key,
                    )
                fold_fn = fold_p
        treedef, leaves = self._state_template(specs, capacity)
        lanes = self._uda_set_sig(specs)
        mesh_s = self._mesh_sig
        col_names = sorted(staged.blocks)
        init_p = self._get_program(
            f"binit|{lanes}|cap:{capacity}|batch:{B}|mesh:{mesh_s}",
            lambda: self._build_batched_init(specs, capacity, B),
        )
        # Merge/finalize are the SAME cached units serial queries use.
        merge_p = self._get_program(
            f"merge|{lanes}|cap:{capacity}|mesh:{mesh_s}",
            lambda: self._build_merge(
                specs, capacity, len(leaves), treedef
            ),
        )
        force_state = m.agg_op.stage == AggStage.PARTIAL
        fin_p = self._get_program(
            f"fin|{lanes}|cap:{capacity}|state:{force_state}|mesh:{mesh_s}",
            lambda: self._build_fin(specs, capacity, force_state, treedef),
        )
        _, templates = self._finalize_modes(specs, capacity, force_state)
        # Replicated args are device_put with an explicit sharding so
        # they match the AOT-compiled executable's input shardings (the
        # in-line jit path auto-placed them; a Compiled does not).
        repl = NamedSharding(self.mesh, P())
        args = [staged.blocks[n] for n in col_names] + [staged.mask]
        if key_plan.host_gids is not None:
            args.append(staged.gids)
        if isinstance(key_plan.device_expr, tuple):
            args.append(
                jax.device_put(np.asarray(key_plan.device_expr[2]), repl)
            )
        args.extend(
            jax.device_put(np.asarray(v), repl) for v in aux_vals
        )
        if staged.narrow_offsets:
            args.append(
                jax.device_put(
                    np.asarray(
                        [
                            staged.narrow_offsets[n]
                            for n in sorted(staged.narrow_offsets)
                        ],
                        np.int64,
                    ),
                    repl,
                )
            )
        args.extend(
            jax.device_put(x, repl)
            for x in (
                t_stack, t_col_i, t_col_f, t_op, t_thr_i, t_thr_f,
                t_lut_i, t_lut_v, t_active, slot_on,
            )
        )
        from pixie_tpu.ops import segment as _segment

        per_slot: list[list] = [[] for _ in range(nslots)]
        with _segment.platform_hint(self.mesh.devices.flat[0].platform):
            for p in range(n_passes):
                flat = list(init_p())
                t0 = time.perf_counter()
                gb = jax.device_put(np.int32(p * capacity), repl)
                flat = list(
                    self._mesh_dispatch(
                        lambda: fold_fn(*flat, *args, gb),
                        what="batched_fold",
                        fold_sig=bsig,
                    )
                )
                dt_b = time.perf_counter() - t0
                if resattr.ACTIVE:
                    resattr.record_dispatch(
                        "batched_fold",
                        dt_b,
                        program=resattr.program_name(bsig),
                        rows=staged.num_rows,
                    )
                for s in range(nslots):
                    merged_flat = merge_p(*[leaf[:, s] for leaf in flat])
                    buf = fin_p(*merged_flat)
                    per_slot[s].append(
                        self._unpack_outputs(templates, capacity, buf)
                    )
        return [
            self._recombine_passes(per_slot[s], specs, capacity, n_passes)
            for s in range(nslots)
        ]

    def _record_fold_shape(
        self, m, specs, key_plan, staged, capacity, aux
    ) -> None:
        """Persist this query's fold shape for cross-restart prewarm
        replay (r12 satellite) when it is inside the replayable profile:
        device dictionary-code group key, bare-column agg args, no
        predicates/aux/windows. Best-effort — recording failures never
        touch the query."""
        if aux or capacity is None:
            return
        try:
            from pixie_tpu.serving.signatures import shape_from_staged

            shape = shape_from_staged(m, specs, key_plan, staged, capacity)
            if shape is not None:
                self.fold_signature_store.record(
                    m.source_op.table_name, shape
                )
        except Exception:
            import logging

            logging.getLogger("pixie_tpu.parallel").warning(
                "fold-shape record failed (ignored)", exc_info=True
            )

    def _run_program(
        self, m, specs, evaluator, key_plan, staged, aux, span=None
    ):
        """Execute the staged aggregation. Default (program_decompose):
        separately-cached init/fold/merge/finalize units — a query that
        differs only in finalize (output names, FULL vs PARTIAL, a new
        quantile over the same lane) reuses the expensive fold
        executable and compiles only the small finalize unit, and each
        unit compiles faster than the fused whole. The fused
        single-dispatch program remains behind the flag."""
        # Int-dictionary LUTs ride the aux lane (replicated args), so
        # dictionary content can change without recompiling.
        for n2 in sorted(staged.int_dicts):
            aux[f"intdict:{n2}"] = np.asarray(staged.int_dicts[n2])
        aux_vals = list(aux.values())
        aux_key_order = list(aux.keys())
        capacity, n_passes = self._pass_plan(specs, key_plan.num_groups)
        if not flags.program_decompose:
            return self._run_program_fused(
                m, specs, evaluator, key_plan, staged, aux, aux_vals,
                capacity, n_passes,
            )
        col_names = sorted(staged.blocks)
        init_p, fold_p, merge_p, fin_p, fold_sig = self._unit_programs(
            m, specs, evaluator, key_plan, staged, aux_key_order,
            aux_vals, capacity,
        )
        _, templates = self._finalize_modes(
            specs, capacity, m.agg_op.stage == AggStage.PARTIAL
        )
        args = [staged.blocks[n] for n in col_names] + [staged.mask]
        if key_plan.host_gids is not None:
            args.append(staged.gids)
        if isinstance(key_plan.device_expr, tuple):
            args.append(jnp.asarray(key_plan.device_expr[2]))
        args.extend(jnp.asarray(v) for v in aux_vals)
        if staged.narrow_offsets:
            args.append(
                jnp.asarray(
                    [
                        staged.narrow_offsets[n]
                        for n in sorted(staged.narrow_offsets)
                    ],
                    jnp.int64,
                )
            )
        from pixie_tpu.ops import segment as _segment

        # r8: the warm fold may already be AOT-compiled (kicked on the
        # background thread at the end of the cold stream, or by a
        # table-create prewarm). A Compiled requires exactly the avals it
        # was lowered at, so the replicated extras are committed
        # explicitly; any dispatch mismatch falls back to the in-line jit
        # with the error recorded (same contract as the stream fold).
        fold_exec = (
            self._aot_compiled.get(fold_sig) if flags.aot_compile else None
        )
        cargs = None
        if fold_exec is not None:
            repl = NamedSharding(self.mesh, P())
            cargs = [staged.blocks[n] for n in col_names] + [staged.mask]
            if key_plan.host_gids is not None:
                cargs.append(staged.gids)
            if isinstance(key_plan.device_expr, tuple):
                cargs.append(
                    jax.device_put(
                        np.asarray(key_plan.device_expr[2]), repl
                    )
                )
            cargs.extend(
                jax.device_put(np.asarray(v), repl) for v in aux_vals
            )
            if staged.narrow_offsets:
                cargs.append(
                    jax.device_put(
                        np.asarray(
                            [
                                staged.narrow_offsets[n]
                                for n in sorted(staged.narrow_offsets)
                            ],
                            np.int64,
                        ),
                        repl,
                    )
                )
        per_pass = []
        with _segment.platform_hint(self.mesh.devices.flat[0].platform):
            for p in range(n_passes):
                flat = list(init_p())
                folded = False
                if fold_exec is not None:
                    try:
                        gb = jax.device_put(
                            np.int32(p * capacity),
                            NamedSharding(self.mesh, P()),
                        )
                        flat = list(
                            self._mesh_dispatch(
                                lambda: fold_exec(*flat, *cargs, gb),
                                what="warm_fold",
                                fold_sig=fold_sig,
                            )
                        )
                        folded = True
                    except mesh_lib.MeshGeometryError:
                        raise  # r23: recovery ladder, not the jit retry
                    except Exception as e:
                        import logging
                        import traceback

                        fold_exec = None
                        key = f"warm-aot {type(e).__name__}: {e}"
                        if key not in self.stream_fallback_errors:
                            self.stream_fallback_errors[key] = (
                                traceback.format_exc()
                            )
                            logging.getLogger(
                                "pixie_tpu.parallel"
                            ).warning(
                                "AOT warm-fold dispatch failed, falling "
                                "back to in-line jit: %s",
                                key,
                            )
                if not folded:
                    flat = self._mesh_dispatch(
                        lambda: fold_p(*flat, *args, jnp.int32(p * capacity)),
                        what="warm_fold",
                        fold_sig=fold_sig,
                    )
                merged_flat = merge_p(*flat)
                buf = fin_p(*merged_flat)
                # ONE blocking fetch per pass: completion + transfer.
                per_pass.append(
                    self._unpack_outputs(templates, capacity, buf)
                )
        self._note_lanes(span, fold_sig)
        return self._recombine_passes(per_pass, specs, capacity, n_passes)

    def _run_program_fused(
        self, m, specs, evaluator, key_plan, staged, aux, aux_vals,
        capacity, n_passes,
    ):
        col_names = sorted(staged.blocks)
        sig = self._signature(m, specs, key_plan, staged, aux_vals, capacity)
        if f"mesh:{self._mesh_sig}" not in sig:  # geometry guard (r21/r23)
            raise mesh_lib.MeshGeometryError(
                "signature_mismatch",
                f"fused program signature does not carry this "
                f"executor's mesh geometry {self._mesh_sig!r}",
            )
        entry = self._program_cache.get(sig)
        if entry is None or entry[1] != len(aux_vals):
            aux_key_order = list(aux.keys())
            program = self._build_program(
                m, specs, evaluator, key_plan, staged, aux_key_order, capacity
            )
            _, templates = self._finalize_modes(
                specs, capacity, m.agg_op.stage == AggStage.PARTIAL
            )
            self._program_cache[sig] = (program, len(aux_key_order), templates)
            _PROGRAMS.set(len(self._program_cache))
        program, _, templates = self._program_cache[sig]
        args = [staged.blocks[n] for n in col_names] + [staged.mask]
        if key_plan.host_gids is not None:
            args.append(staged.gids)
        if isinstance(key_plan.device_expr, tuple):
            args.append(jnp.asarray(key_plan.device_expr[2]))
        args.extend(jnp.asarray(v) for v in aux_vals)
        if staged.narrow_offsets:
            args.append(
                jnp.asarray(
                    [
                        staged.narrow_offsets[n]
                        for n in sorted(staged.narrow_offsets)
                    ],
                    jnp.int64,
                )
            )
        # First call traces: pin the kernel strategy to the platform the
        # MESH runs on (may differ from jax.default_backend()).
        from pixie_tpu.ops import segment as _segment

        per_pass = []
        with _segment.platform_hint(self.mesh.devices.flat[0].platform):
            for p in range(n_passes):
                buf = self._mesh_dispatch(
                    lambda: program(*args, jnp.int32(p * capacity)),
                    what="fused_fold",
                    fold_sig=sig,
                )
                # ONE blocking fetch per pass: completion + transfer.
                per_pass.append(
                    self._unpack_outputs(templates, capacity, buf)
                )
        return self._recombine_passes(per_pass, specs, capacity, n_passes)

    @staticmethod
    def _recombine_passes(per_pass, specs, capacity, n_passes):
        if n_passes == 1:
            return per_pass[0], capacity
        # Recombine: every leaf (finalized output or state) and the
        # presence counts carry a leading group axis — concatenation
        # reassembles the full gid space across pass windows.
        values = [
            jax.tree.map(
                lambda *leaves: np.concatenate(leaves, axis=0),
                *(vp[0][i] for vp in per_pass),
            )
            for i in range(len(specs))
        ]
        presence = np.concatenate([vp[1] for vp in per_pass])
        return (values, presence), capacity

    # -- finalize -----------------------------------------------------------
    def _partial_state_batch(self, m, specs, key_plan, outputs_and_presence, table):
        """PARTIAL stage: wrap the device-computed states as the StateBatch
        the downstream MERGE agg consumes (ref: the PEM side of
        partial_op_mgr.h:94 serializing partial aggregates). Only observed
        groups ship — a dictionary-keyed plan may carry unobserved slots."""
        from pixie_tpu.exec.agg_node import StateBatch

        values, presence = outputs_and_presence
        n = max(key_plan.num_groups, 1) if m.agg_op.groups else 1
        if m.agg_op.groups:
            keep = np.asarray(presence[:n]) > 0
        else:
            keep = np.ones(1, dtype=bool)
        idx = np.nonzero(keep)[0]
        key_columns = [
            col.take(idx) if isinstance(col, DictColumn)
            else np.asarray(col)[idx]
            for col in key_plan.key_columns
        ]
        states = {}
        arg_dicts = {}
        for (out_name, arg_e, uda), st in zip(specs, values):
            states[out_name] = jax.tree.map(
                lambda a: np.asarray(a)[:n][keep], st
            )
            if uda.string_state and isinstance(arg_e, ColumnRef):
                d = table.dictionaries.get(arg_e.name)
                if d is not None:
                    # Snapshot: device states hold codes into the table's
                    # dictionary; the merge stage translates through this.
                    arg_dicts[out_name] = StringDictionary(list(d.values()))
        return StateBatch(
            key_columns=key_columns,
            states=states,
            num_groups=int(keep.sum()),
            group_names=m.agg_op.groups,
            eow=True,
            eos=True,
            arg_dicts=arg_dicts,
        )

    def _finalize(
        self,
        m,
        specs,
        key_plan,
        capacity,
        outputs_and_presence,
        registry,
        table,
        host_any=None,
        group_range=None,
        eow=True,
        eos=True,
    ):
        host_any = host_any or {}
        device_specs = [s for s in specs if s[0] not in host_any]
        values, presence = outputs_and_presence
        # Use the SAME per-pass capacity the program was compiled with —
        # recomputing modes at staged.capacity could disagree with the
        # packed buffer layout when _pass_plan shrank the window (ADVICE r3).
        modes, _ = self._finalize_modes(device_specs, capacity)
        by_out = {
            s[0]: (s, mode, val)
            for s, mode, val in zip(device_specs, modes, values)
        }
        if group_range is not None:
            # Windowed finalize: this call covers groups
            # [off, off+cnt) — one window's slice of the (window x group)
            # id space.
            off, cnt = group_range
            values = [
                jax.tree.map(lambda a: np.asarray(a)[off : off + cnt], v)
                for v in values
            ]
            by_out = {
                s[0]: (s, mode, val)
                for s, mode, val in zip(device_specs, modes, values)
            }
            presence = np.asarray(presence)[off : off + cnt]
            n = cnt if m.agg_op.groups else 1
        else:
            n = max(key_plan.num_groups, 1) if m.agg_op.groups else 1
        rel = m.agg_op.output_relation([_pre_agg_relation(m, registry)], registry)
        # Only observed groups are emitted (host-engine semantics): drop
        # slots whose rows were all filtered out / expired. Group-by-none
        # keeps its single row (the reference emits one row on empty input).
        if m.agg_op.groups:
            keep = np.asarray(presence[:n]) > 0
        else:
            keep = np.ones(1, dtype=bool)
        out_cols: list = []
        for g, col in zip(m.agg_op.groups, key_plan.key_columns):
            out_cols.append(
                col.take(np.nonzero(keep)[0])
                if isinstance(col, DictColumn)
                else np.asarray(col)[keep]
            )
        from pixie_tpu.types.dtypes import host_dtype

        for out_name, arg_e, uda in specs:
            schema = rel.col(out_name)
            if out_name in host_any:
                rep = np.asarray(host_any[out_name])[:n][keep]
                if schema.data_type == DataType.STRING:
                    src_dict = table.dictionaries.get(arg_e.name)
                    vals2 = (
                        src_dict.decode(rep.astype(np.int32))
                        if src_dict is not None
                        else np.full(len(rep), "", dtype=object)
                    )
                    d = StringDictionary()
                    out_cols.append(DictColumn(d.encode(vals2), d))
                else:
                    out_cols.append(
                        rep.astype(host_dtype(schema.data_type))
                    )
                continue
            _spec, mode, val = by_out[out_name]
            if mode == "state":
                sliced = jax.tree.map(lambda a: np.asarray(a)[:n][keep], val)
                out = uda.finalize(sliced)
            else:
                arr = np.asarray(val)[:n][keep]
                out = (
                    uda.format_output(arr)
                    if mode == "devfin" and uda.format_output is not None
                    else arr
                )
            if schema.data_type == DataType.STRING:
                if uda.string_state:
                    # Code-valued state (any(STRING)): decode through the
                    # table dictionary — matches agg_node._finalized_batch.
                    src_dict = (
                        table.dictionaries.get(arg_e.name)
                        if isinstance(arg_e, ColumnRef)
                        else None
                    )
                    codes = np.asarray(out)
                    vals = (
                        src_dict.decode(codes)
                        if src_dict is not None
                        else np.full(len(codes), "", dtype=object)
                    )
                else:
                    vals = np.asarray(out, dtype=object)
                d = StringDictionary()
                out_cols.append(DictColumn(d.encode(vals), d))
            else:
                out_cols.append(np.asarray(out, dtype=host_dtype(schema.data_type)))
        return RowBatch(rel, out_cols, eow=eow, eos=eos)


def _pre_agg_relation(m: _Match, registry):
    return MapOp(
        tuple((name, e) for name, e in m.col_exprs.items())
    ).output_relation([m.source_relation], registry)


def _uses_ctx_func(expr, relation, registry) -> bool:
    """Does the expression call a needs_ctx (metadata-state) UDF? Such
    results change when k8s metadata churns, with no table write. Resolves
    the actual overload by argument types; only when typing fails does it
    fall back to any-overload (conservative: may disable caching, never
    enables stale results)."""
    if isinstance(expr, FuncCall):
        udf = None
        try:
            types = [expr_data_type(a, relation, registry) for a in expr.args]
            udf = registry.lookup_scalar(expr.name, types)
        except (KeyError, ValueError):
            pass
        if udf is not None:
            if udf.needs_ctx:
                return True
        elif any(
            f.needs_ctx for f in registry.scalar_overloads(expr.name)
        ):
            return True
        return any(_uses_ctx_func(a, relation, registry) for a in expr.args)
    return False
