"""Sketch UDAs: quantiles (log-histogram + t-digest), HLL, count-min.

Ref: src/carnot/funcs/builtins/math_sketches.h:34-82 (QuantilesUDA, t-digest —
the only sketch the reference ships; HLL and count-min are net-new here, per
SURVEY.md §6). Output format parity: quantiles finalize to a JSON string
{"p01":..,"p10":..,"p25":..,"p50":..,"p75":..,"p90":..,"p99":..} with
ST_QUANTILES semantics so `px.pluck_float64(col, 'p50')` works unchanged.

The default `quantiles` UDA uses the log-histogram sketch (merge == add ==
one lax.psum over ICI); `quantiles_tdigest` is the t-digest variant whose
merge is a TREE contract (all-gather + sort-recompress).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from pixie_tpu.ops import countmin, histogram, hll, segment, tdigest
from pixie_tpu.types import DataType, SemanticType
from pixie_tpu.udf.registry import Registry
from pixie_tpu.udf.udf import UDA, MergeKind

F = DataType.FLOAT64
I = DataType.INT64
S = DataType.STRING

QUANTILE_KEYS = ("p01", "p10", "p25", "p50", "p75", "p90", "p99")
QUANTILE_QS = (0.01, 0.10, 0.25, 0.50, 0.75, 0.90, 0.99)


def _quantile_semantic(sems):
    if sems and sems[0] in (
        SemanticType.ST_DURATION_NS,
        SemanticType.ST_TIME_NS,
    ):
        return SemanticType.ST_DURATION_NS_QUANTILES
    return SemanticType.ST_QUANTILES


def _format_quantiles(qv: np.ndarray) -> np.ndarray:
    """[G, 7] quantile values -> JSON strings (host finalize)."""
    out = np.empty(qv.shape[0], dtype=object)
    for g in range(qv.shape[0]):
        out[g] = (
            "{"
            + ",".join(
                f'"{k}":{float(qv[g, i]):.6g}' for i, k in enumerate(QUANTILE_KEYS)
            )
            + "}"
        )
    return out


def register(r: Registry) -> None:
    def hist_quantiles_uda():
        return UDA(
            name="quantiles",
            arg_types=(F,),
            out_type=S,
            init=lambda g: histogram.init(g),
            update=lambda st, gids, col, mask=None: histogram.update(
                st, gids, col, mask
            ),
            merge=histogram.merge,
            finalize=lambda st: _format_quantiles(
                np.asarray(histogram.quantile_values(st, QUANTILE_QS))
            ),
            device_finalize=lambda st: histogram.quantile_values(
                st, QUANTILE_QS
            ),
            format_output=_format_quantiles,
            merge_kind=MergeKind.PSUM,
            out_semantic=_quantile_semantic,
            host_finalize=True,
            stage_f32_ok=True,  # log-bin assignment is way coarser than f32
            doc=(
                "Approximate p01..p99 via a log-binned histogram sketch "
                "(DDSketch-style; ~1.4% relative error; psum-mergeable)."
            ),
        )

    r.register_uda(hist_quantiles_uda())

    def tdigest_uda():
        return UDA(
            name="quantiles_tdigest",
            arg_types=(F,),
            out_type=S,
            init=lambda g: tdigest.init(g),
            update=lambda st, gids, col, mask=None: tdigest.update(
                st, gids, col, mask
            ),
            merge=tdigest.merge,
            finalize=lambda st: _format_quantiles(
                np.asarray(tdigest.quantile_values(st, QUANTILE_QS))
            ),
            device_finalize=lambda st: tdigest.quantile_values(
                st, QUANTILE_QS
            ),
            format_output=_format_quantiles,
            merge_kind=MergeKind.TREE,
            out_semantic=_quantile_semantic,
            host_finalize=True,
            stage_f32_ok=True,  # centroid means/weights are f32 already
            doc="Approximate p01..p99 via a static-shape merging t-digest.",
        )

    r.register_uda(tdigest_uda())

    def hll_uda(arg_t):
        return UDA(
            name="approx_count_distinct",
            arg_types=(arg_t,),
            out_type=I,
            init=lambda g: hll.init(g),
            update=lambda st, gids, col, mask=None: hll.update(st, gids, col, mask),
            merge=hll.merge,
            # Cell lane: int-dict-staged columns (<=256 distinct) update
            # registers from the per-(group, code) presence histogram —
            # the pipeline only routes INT64 columns here, so the LUT
            # hashes exactly like the row path's raw values.
            cell_update=hll.cell_update,
            finalize=hll.estimate,
            device_finalize=hll.estimate_terms,
            format_output=hll.estimate_from_terms,
            merge_kind=MergeKind.PMAX,
            doc=(
                "Approximate distinct count via HyperLogLog "
                "(2048 registers, ~2.3% error; pmax-mergeable). Net-new vs "
                "the reference. High-cardinality columns update registers "
                "via the r8 sort–compact lane above segment.SORTED_MIN_ROWS "
                "(O(registers) scatter instead of O(rows)); small-domain "
                "columns keep the MXU cell lane."
            ),
        )

    for t in (I, F, S):  # strings arrive as dictionary codes
        r.register_uda(hll_uda(t))

    def countmin_uda(arg_t):
        return UDA(
            name="count_min",
            arg_types=(arg_t,),
            out_type=S,
            init=lambda g: {
                "cm": countmin.init(g),
                "total": jnp.zeros((g,), jnp.int64),
            },
            update=lambda st, gids, col, mask=None: {
                "cm": countmin.update(st["cm"], gids, col, mask),
                "total": st["total"]
                + segment.seg_count(gids, st["total"].shape[0], mask),
            },
            merge=lambda a, b: {"cm": a["cm"] + b["cm"], "total": a["total"] + b["total"]},
            cell_update=lambda st, hist, lut: {
                "cm": countmin.cell_update(st["cm"], hist, lut),
                "total": st["total"] + hist.sum(axis=1),
            },
            finalize=lambda st: _format_cm(st),
            device_finalize=lambda st: jnp.stack(
                [st["total"], st["cm"].max(axis=(1, 2))], axis=1
            ),
            format_output=_format_cm_totals,
            merge_kind=MergeKind.PSUM,
            host_finalize=True,
            doc=(
                "Count-min frequency sketch (4x8192; psum-mergeable). "
                "Finalize emits sketch metadata JSON; use pixie_tpu.ops."
                "countmin.query for point lookups. Net-new vs the "
                "reference. Bucket counts ride the r8 sort–compact lane "
                "above segment.SORTED_MIN_ROWS; the cell lane serves "
                "small-domain columns."
            ),
        )

    for t in (I, S):
        r.register_uda(countmin_uda(t))


def _format_cm(st) -> np.ndarray:
    cm = np.asarray(st["cm"])
    total = np.asarray(st["total"])
    return _format_cm_totals(
        np.stack([total, cm.max(axis=(1, 2), initial=0)], axis=1)
    )


def _format_cm_totals(arr) -> np.ndarray:
    """[G, 2] (total, max_est) -> metadata JSON (depth/width are static)."""
    arr = np.asarray(arr)
    out = np.empty(arr.shape[0], dtype=object)
    for g in range(arr.shape[0]):
        out[g] = (
            f'{{"total":{int(arr[g, 0])},"depth":{countmin.DEFAULT_DEPTH},'
            f'"width":{countmin.DEFAULT_WIDTH},"max_est":{int(arr[g, 1])}}}'
        )
    return out
