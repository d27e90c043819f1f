"""http_events: the table px/service_stats reads, its generator, its
query and its plain reference.

The query is px/service_stats's ``svc_let`` as the dashboard runs it by
default (``svc=''``, every service): the script's filters, the 10 s
``px.bin`` of ``time_``, ``calc_http_LET``'s aggregation per service and
window, and ``format_LET_aggs``. The generator is ``bench.gen_http_events``
copied (uniform services, weighted status codes, exponential latency),
vectorised and split into independent streams, with the columns the
script reads that it lacked (``resp_body_size``, ``req_path``,
``remote_addr``) added. The reference is plain numpy over the generated
arrays: exact counts, sums, error rates and order statistics per service
and window. It imports nothing of the program.
"""

from __future__ import annotations

import math

import numpy as np

TABLE = "http_events"
OUT = "LET"
# The quantiles svc_let plucks from px.quantiles.
QUANTILES = (("latency_p50", 0.50), ("latency_p90", 0.90), ("latency_p99", 0.99))
PROBE_PATHS = ("/healthz", "/readyz")  # what the script filters out
UNRESOLVED = "-"
_CHUNK = 1 << 24
_LUT_BITS = 20  # categorical draws: one integer a row, looked up


def service_names(cfg: dict) -> list[str]:
    return [f"ns/svc-{i}" for i in range(cfg["services"])]


def path_names(cfg: dict) -> list[str]:
    return [f"/api/v1/r{i}" for i in range(cfg["api_paths"])] + list(PROBE_PATHS)


def addr_names(cfg: dict) -> list[str]:
    return [f"10.0.{i // 256}.{i % 256}" for i in range(cfg["clients"])] + [UNRESOLVED]


def _lut(shares) -> np.ndarray:
    """A table of 2**_LUT_BITS codes, code i filling round(share_i * size)
    entries (the rounding goes to the first code)."""
    size = 1 << _LUT_BITS
    counts = np.round(np.asarray(shares) * size).astype(np.int64)
    counts[0] += size - counts.sum()
    return np.repeat(np.arange(len(counts), dtype=np.int32), counts)


def _path_shares(cfg: dict) -> list[float]:
    probe = cfg["probe_share"]
    api = (1.0 - len(PROBE_PATHS) * probe) / cfg["api_paths"]
    return [api] * cfg["api_paths"] + [probe] * len(PROBE_PATHS)


def _addr_shares(cfg: dict) -> list[float]:
    u = cfg["unresolved_share"]
    return [(1.0 - u) / cfg["clients"]] * cfg["clients"] + [u]


def generate(cfg: dict, n: int, rng: np.random.Generator) -> dict:
    """``n`` rows of the config's columns (no time column: row order is
    time order, and the timeline gives each row's timestamp)."""
    svc = np.empty(n, np.int32)
    status = np.empty(n, np.int64)
    latency = np.empty(n, np.float64)
    body = np.empty(n, np.int64)
    path = np.empty(n, np.int32)
    addr = np.empty(n, np.int32)
    # Status by lookup in a table of 1000 draws, as the weights are in
    # thousandths: one integer draw a row, not a float and a search.
    share = np.asarray(cfg["status_weights"]) * 1000
    if not np.allclose(share, np.round(share)) or round(share.sum()) != 1000:
        raise ValueError("status_weights must be thousandths summing to 1")
    status_lut = np.repeat(
        np.asarray(cfg["status_codes"], np.int64), np.round(share).astype(int)
    )
    path_lut, addr_lut = _lut(_path_shares(cfg)), _lut(_addr_shares(cfg))
    for off in range(0, n, _CHUNK):
        m = min(_CHUNK, n - off)
        s = slice(off, off + m)
        svc[s] = rng.integers(0, cfg["services"], m, dtype=np.int32)
        status[s] = status_lut[rng.integers(0, 1000, m, dtype=np.uint16)]
        latency[s] = rng.exponential(cfg["latency_mean_ns"], m)
        body[s] = rng.integers(0, 1 << cfg["body_size_bits"], m, dtype=np.int64)
        path[s] = path_lut[rng.integers(0, 1 << _LUT_BITS, m, dtype=np.uint32)]
        addr[s] = addr_lut[rng.integers(0, 1 << _LUT_BITS, m, dtype=np.uint32)]
    return {
        "service": svc,
        "resp_status": status,
        "latency": latency,
        "resp_body_size": body,
        "req_path": path,
        "remote_addr": addr,
    }


def relation():
    from pixie_tpu.types import DataType, Relation, SemanticType

    S = DataType.STRING
    return Relation.of(
        ("time_", DataType.TIME64NS, SemanticType.ST_TIME_NS),
        ("service", S, SemanticType.ST_SERVICE_NAME),
        ("req_path", S),
        ("remote_addr", S),
        ("resp_status", DataType.INT64),
        ("resp_body_size", DataType.INT64),
        ("latency", DataType.FLOAT64, SemanticType.ST_DURATION_NS),
    )


_DICTS = {"service": service_names, "req_path": path_names, "remote_addr": addr_names}


def identity_codes(table, cfg: dict) -> None:
    """Dictionary codes 0..n-1 in generator order."""
    for col, names in _DICTS.items():
        d = table.dictionaries[col]
        for name in names(cfg):
            d.get_code(name)


def pydict(table, cols: dict, lo: int, hi: int, times: np.ndarray) -> dict:
    """Rows [lo, hi) of ``cols`` as the program's write_pydict input."""
    from pixie_tpu.table.column import DictColumn

    out = {"time_": times}
    for col in _DICTS:
        out[col] = DictColumn(cols[col][lo:hi], table.dictionaries[col])
    for col in ("resp_status", "resp_body_size", "latency"):
        out[col] = cols[col][lo:hi]
    return out


def query(cfg: dict, start_ns: int, end_ns: int) -> str:
    """px/service_stats's svc_let over [start_ns, end_ns] (both ends
    inclusive), with svc='' as the dashboard's default. The script's
    ``df.ctx[k8s_object]`` is the stored ``service`` column here."""
    return (
        f"window_ns = px.DurationNanos({cfg['window_ns']})\n"
        f"df = px.DataFrame(table='{TABLE}', start_time={start_ns},"
        f" end_time={end_ns})\n"
        "df.timestamp = px.bin(df.time_, window_ns)\n"
        "df = df[df.service != '']\n"
        "df.failure = df.resp_status >= 400\n"
        "df = df[df.req_path != '/healthz' and df.req_path != '/readyz'"
        " and df.remote_addr != '-']\n"
        "df = df[px.contains(df.service, '')]\n"
        "df = df.groupby(['service', 'timestamp']).agg(\n"
        "    latency_quantiles=('latency', px.quantiles),\n"
        "    error_rate_per_window=('failure', px.mean),\n"
        "    throughput_total=('latency', px.count),\n"
        "    bytes_total=('resp_body_size', px.sum),\n"
        ")\n"
        "df.latency_p50 = px.DurationNanos(px.floor("
        "px.pluck_float64(df.latency_quantiles, 'p50')))\n"
        "df.latency_p90 = px.DurationNanos(px.floor("
        "px.pluck_float64(df.latency_quantiles, 'p90')))\n"
        "df.latency_p99 = px.DurationNanos(px.floor("
        "px.pluck_float64(df.latency_quantiles, 'p99')))\n"
        "df['time_'] = df['timestamp']\n"
        "df.request_throughput = df.throughput_total / window_ns\n"
        "df.bytes_throughput = df.bytes_total / window_ns\n"
        "df.error_rate = df.error_rate_per_window * df.request_throughput"
        " / px.DurationNanos(1)\n"
        "df.k8s = df.service\n"
        "df = df[['time_', 'k8s', 'latency_p50', 'latency_p90', 'latency_p99',"
        " 'error_rate', 'request_throughput', 'bytes_throughput']]\n"
        f"px.display(df, '{OUT}')\n"
    )


def _bits(n: int) -> int:
    return math.ceil(math.log2(n))


def lower_bound_bits(cfg: dict) -> int:
    """Bits per row the aggregation must read, at each column's narrowest
    lossless width: the service, path, client and status as codes over
    their distinct values, the 10 s window over the retained table's
    windows, the body size over its range, and latency as the f64 it is
    (exponential draws are all distinct)."""
    windows = -(-cfg["rows"] * 10**9 // (cfg["events_per_s"] * cfg["window_ns"]))
    return (
        _bits(cfg["services"])
        + _bits(len(path_names(cfg)))
        + _bits(len(addr_names(cfg)))
        + _bits(len(cfg["status_codes"]))
        + _bits(windows)
        + cfg["body_size_bits"]
        + 64
    )


def reference(cfg: dict, cols: dict, precision: str = "exact") -> dict:
    """svc_let's answer over the rows in ``cols`` (with their ``time_``),
    keyed by (service, window start): count, error count, body bytes,
    error rate as format_LET_aggs forms it, and the exact latency order
    statistics of ranks ceil(q * n) - 1 .. + 1. ``precision="low"`` is
    the control: one step down, float32 for the float64 latency and mean."""
    w_ns = cfg["window_ns"]
    names = path_names(cfg)
    probe = np.isin(cols["req_path"], [names.index(p) for p in PROBE_PATHS])
    keep = ~probe & (cols["remote_addr"] != len(addr_names(cfg)) - 1)
    svc = cols["service"][keep]
    lat = cols["latency"][keep]
    if precision == "low":
        lat = lat.astype(np.float32).astype(np.float64)
    window = cols["time_"][keep] // w_ns
    w0 = int(window.min()) if len(window) else 0
    n_svc = cfg["services"]
    g = (window - w0) * n_svc + svc
    n_groups = int(g.max()) + 1 if len(g) else 0
    count = np.bincount(g, minlength=n_groups)
    errors = np.bincount(g, (cols["resp_status"][keep] >= 400).astype(np.float64), n_groups)
    # Exact in float64: every group's sum stays far below 2**53.
    body = np.bincount(g, cols["resp_body_size"][keep], n_groups).astype(np.int64)
    # Latency sorted within each group: by value, then stably by group.
    order = np.argsort(lat)
    key = g[order].astype(np.uint16 if n_groups <= 1 << 16 else np.int64)
    srt = lat[order[np.argsort(key, kind="stable")]]
    start = np.cumsum(count) - count
    present = np.nonzero(count)[0]
    n = count[present]
    if precision == "low":
        mean = (errors[present].astype(np.float32) / n.astype(np.float32)).astype(
            np.float64
        )
    else:
        mean = errors[present] / n
    tput = n / w_ns
    near = {}
    for k, q in QUANTILES:
        r = np.maximum(np.ceil(q * n).astype(np.int64), 1) - 1
        near[k] = np.stack(
            [srt[start[present] + np.clip(r + d, 0, n - 1)] for d in (-1, 0, 1)],
            axis=1,
        )
    svcs = service_names(cfg)
    out = {}
    for i, gi in enumerate(present):
        w, s = divmod(int(gi), n_svc)
        out[svcs[s], (w0 + w) * w_ns] = {
            "count": int(n[i]),
            "bytes": int(body[gi]),
            "error_rate": float(mean[i] * tput[i] / 1),
            "near": {k: near[k][i].tolist() for k, _ in QUANTILES},
            # The order statistic itself: the control's answer.
            "latency": {k: float(near[k][i][1]) for k, _ in QUANTILES},
        }
    return out


def as_reference(rows: dict, cfg: dict) -> dict:
    """The program's output table in the reference's shape: counts and
    body bytes recovered from the rates (exact: both are whole numbers
    far below 2**53 divided by the window once)."""
    w_ns = cfg["window_ns"]
    out = {}
    for i, name in enumerate(rows.get("k8s", [])):
        out[name, int(rows["time_"][i])] = {
            "count": round(float(rows["request_throughput"][i]) * w_ns),
            "bytes": round(float(rows["bytes_throughput"][i]) * w_ns),
            "error_rate": float(rows["error_rate"][i]),
            "latency": {k: float(rows[k][i]) for k, _ in QUANTILES},
        }
    return out


def compare(got: dict, want: dict) -> dict:
    """The numbers ``correct`` is decided on (see the config's limits):
    rows counted in the wrong group or missed, the widest body-byte gap,
    the widest relative error-rate gap, and the widest relative gap of a
    latency quantile from the nearest exact order statistic of ranks
    ceil(q * n) - 1 .. + 1, less the 1 ns that svc_let's px.floor may
    take. A group missing on either side counts all its rows."""
    count_gap = bytes_gap = 0
    rate_gap = q_gap = 0.0
    for key in set(got) | set(want):
        g, w = got.get(key), want.get(key)
        if g is None or w is None:
            count_gap += (g or w)["count"]
            bytes_gap = max(bytes_gap, (g or w)["bytes"], 1)
            rate_gap = q_gap = math.inf
            continue
        count_gap += abs(g["count"] - w["count"])
        bytes_gap = max(bytes_gap, abs(g["bytes"] - w["bytes"]))
        if g["error_rate"] != w["error_rate"]:
            rate_gap = max(
                rate_gap,
                abs(g["error_rate"] - w["error_rate"]) / w["error_rate"]
                if w["error_rate"] > 0
                else math.inf,
            )
        for k, _ in QUANTILES:
            est = g["latency"][k]
            q_gap = max(
                q_gap,
                min(max(abs(est - x) - 1.0, 0.0) / max(x, 1.0) for x in w["near"][k]),
            )
    return {
        "count_gap": count_gap,
        "bytes_gap": bytes_gap,
        "error_rate_rel_gap": rate_gap,
        "quantile_rel_gap": q_gap,
    }
