"""conn_stats: the table px/net_flow_graph reads, its generator, its query
and its plain reference.

One row is one report of a connection aggregate's cumulative counters, as
Stirling's socket tracer writes conn_stats: an aggregate is one (process,
remote address, trace role), and every aggregate reports once a second.
Row ``i`` is aggregate ``i % A`` at report ``i // A``, where ``A`` is the
node's number of aggregates, so row order is time order. The counters
start per aggregate above 2**32 and grow by a uniform draw a report.

The query is the vendored script's ``net_flow_graph`` body with the
dashboard's defaults (see ``query``). The reference is plain numpy over
the generated arrays: the filters, per-connection min/max by sort and
reduceat, the loopback rule with nslookup as identity, int64 (from, to)
sums, the whole-table time window and the rates divided once in float64.
It imports nothing of the program.
"""

from __future__ import annotations

import math
import re

import numpy as np

TABLE = "conn_stats"
OUT = "net_flow"
CLIENT = 1  # trace_role of a client-side aggregate (the script keeps these)
LOOPBACK = r"127\.0\.0\.[0-9]+"  # the script's localhost_ip_regexp
QUANTITIES = ("bytes_sent", "bytes_recv", "bytes_total")
_CHUNK = 1 << 22


def namespace_names(cfg: dict) -> list[str]:
    return list(cfg["namespaces"])


def _pod_namespace(cfg: dict) -> np.ndarray:
    """Namespace index of each pod: the queried namespace's pods first,
    the rest dealt round the other namespaces."""
    q, n = cfg["queried_pods"], cfg["pods"]
    others = len(cfg["namespaces"]) - 1
    return np.concatenate(
        [np.zeros(q, np.int32), 1 + np.arange(n - q, dtype=np.int32) % others]
    )


def pod_names(cfg: dict) -> list[str]:
    ns = namespace_names(cfg)
    return [f"{ns[k]}/pod-{p}" for p, k in enumerate(_pod_namespace(cfg))]


def upid_names(cfg: dict) -> list[str]:
    """Pixie's upid text (asid:pid:start time), one per process."""
    procs = cfg["pods"] * cfg["upids_per_pod"]
    return [f"1:{4000 + i}:{1000003 * (i + 1)}" for i in range(procs)]


def _loopbacks(cfg: dict) -> int:
    return round(cfg["remote_pool"] * cfg["loopback_share"])


def addr_names(cfg: dict) -> list[str]:
    """The pool of remote addresses: the loopback ones first."""
    lo = _loopbacks(cfg)
    rest = cfg["remote_pool"] - lo
    return [f"127.0.0.{i + 1}" for i in range(lo)] + [
        f"10.{i >> 16}.{(i >> 8) & 255}.{i & 255}" for i in range(rest)
    ]


def aggregates(cfg: dict) -> int:
    return (
        cfg["pods"]
        * cfg["upids_per_pod"]
        * cfg["remotes_per_process"]
        * len(cfg["trace_roles"])
    )


def generate(cfg: dict, n: int, rng: np.random.Generator) -> dict:
    """``n`` rows of the config's columns (no time column: row order is
    time order, and the timeline gives each row's timestamp)."""
    per_proc = cfg["remotes_per_process"]
    roles = np.asarray(cfg["trace_roles"], np.int64)
    procs = cfg["pods"] * cfg["upids_per_pod"]
    a = aggregates(cfg)
    # Aggregate j: process j // (R * roles), remote slot, role j % roles.
    proc = np.arange(a) // (per_proc * len(roles))
    slot = (np.arange(a) // len(roles)) % per_proc
    # Each process's remotes: distinct draws from the pool.
    remotes = np.argsort(rng.random((procs, cfg["remote_pool"])), axis=1)[:, :per_proc]
    agg_cols = {
        "upid": proc.astype(np.int32),
        "pod": (proc // cfg["upids_per_pod"]).astype(np.int32),
        "namespace": _pod_namespace(cfg)[proc // cfg["upids_per_pod"]],
        "remote_addr": remotes[proc, slot].astype(np.int32),
        "trace_role": roles[np.arange(a) % len(roles)],
    }
    lo_bits, hi_bits = cfg["counter_start_bits"]
    counters = {
        c: rng.integers(1 << lo_bits, 1 << hi_bits, a, dtype=np.int64)
        for c in ("bytes_sent", "bytes_recv")
    }
    out = {c: np.resize(v, n) for c, v in agg_cols.items()}
    step = 1 << cfg["counter_step_bits"]
    for c, start in counters.items():
        col = np.empty(n, np.int64)
        run = start.copy()
        # A chunk of whole reports at a time: cumulative sums down each
        # aggregate's reports, carried from one chunk to the next.
        reports = max(_CHUNK // a, 1)
        for off in range(0, n, reports * a):
            m = min(reports * a, n - off)
            k = -(-m // a)
            inc = rng.integers(0, step, (k, a), dtype=np.int64)
            inc[0] += run
            np.cumsum(inc, axis=0, out=inc)
            run = inc[-1]
            col[off : off + m] = inc.reshape(-1)[:m]
        out[c] = col
    return out


def relation():
    from pixie_tpu.types import DataType, Relation, SemanticType

    S, I = DataType.STRING, DataType.INT64
    return Relation.of(
        ("time_", DataType.TIME64NS, SemanticType.ST_TIME_NS),
        ("upid", S, SemanticType.ST_UPID),
        ("pod", S, SemanticType.ST_POD_NAME),
        ("namespace", S, SemanticType.ST_NAMESPACE_NAME),
        ("remote_addr", S, SemanticType.ST_IP_ADDRESS),
        ("trace_role", I),
        ("bytes_sent", I, SemanticType.ST_BYTES),
        ("bytes_recv", I, SemanticType.ST_BYTES),
    )


_DICTS = {
    "upid": upid_names,
    "pod": pod_names,
    "namespace": namespace_names,
    "remote_addr": addr_names,
}


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
    for col in ("trace_role", "bytes_sent", "bytes_recv"):
        out[col] = cols[col][lo:hi]
    return out


def query(cfg: dict, start_ns: int, end_ns: int) -> str:
    """The body of the vendored px/net_flow_graph script
    (pixie_tpu/scripts/px/net_flow_graph/net_flow_graph.pxl), verbatim
    but for: ``df.ctx['namespace']`` and ``df.ctx['pod']`` read stored
    columns, ``start_time`` is [start_ns, end_ns] (both ends inclusive),
    ``ns`` is the queried namespace, the entity filters are '' and the
    throughput filter 0.0 (the view's defaults), and the result is
    displayed as OUT where the function returns it."""
    ns = cfg["namespaces"][0]
    return f"""\
df = px.DataFrame('conn_stats', start_time={start_ns}, end_time={end_ns})

# Filter on namespace.
df = df[df.namespace == '{ns}']

# Filter for client side requests.
df = df[df.trace_role == 1]

# Store the pod. Ideally this would be done after the aggregate,
# but that's not working right now.
df.pod = df.pod

# Filter out any non k8s sources.
df = df[df.pod != '']

# Find the time window
time_window = df.agg(
    time_min=('time_', px.min),
    time_max=('time_', px.max),
)
time_window.time_delta = px.DurationNanos(time_window.time_max - time_window.time_min)
time_window = time_window.drop(['time_min', 'time_max'])

# Use aggregate to pick the first and last sample for any given client-server pair.
# We do this by picking the min/max of the stats, since they are all counters.
df = df.groupby(['pod', 'upid', 'remote_addr']).agg(
    bytes_sent_min=('bytes_sent', px.min),
    bytes_sent_max=('bytes_sent', px.max),
    bytes_recv_min=('bytes_recv', px.min),
    bytes_recv_max=('bytes_recv', px.max),
)
df.bytes_sent = df.bytes_sent_max - df.bytes_sent_min
df.bytes_recv = df.bytes_recv_max - df.bytes_recv_min
df.bytes_total = df.bytes_sent + df.bytes_recv
df = df.drop(['bytes_sent_max', 'bytes_sent_min', 'bytes_recv_max', 'bytes_recv_min'])

# To create a graph, add 'from' and 'to' entities.
df.from_entity = df.pod

# TODO(yzhao): Handle IPv6 ::1 as well.
localhost_ip_regexp = r'127\\.0\\.0\\.[0-9]+'
df.is_remote_addr_localhost = px.regex_match(localhost_ip_regexp, df.remote_addr)
df.to_entity = px.select(df.is_remote_addr_localhost,
                         df.pod,
                         px.nslookup(df.remote_addr))

# Filter out entities as specified by the user.
df = df[px.contains(df.from_entity, '')]
df = df[px.contains(df.to_entity, '')]

# Since there may be multiple processes per pod,
# perform an additional aggregation to consolidate those into one entry.
df = df.groupby(['from_entity', 'to_entity']).agg(
    bytes_sent=('bytes_sent', px.sum),
    bytes_recv=('bytes_recv', px.sum),
    bytes_total=('bytes_total', px.sum),
)

# Add time_delta to every row. Use a join to do this.
# Future syntax will support: df.time_delta = time_window.at[0, 'time_delta']
df.join_key = 1
time_window.join_key = 1
df = df.merge(time_window, how='inner', left_on='join_key', right_on='join_key')
df = df.drop(['join_key_x', 'join_key_y'])

# Compute as rates.
df.bytes_sent = df.bytes_sent / df.time_delta
df.bytes_recv = df.bytes_recv / df.time_delta
df.bytes_total = df.bytes_total / df.time_delta
df = df.drop(['time_delta'])

# Apply rate filter.
df = df[df.bytes_total > 0.0 / 1000000000]

px.display(df, '{OUT}')
"""


def _bits(n: int) -> int:
    return max(math.ceil(math.log2(n)), 1)


def lower_bound_bits(cfg: dict) -> int:
    """Bits per row the query must read, at each column's narrowest
    lossless width over the generated table: namespace, trace role, pod,
    upid and remote address as codes over their distinct values, time_
    over the retained span in ns, and each counter over its range (its
    start's range plus the most it grows over the table's reports)."""
    span_ns = cfg["rows"] * 10**9 // cfg["events_per_s"]
    reports = -(-cfg["rows"] // aggregates(cfg))
    lo_bits, hi_bits = cfg["counter_start_bits"]
    counter = (1 << hi_bits) - (1 << lo_bits) + reports * (1 << cfg["counter_step_bits"])
    return (
        _bits(len(cfg["namespaces"]))
        + _bits(len(cfg["trace_roles"]))
        + _bits(cfg["pods"])
        + _bits(cfg["pods"] * cfg["upids_per_pod"])
        + _bits(cfg["remote_pool"])
        + _bits(span_ns)
        + 2 * _bits(counter)
    )


def reference(cfg: dict, cols: dict, precision: str = "exact") -> dict:
    """net_flow_graph's answer over the rows in ``cols`` (with their
    ``time_``), keyed by (from_entity, to_entity): the exact int64 byte
    sums, the exact time_delta, and the three rates, each sum over
    time_delta divided once in float64. ``precision="low"`` is the
    control: one step down, the counters, sums and rates in float32."""
    keep = (cols["namespace"] == 0) & (cols["trace_role"] == CLIENT)
    pods = np.asarray(pod_names(cfg), dtype=object)
    keep &= (pods != "")[cols["pod"]]
    if not keep.any():
        return {}
    t = cols["time_"][keep]
    delta_t = int(t.max()) - int(t.min())
    pod, upid, addr = (cols[c][keep] for c in ("pod", "upid", "remote_addr"))
    order = np.lexsort((addr, upid, pod))
    pod, upid, addr = pod[order], upid[order], addr[order]
    new = np.ones(len(pod), bool)
    new[1:] = (pod[1:] != pod[:-1]) | (upid[1:] != upid[:-1]) | (addr[1:] != addr[:-1])
    starts = np.flatnonzero(new)
    deltas = []
    for c in ("bytes_sent", "bytes_recv"):
        v = cols[c][keep][order]
        if precision == "low":
            v = v.astype(np.float32)
        deltas.append(np.maximum.reduceat(v, starts) - np.minimum.reduceat(v, starts))
    deltas.append(deltas[0] + deltas[1])
    # to_entity: the pod itself for a loopback address, else nslookup's
    # answer, which is the address itself with no metadata to resolve it.
    addrs = addr_names(cfg)
    loop = np.array([re.fullmatch(LOOPBACK, a) is not None for a in addrs])
    g_pod, g_addr = pod[starts], addr[starts]
    to_key = np.where(loop[g_addr], -1 - g_pod, g_addr)
    order2 = np.lexsort((to_key, g_pod))
    g_pod, to_key = g_pod[order2], to_key[order2]
    new2 = np.ones(len(g_pod), bool)
    new2[1:] = (g_pod[1:] != g_pod[:-1]) | (to_key[1:] != to_key[:-1])
    starts2 = np.flatnonzero(new2)
    sums = [np.add.reduceat(d[order2], starts2) for d in deltas]
    if precision == "low":
        rates = [(s / np.float32(delta_t)).astype(np.float64) for s in sums]
    else:
        rates = [s / float(delta_t) for s in sums]
    out = {}
    for i, j in enumerate(starts2):
        p, k = int(g_pod[j]), int(to_key[j])
        if not rates[2][i] > 0.0:  # the script's throughput filter
            continue
        to = pods[p] if k < 0 else addrs[k]
        out[pods[p], to] = {
            "sums": [int(s[i]) for s in sums],
            "rates": [float(r[i]) for r in rates],
            "time_delta": delta_t,
        }
    return out


def as_reference(rows: dict, cfg: dict) -> dict:
    """The program's output table in the reference's shape: the rates of
    each (from_entity, to_entity)."""
    out = {}
    for i, (a, b) in enumerate(zip(rows.get("from_entity", []), rows.get("to_entity", []))):
        out[a, b] = {"rates": [float(rows[q][i]) for q in QUANTITIES]}
    return out


def compare(got: dict, want: dict) -> dict:
    """The numbers ``correct`` is decided on (see the config's limits):
    (from, to) rows missing on either side, the widest gap of a byte sum
    recovered from its rate as round(rate * time_delta), and the widest
    gap of time_delta recovered from the total's rate as
    round(sum / rate)."""
    rows_gap = len(set(got) ^ set(want))
    bytes_gap = time_gap = 0
    for key in set(got) & set(want):
        g, w = got[key]["rates"], want[key]
        t = w["time_delta"]
        for rate, s in zip(g, w["sums"]):
            gap = abs(round(rate * t) - s) if math.isfinite(rate) else math.inf
            bytes_gap = max(bytes_gap, gap)
        total = g[2]
        time_gap = max(
            time_gap,
            abs(round(w["sums"][2] / total) - t)
            if math.isfinite(total) and total > 0
            else math.inf,
        )
    return {"bytes_gap": bytes_gap, "rows_gap": rows_gap, "time_delta_gap": time_gap}
