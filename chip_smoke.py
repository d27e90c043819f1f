"""Smoke of the PxL device path on a TPU chip. A smoke, not a benchmark.

It drives the query engine's device path (``parallel/`` staging and fold,
``ops/`` kernels) through the entry points a user calls:
``Carnot(device_executor=MeshExecutor(mesh))``. It runs bench configs 2, 5,
3 and 8 at the bench's schemas, generated from ``--seed``, once cold and
once warm each, at the bench's block size. These reach the MXU segment
lane, the t-digest/count-min lane, the HLL lane (its scatter form at
config 3's 4096 groups, see PERF.md) and the sort-merge join lane. Every
answer is checked against the host engine (a ``Carnot`` with no device
executor, over the same tables) and against the bench's truth. Every
query must offload, with no fallback, unmatched fragment or breaker trip.
Then one config-2 query goes through the in-process broker path
(MessageBus + BridgeRouter + QueryBroker, a PEM Agent holding the
executor).

    python chip_smoke.py              # one chip: what the driver runs
    python chip_smoke.py --chips 4    # only the four-chip mesh phase

It exits non-zero and prints no result unless JAX's first device is a
TPU: it never falls back to the CPU. The last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Earlier lines carry smoke readings (walls, peak HBM, compile-cache hits).
They are not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import bench

COUNTERS = (
    "device_offload_total",
    "device_offload_unmatched_total",
    "device_offload_fallback_total",
    "device_offload_fallback_breaker_trips_total",
    "device_offload_fallback_breaker_open_total",
    "mesh_degrade_events_total",
)
N_SERVICES = 16


class SmokeFailure(AssertionError):
    pass


def say(phase: str, **fields) -> None:
    print(json.dumps({"smoke": phase, **fields}, default=str), flush=True)


def require_tpu():
    """JAX's devices, or exit: the smoke runs on a TPU or not at all."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (first device is "
            f"{devices[0].platform!r}); the smoke never falls back to it"
        )
    return devices


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--rows-log2",
        type=int,
        default=26,
        help="http_events and conn_flows rows, as a power of two",
    )
    p.add_argument(
        "--join-rows",
        type=int,
        default=4_000_000,
        help="fact-side rows of the config-8 join",
    )
    return p.parse_args(argv)


# ---- checks ---------------------------------------------------------------


def counters() -> dict:
    from pixie_tpu.utils import metrics_registry

    reg = metrics_registry()
    return {c: reg.counter(c).value() for c in COUNTERS}


def run_offloaded(carnot, ex, query: str, out: str):
    """(rows, wall seconds) of one query that must offload cleanly."""
    before = counters()
    t0 = time.perf_counter()
    rows = carnot.execute_query(query).table(out)
    wall = time.perf_counter() - t0
    after = counters()
    delta = {c: after[c] - before[c] for c in COUNTERS}
    errors = {
        "fallback_errors": ex.fallback_errors,
        "stream_fallback_errors": ex.stream_fallback_errors,
        "prewarm_errors": ex.prewarm_errors,
    }
    captured = "\n".join(
        f"--- {kind}: {key}\n{tb}"
        for kind, errs in errors.items()
        for key, tb in errs.items()
    )
    if captured:
        raise SmokeFailure(f"device path failed:\n{captured}")
    if delta["device_offload_total"] < 1:
        raise SmokeFailure(f"query did not offload: {delta}")
    bad = {c: v for c, v in delta.items() if c != "device_offload_total" and v}
    if bad:
        raise SmokeFailure(f"query fell off the device: {bad}")
    return rows, wall


def by_key(rows: dict, keys) -> dict:
    cols = list(rows)
    out = {}
    for i in range(len(rows[keys[0]])):
        k = tuple(rows[c][i] for c in keys)
        if k in out:
            raise SmokeFailure(f"duplicate output key {k}")
        out[k] = {c: rows[c][i] for c in cols}
    return out


def compare(got: dict, want: dict, keys, close=None, what="") -> dict:
    """Same keys and columns, values exact except ``close`` columns, held
    to an absolute tolerance: f64 means (the TPU emulates f64 division)
    and HLL estimates (rounded from an f64 estimate). Returns the count
    of inexact values per ``close`` column."""
    close = close or {}
    inexact = dict.fromkeys(close, 0)
    g, w = by_key(got, keys), by_key(want, keys)
    if set(g) != set(w):
        raise SmokeFailure(
            f"{what}: key sets differ ({len(set(g) ^ set(w))} keys)"
        )
    if set(got) != set(want):
        raise SmokeFailure(f"{what}: columns {set(got)} vs {set(want)}")
    for k, row in g.items():
        for c, v in row.items():
            ref = w[k][c]
            if v == ref:
                continue
            if c in close and abs(v - ref) <= close[c]:
                inexact[c] += 1
                continue
            raise SmokeFailure(f"{what}: {c} at {k}: {v!r} vs {ref!r}")
    return inexact


def quantile_gap(got: dict, want: dict, col: str) -> float:
    """Largest relative p50/p99 gap of a sketch column (reported only)."""
    w = {s: json.loads(q) for s, q in zip(want["service"], want[col])}
    gap = 0.0
    for s, q in zip(got["service"], got[col]):
        for key in ("p50", "p99"):
            ref = w[s][key]
            gap = max(gap, abs(json.loads(q)[key] - ref) / max(ref, 1e-9))
    return gap


MEAN_TOL = {"error_rate": 1e-12}


def check_service_stats(dev, ref, data) -> dict:
    inexact = compare(dev, ref, ("service",), MEAN_TOL, what="config 2")
    bench.verify_service_stats(dev, data["http"], data["services"])
    return {
        "inexact_vs_host": inexact,
        "p50_p99_gap_vs_host": quantile_gap(dev, ref, "latency"),
    }


def check_sketches(dev, ref, data) -> dict:
    """Count-min sketches are integer counts: equal to the host's, and
    their total is the exact group count. t-digest p50/p99 within 4% of
    the independent numpy histogram, as the bench holds config 2."""
    h, services = data["http"], data["services"]
    want = by_key(ref, ("service",))
    for i, name in enumerate(dev["service"]):
        if dev["freq"][i] != want[(name,)]["freq"]:
            raise SmokeFailure(f"config 5: count-min differs at {name}")
    max_freq = data["http_status_max"]
    for i, name in enumerate(dev["service"]):
        j = list(services).index(name)
        cm = json.loads(dev["freq"][i])
        if cm["total"] != h["true_count"][j]:
            raise SmokeFailure(f"config 5: count-min total at {name}")
        if not max_freq[j] <= cm["max_est"] <= cm["total"]:
            raise SmokeFailure(f"config 5: count-min max_est at {name}")
        q = json.loads(dev["lat"][i])
        for key, qq in (("p50", 0.50), ("p99", 0.99)):
            truth = bench.truth_quantile(h["true_hist"][j], qq)
            if abs(q[key] - truth) > 0.04 * truth:
                raise SmokeFailure(
                    f"config 5: t-digest {key} at {name}: {q[key]} vs "
                    f"truth {truth}"
                )
    return {"p50_p99_gap_vs_host": quantile_gap(dev, ref, "lat")}


def check_net_flow(dev, ref, data) -> dict:
    """Sums exact against the host and numpy; HLL estimates within 1 of
    the host's and within 10% (or 3, for tiny groups) of the exact
    distinct count (2048 registers: 2.3% standard error, so 10% is over
    4 sigma across 4096 groups)."""
    inexact = compare(
        dev, ref, ("src", "dst"), {"ports": 1}, what="config 3"
    )
    truth = data["flows_truth"]
    for i in range(len(dev["src"])):
        g = int(dev["src"][i].rsplit("-", 1)[1]) * bench.N_HOSTS + int(
            dev["dst"][i].rsplit("-", 1)[1]
        )
        for col in ("bytes_sent", "bytes_recv", "ports"):
            want = truth[col][g]
            got = dev[col][i]
            if col == "ports":
                ok = abs(got - want) <= max(0.10 * want, 3)
            else:
                ok = got == want
            if not ok:
                raise SmokeFailure(
                    f"config 3: {col} at group {g}: {got} vs {want}"
                )
    return {"groups": len(dev["src"]), "inexact_vs_host": inexact}


def check_join(dev, ref, data) -> dict:
    bench.verify_join(dev, data["join_rows"])
    compare(dev, ref, ("time_",), what="config 8")
    return {"rows": len(dev["time_"])}


QUERIES = (
    ("2", bench.QUERY_SERVICE_STATS, "service_stats", check_service_stats),
    ("5", bench.QUERY_SKETCHES, "sketches", check_sketches),
    ("3", bench.QUERY_NET_FLOW, "flows", check_net_flow),
    ("8", bench.QUERY_JOIN, "joined", check_join),
)


# ---- data -------------------------------------------------------------------


def load_http(store, args) -> dict:
    n = 1 << args.rows_log2
    services = bench.service_names(N_SERVICES)
    t0 = time.perf_counter()
    d = bench.gen_http_events(n, N_SERVICES, seed=args.seed)
    t1 = time.perf_counter()
    bench.load_http_events(store.create_table, d, services)
    say(
        "data",
        table="http_events",
        rows=n,
        generate_s=t1 - t0,
        load_s=time.perf_counter() - t1,
    )
    return {"http": d, "services": services}


def load_all(store, args) -> dict:
    data = load_http(store, args)
    h = data["http"]
    # Per-service count of each status: the count-min floor.
    per_status = [
        np.bincount(h["svc_idx"][h["status"] == s], minlength=N_SERVICES)
        for s in np.unique(h["status"])
    ]
    data["http_status_max"] = np.max(per_status, axis=0)

    n = 1 << args.rows_log2
    t0 = time.perf_counter()
    f = bench.gen_conn_flows(n, seed=args.seed + 1)
    t1 = time.perf_counter()
    bench.load_conn_flows(store.create_table, f)
    t2 = time.perf_counter()
    g = f["src"].astype(np.int64) * bench.N_HOSTS + f["dst"]
    n_groups = bench.N_HOSTS * bench.N_HOSTS
    seen = np.zeros(n_groups * 65536, bool)
    seen[g * 65536 + f["port"]] = True
    data["flows_truth"] = {
        "bytes_sent": np.bincount(g, f["bs"], n_groups).astype(np.int64),
        "bytes_recv": np.bincount(g, f["br"], n_groups).astype(np.int64),
        "ports": seen.reshape(n_groups, 65536).sum(axis=1),
    }
    say(
        "data",
        table="conn_flows",
        rows=n,
        generate_s=t1 - t0,
        load_s=t2 - t1,
        truth_s=time.perf_counter() - t2,
    )

    t0 = time.perf_counter()
    j = bench.gen_join_fact(args.join_rows, N_SERVICES, seed=args.seed + 2)
    t1 = time.perf_counter()
    bench.load_join_tables(store.create_table, j, data["services"])
    data["join_rows"] = args.join_rows
    say(
        "data",
        table="join_fact",
        rows=args.join_rows,
        generate_s=t1 - t0,
        load_s=time.perf_counter() - t1,
    )
    return data


# ---- phases -----------------------------------------------------------------


def one_chip_phase(devices, args) -> None:
    from jax.sharding import Mesh

    from pixie_tpu.engine import Carnot
    from pixie_tpu.ops import segment
    from pixie_tpu.parallel import MeshExecutor
    from pixie_tpu.parallel.staging import reset_cold_profile

    ex = MeshExecutor(
        mesh=Mesh(np.array(devices[:1]), ("d",)), block_rows=bench.BLOCK_ROWS
    )
    dev = Carnot(device_executor=ex)
    host = Carnot(table_store=dev.table_store)
    data = load_all(dev.table_store, args)
    direct = {}
    for name, query, out, check in QUERIES:
        segment.reduce_lanes(reset=True)
        reset_cold_profile()
        cold, cold_s = run_offloaded(dev, ex, query, out)
        profile = {k: round(v, 3) for k, v in reset_cold_profile().items()}
        warm, warm_s = run_offloaded(dev, ex, query, out)
        lanes = segment.reduce_lanes(reset=True)
        t0 = time.perf_counter()
        ref = host.execute_query(query).table(out)
        host_s = time.perf_counter() - t0
        info = check(cold, ref, data)
        check(warm, ref, data)
        say(
            f"config {name}",
            cold_s=cold_s,
            warm_s=warm_s,
            host_engine_s=host_s,
            offloaded=True,
            fallbacks=0,
            matches_host=True,
            lanes=lanes,
            cold_profile=profile,
            **info,
        )
        direct[name] = warm
    broker_phase(ex, dev.table_store, direct["2"])


def broker_phase(ex, store, direct: dict) -> None:
    """Config 2 through the in-process broker: MessageBus + BridgeRouter +
    QueryBroker, a PEM Agent holding the executor and a Kelvin."""
    from pixie_tpu.exec.router import BridgeRouter
    from pixie_tpu.vizier import Agent, MessageBus, QueryBroker

    bus = MessageBus()
    router = BridgeRouter()
    broker = QueryBroker(
        bus,
        router,
        table_relations={"http_events": store.get_relation("http_events")},
    )
    agents = [
        Agent("pem", bus, router, table_store=store, device_executor=ex),
        Agent("kelvin", bus, router, is_kelvin=True),
    ]
    for a in agents:
        a.start()
    try:
        deadline = time.monotonic() + 60
        while len(broker.tracker.agents_snapshot()) < len(agents):
            if time.monotonic() > deadline:
                raise SmokeFailure("agents never registered with the broker")
            time.sleep(0.05)
        before = counters()["device_offload_total"]
        t0 = time.perf_counter()
        res = broker.execute_script(bench.QUERY_SERVICE_STATS, timeout_s=900)
        wall = time.perf_counter() - t0
        if counters()["device_offload_total"] <= before:
            raise SmokeFailure("broker query did not offload on the PEM")
        inexact = compare(
            res.table("service_stats"),
            direct,
            ("service",),
            MEAN_TOL,
            what="broker config 2",
        )
        say(
            "broker config 2",
            wall_s=wall,
            matches_direct=True,
            inexact_vs_direct=inexact,
        )
    finally:
        broker.stop()
        for a in agents:
            a.stop()


def four_chip_phase(devices, args) -> None:
    """Configs 2 and 3 on d:4 and hosts:2,d:2 meshes, each bit-identical
    to a one-chip executor's answer in this process."""
    from jax.sharding import Mesh

    from pixie_tpu.distributed.mesh import MeshConfig
    from pixie_tpu.engine import Carnot
    from pixie_tpu.parallel import MeshExecutor
    from pixie_tpu.table import TableStore

    if len(devices) < 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, have {len(devices)}")
    store = TableStore()
    meshes = {"1 chip": Mesh(np.array(devices[:1]), ("d",))}
    for spec in ("d:4", "hosts:2,d:2"):
        meshes[spec] = MeshConfig.parse(spec, 4).build(devices[:4])
    executors = {
        k: MeshExecutor(mesh=m, block_rows=bench.BLOCK_ROWS)
        for k, m in meshes.items()
    }
    engines = {
        k: Carnot(table_store=store, device_executor=ex)
        for k, ex in executors.items()
    }
    data = load_http(store, args)
    flows = bench.gen_conn_flows(1 << args.rows_log2, seed=args.seed + 1)
    bench.load_conn_flows(store.create_table, flows)
    degrades = counters()["mesh_degrade_events_total"]
    meshed = ("d:4", "hosts:2,d:2")
    for name, query, out, keys in (
        ("2", bench.QUERY_SERVICE_STATS, "service_stats", ("service",)),
        ("3", bench.QUERY_NET_FLOW, "flows", ("src", "dst")),
    ):
        results = {}
        for geom, carnot in engines.items():
            ex = executors[geom]
            results[geom], cold_s = run_offloaded(carnot, ex, query, out)
            _, warm_s = run_offloaded(carnot, ex, query, out)
            say(f"config {name}", geometry=geom, cold_s=cold_s, warm_s=warm_s)
        if name == "2":
            bench.verify_service_stats(
                results["1 chip"], data["http"], data["services"]
            )
        for geom in meshed:
            compare(
                results[geom],
                results["1 chip"],
                keys,
                what=f"{geom} config {name}",
            )
        say(f"config {name}", bit_identical_to_one_chip=list(meshed))
    for geom in meshed:
        staged = executors[geom]._staged_cache.values()
        if not staged:
            raise SmokeFailure(f"{geom}: nothing staged")
        for st in staged:
            for col, arr in st.blocks.items():
                held = {s.device for s in arr.addressable_shards}
                if len(held) != 4:
                    raise SmokeFailure(
                        f"{geom}: {col} shards on {len(held)} devices"
                    )
        say("staging", geometry=geom, entries=len(staged), shard_devices=4)
    if counters()["mesh_degrade_events_total"] != degrades:
        raise SmokeFailure("mesh degrade events during the four-chip phase")
    say("mesh", degrade_events=0)


def main(argv=None) -> int:
    args = parse_args(argv)
    t_start = time.perf_counter()
    # Before JAX: the native build is a child process (g++), and no child
    # may start once JAX holds the chip.
    native = bench.build_native_runtime()
    devices = require_tpu()
    import jax

    from pixie_tpu.table import column
    from pixie_tpu.utils import compile_cache

    cache = {"hits": 0, "misses": 0}

    def on_event(event, *a, **k):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    cache_dir = compile_cache.enable()
    say(
        "start",
        note="smoke readings, not a benchmark",
        platform=devices[0].platform,
        device_kind=devices[0].device_kind,
        devices=len(devices),
        native_encoder_loaded=column._native is not None,
        native_build=native,
        compile_cache_dir=cache_dir,
    )
    if args.chips == 4:
        four_chip_phase(devices, args)
    else:
        one_chip_phase(devices, args)
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in devices[: args.chips]
    ]
    say(
        "end",
        wall_s=time.perf_counter() - t_start,
        peak_hbm_bytes=peaks if None not in peaks else "not reported",
        compile_cache_hits=cache["hits"],
        compile_cache_misses=cache["misses"],
    )
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
