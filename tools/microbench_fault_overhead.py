"""Fault-injection + acked-transport overhead microbench (r9/r10 gates).

Proves the disabled injection sites cost <1% on (a) the warm device agg
path and (b) the transport round-trip, and (r10) that the ack-window
bookkeeping costs <1% when DISABLED (``transport_ack_window=0``). Method:

1. ``per_check_ns`` — cost of the call-site idiom with nothing armed
   (``faults.ACTIVE and faults.fires(site)``: one attribute load + branch)
   and with a foreign site armed (dict lookup under the registry lock, the
   worst case a production query sees while an operator injects elsewhere).
2. Site census — every shipped site armed at ``p=0`` (counts checks,
   never fires) while one warm query / one transport round-trip runs, so
   checks-per-operation is measured, not guessed.
3. ``overhead_pct = checks_per_op * per_check_ns / op_ns * 100`` for both
   paths, plus a direct A/B of the warm query with the registry idle vs a
   foreign site armed.
4. Acked-vs-disabled transport comparison (r10): RTT and one-way
   windowed throughput with the default ack window vs
   ``transport_ack_window=0``; the modeled <1% disabled gate re-runs on
   the window-disabled plane (that configuration IS the r9-equivalent
   hot path plus the ack bookkeeping branches).

Also gates (r14) the durability spill hooks: <1% modeled on the acked
RTT with durability DISABLED (bare ``wal is None`` branches; the warm
query path has zero durability hooks), and reports the enabled cost per
``wal_fsync`` policy ('always' fsyncs every windowed frame; 'never'
rides the page cache — crash-safe, not powerloss-safe).

Also gates (r15) the resource-attribution hooks: <1% modeled on the
warm fold with attribution DISABLED (bare ``ACTIVE`` branches at the
dispatch recorders, attribution contexts, and residency usage sampling;
the transport path has zero attribution hooks).

Also gates (r20) the materialized-view probe: <1% modeled on the warm
broker query for a script NO view serves — with a live registry and a
registered decoy view, the non-view path pays one flag check plus a
probe-cache lookup resolving to a cached miss entry.

Also gates (r23) the mesh recovery plane: <1% modeled on the warm fold
at the default single-axis geometry, where every sharded dispatch pays
exactly one axis-count branch in _mesh_dispatch (no fault-site probes,
no watchdog, no collective lock), censused by counting dispatches
through one warm query.

Also gates (r24) the ingest-robustness hooks: <1% modeled on the
per-event legacy capture pipe with ``ingest_robustness`` DISABLED —
every event pays only bare branches (the connector's cached
``self._robust`` check and the stream buffer's ledger-is-None guards);
no budget, ledger, or quarantine bookkeeping exists on that path.
Enabled cost reported as a replay A/B.

Prints ONE JSON line on stdout. With MB_WRITE_BENCH_DETAIL=1, merges the
headline numbers into BENCH_DETAIL.json under the ``fault_overhead``,
``ack_overhead``, ``trace_overhead``, ``durability_overhead``,
``profiler_overhead`` and ``ingest_overhead`` keys.

Env knobs: MB_ROWS (default 200k), MB_WARM_RUNS (default 20),
MB_RTT_MSGS (default 400), MB_THRPT_MSGS (default 2000), JAX_PLATFORMS.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Shipped sites (keep in sync with `grep -r "faults.fires\|faults.check"`).
SITES = (
    "transport.send",
    "transport.send_data",
    "transport.recv_dup",
    "transport.handshake",
    "transport.ack_drop",
    "transport.replay_dup",
    "transport.conn_kill_midflight",
    "transport.crash_restart",
    "agent.heartbeat",
    "agent.execute",
    "agent.execute_hang",
    "broker.forward",
    "datastore.append",
    "staging.pack",
    "pipeline.fold",
    "wal.torn_write",
    "resident.spill_corrupt",
    "serving.admission_reject",
    "serving.evict_pinned_attempt",
    "agent.kill_holding_fragment",
    "resident.replica_lag",
    "hedge.both_complete",
    "ingest.parse_error",
    "ingest.push_stall",
    "ingest.event_flood",
    "ingest.tracker_leak",
)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _per_check_ns(iters: int = 1_000_000) -> tuple[float, float]:
    """(disabled_ns, armed_elsewhere_ns) per call-site check."""
    from pixie_tpu.utils import faults

    faults.reset()

    def loop(n):
        t0 = time.perf_counter_ns()
        for _ in range(n):
            if faults.ACTIVE and faults.fires("mb.never"):
                raise AssertionError
        return (time.perf_counter_ns() - t0) / n

    disabled = loop(iters)
    faults.arm("mb.other", p=0.0)  # foreign site armed: ACTIVE gate passes
    armed = loop(iters)
    faults.reset()
    return disabled, armed


def main() -> None:
    n_rows = int(os.environ.get("MB_ROWS", 200_000))
    warm_runs = int(os.environ.get("MB_WARM_RUNS", 20))
    rtt_msgs = int(os.environ.get("MB_RTT_MSGS", 400))

    import jax
    from jax.sharding import Mesh

    from pixie_tpu.engine import Carnot
    from pixie_tpu.exec import BridgeRouter
    from pixie_tpu.parallel import MeshExecutor
    from pixie_tpu.types import DataType, Relation
    from pixie_tpu.utils import faults
    from pixie_tpu.vizier.bus import MessageBus
    from pixie_tpu.vizier.transport import BusTransportServer, RemoteBus

    disabled_ns, armed_ns = _per_check_ns()
    log(f"per-check: disabled {disabled_ns:.1f}ns, foreign-armed {armed_ns:.1f}ns")

    # -- warm device agg path ------------------------------------------------
    F, I, S, T = (
        DataType.FLOAT64,
        DataType.INT64,
        DataType.STRING,
        DataType.TIME64NS,
    )
    rel = Relation.of(("time_", T), ("service", S), ("latency", F))
    mesh = Mesh(np.array(jax.devices()), ("d",))
    dev = MeshExecutor(mesh=mesh)
    c = Carnot(device_executor=dev)
    t = c.table_store.create_table("http_events", rel)
    rng = np.random.default_rng(3)
    t.write_pydict(
        {
            "time_": np.arange(n_rows),
            "service": rng.choice(["a", "b", "c", "d"], n_rows).astype(object),
            "latency": rng.exponential(10.0, n_rows),
        }
    )
    t.compact()
    t.stop()
    query = (
        "df = px.DataFrame(table='http_events')\n"
        "s = df.groupby(['service']).agg(\n"
        "    total=('latency', px.sum), n=('latency', px.count))\n"
        "px.display(s, 'out')\n"
    )

    def run_warm(k):
        times = []
        for _ in range(k):
            t0 = time.perf_counter_ns()
            c.execute_query(query)
            times.append(time.perf_counter_ns() - t0)
        return float(np.median(times))

    c.execute_query(query)  # cold: stage + compile
    run_warm(3)
    faults.reset()
    warm_idle_ns = run_warm(warm_runs)
    faults.arm("mb.other", p=0.0)
    warm_armed_ns = run_warm(warm_runs)
    # Census: every shipped site armed at p=0 counts checks without firing.
    faults.reset()
    for s in SITES:
        faults.arm(s, p=0.0)
    c.execute_query(query)
    warm_checks = sum(ck for ck, _ in faults.stats().values())
    faults.reset()
    warm_overhead_pct = 100.0 * warm_checks * armed_ns / warm_idle_ns
    warm_ab_pct = 100.0 * (warm_armed_ns - warm_idle_ns) / warm_idle_ns
    log(
        f"warm agg: {warm_idle_ns/1e6:.2f}ms, {warm_checks} site checks "
        f"-> {warm_overhead_pct:.4f}% modeled, {warm_ab_pct:+.2f}% A/B"
    )

    # -- transport round-trip ------------------------------------------------
    bus = MessageBus()
    router = BridgeRouter()
    server = BusTransportServer(bus, router)
    rbus = RemoteBus(server.address)
    sub = bus.subscribe("mb/topic")

    def rtt(k):
        t0 = time.perf_counter_ns()
        for i in range(k):
            rbus.publish("mb/topic", {"i": i})
            got = sub.get(timeout=5.0)
            assert got is not None
        return (time.perf_counter_ns() - t0) / k

    rtt(50)  # warm
    faults.reset()
    rtt_idle_ns = rtt(rtt_msgs)  # default window: the acked transport
    for s in SITES:
        faults.arm(s, p=0.0)
    rtt(rtt_msgs)
    stats = faults.stats()
    rtt_checks = sum(ck for ck, _ in stats.values()) / rtt_msgs
    faults.reset()
    rtt_overhead_pct = 100.0 * rtt_checks * armed_ns / rtt_idle_ns
    log(
        f"transport rtt (acked): {rtt_idle_ns/1e3:.1f}us, "
        f"{rtt_checks:.2f} checks/rt -> {rtt_overhead_pct:.4f}%"
    )

    # -- acked vs disabled ack window (r10) ----------------------------------
    from pixie_tpu.utils import flags

    thrpt_msgs = int(os.environ.get("MB_THRPT_MSGS", 2000))

    def throughput(rb, topic, sub, n):
        t0 = time.perf_counter_ns()
        for i in range(n):
            rb.publish(topic, {"i": i})
        got = 0
        while got < n:
            if sub.get(timeout=10.0) is None:
                break
            got += 1
        assert got == n, f"throughput run lost messages ({got}/{n})"
        return n / ((time.perf_counter_ns() - t0) / 1e9)

    thr_sub = bus.subscribe("mb/thr")
    throughput(rbus, "mb/thr", thr_sub, 200)  # warm
    thrpt_ack = throughput(rbus, "mb/thr", thr_sub, thrpt_msgs)
    rbus.close()

    saved_window = flags.get("transport_ack_window")
    flags.set("transport_ack_window", 0)  # disables all ack bookkeeping
    try:
        rbus0 = RemoteBus(server.address)
        sub0 = bus.subscribe("mb/noack")

        def rtt0(k):
            t0 = time.perf_counter_ns()
            for i in range(k):
                rbus0.publish("mb/noack", {"i": i})
                got = sub0.get(timeout=5.0)
                assert got is not None
            return (time.perf_counter_ns() - t0) / k

        rtt0(50)
        faults.reset()
        rtt_noack_ns = rtt0(rtt_msgs)
        for s in SITES:
            faults.arm(s, p=0.0)
        rtt0(rtt_msgs)
        noack_checks = sum(
            ck for ck, _ in faults.stats().values()
        ) / rtt_msgs
        faults.reset()
        noack_overhead_pct = 100.0 * noack_checks * armed_ns / rtt_noack_ns
        thr_sub0 = bus.subscribe("mb/thr0")
        throughput(rbus0, "mb/thr0", thr_sub0, 200)  # warm
        thrpt_noack = throughput(rbus0, "mb/thr0", thr_sub0, thrpt_msgs)
        rbus0.close()
    finally:
        flags.set("transport_ack_window", saved_window)

    # -- query-tracing overhead (r11) ----------------------------------------
    # Same method as the fault gate: (a) per-check cost of the disabled
    # call-site idiom (``if trace.ACTIVE: ...`` — one attribute load +
    # branch); (b) census of trace sites per operation, measured as the
    # spans an ENABLED run creates (every span creation is one gated
    # check); (c) modeled disabled overhead = census * per_check_ns /
    # op_ns, gated <1%; plus a direct enabled-vs-disabled A/B.
    from pixie_tpu.utils import trace

    def _trace_check_ns(iters: int = 1_000_000) -> float:
        trace.set_enabled(False)
        t0 = time.perf_counter_ns()
        for _ in range(iters):
            if trace.ACTIVE:
                raise AssertionError
        return (time.perf_counter_ns() - t0) / iters

    trace_check_ns = _trace_check_ns()
    trace.set_enabled(True)
    trace.clear()
    c.execute_query(query)
    warm_trace_census = trace.buffered_count()
    trace.clear()
    warm_traced_ns = run_warm(warm_runs)
    trace.set_enabled(False)
    warm_untraced_ns = run_warm(warm_runs)

    rbus_t = RemoteBus(server.address)
    sub_t = bus.subscribe("mb/trace")

    def rtt_t(k):
        t0 = time.perf_counter_ns()
        for i in range(k):
            rbus_t.publish("mb/trace", {"i": i})
            got = sub_t.get(timeout=5.0)
            assert got is not None
        return (time.perf_counter_ns() - t0) / k

    rtt_t(50)
    rtt_untraced_ns = rtt_t(rtt_msgs)
    trace.set_enabled(True)
    trace.clear()
    rtt_t(rtt_msgs)
    # Each windowed frame's ack span is one gated check; stamp() checks
    # once more per send.
    rtt_trace_census = trace.buffered_count() / rtt_msgs + 1.0
    trace.clear()
    rtt_traced_ns = rtt_t(rtt_msgs)
    rbus_t.close()
    trace.set_enabled(True)  # default posture
    trace.clear()

    warm_trace_pct = 100.0 * warm_trace_census * trace_check_ns / warm_untraced_ns
    rtt_trace_pct = 100.0 * rtt_trace_census * trace_check_ns / rtt_untraced_ns
    trace_overhead = {
        "trace_check_disabled_ns": round(trace_check_ns, 2),
        "warm_spans_per_query": int(warm_trace_census),
        "warm_disabled_modeled_pct": round(warm_trace_pct, 5),
        "warm_enabled_delta_pct": round(
            100.0 * (warm_traced_ns - warm_untraced_ns) / warm_untraced_ns, 3
        ),
        "rtt_checks_per_rtt": round(rtt_trace_census, 2),
        "rtt_disabled_modeled_pct": round(rtt_trace_pct, 5),
        "rtt_enabled_delta_pct": round(
            100.0 * (rtt_traced_ns - rtt_untraced_ns) / rtt_untraced_ns, 3
        ),
        "pass_under_1pct": bool(warm_trace_pct < 1.0 and rtt_trace_pct < 1.0),
    }
    log(
        f"tracing: {warm_trace_census} spans/warm-query, disabled modeled "
        f"{warm_trace_pct:.4f}% warm / {rtt_trace_pct:.4f}% rtt; enabled "
        f"A/B {trace_overhead['warm_enabled_delta_pct']:+.2f}% warm, "
        f"{trace_overhead['rtt_enabled_delta_pct']:+.2f}% rtt"
    )

    # -- resource-attribution overhead (r15) ---------------------------------
    # Same method as the fault/trace gates: (a) per-check cost of the
    # disabled call-site idiom (``if trace.ATTR_ACTIVE:`` /
    # ``if resattr.ACTIVE:`` — one attribute load + branch); (b) census
    # of attribution hooks per warm query, measured as the records an
    # ENABLED run creates (each record is one gated check) plus the
    # attribution-context enters and residency publish checks the warm
    # path crosses; (c) modeled disabled overhead = census *
    # per_check_ns / op_ns, gated <1%, plus a direct enabled-vs-disabled
    # A/B. The transport RTT has ZERO attribution hooks (attribution
    # never touches the send/ack path) — reported as such.
    from pixie_tpu.parallel import profiler as resattr

    def _attr_check_ns(iters: int = 1_000_000) -> float:
        resattr.set_enabled(False)
        t0 = time.perf_counter_ns()
        for _ in range(iters):
            if trace.ACTIVE and trace.ATTR_ACTIVE and resattr.ACTIVE:
                pass
            if trace.ATTR_ACTIVE:
                raise AssertionError
        return (time.perf_counter_ns() - t0) / iters / 2.0

    attr_check_ns = _attr_check_ns()
    resattr.set_enabled(True)
    resattr.clear()
    c.execute_query(query)
    counts = resattr.buffered_counts()
    # Records created (each = one gated check that passed) + the warm
    # path's constant hooks: the engine's attribution context
    # (enter/exit), the device.execute record check, and the residency
    # pin/unpin publish checks.
    warm_attr_census = (
        counts["dispatches"] + counts["hbm"] + counts["programs"] + 6
    )
    resattr.clear()
    warm_attr_on_ns = run_warm(warm_runs)
    resattr.set_enabled(False)
    warm_attr_off_ns = run_warm(warm_runs)
    resattr.set_enabled(True)
    resattr.clear()
    warm_attr_pct = (
        100.0 * warm_attr_census * attr_check_ns / warm_attr_off_ns
    )
    profiler_overhead = {
        "attr_check_disabled_ns": round(attr_check_ns, 2),
        "warm_hooks_per_query": int(warm_attr_census),
        "warm_disabled_modeled_pct": round(warm_attr_pct, 5),
        "warm_enabled_delta_pct": round(
            100.0 * (warm_attr_on_ns - warm_attr_off_ns)
            / warm_attr_off_ns, 3
        ),
        "rtt_hooks_per_rtt": 0,  # no attribution hooks on the transport
        "rtt_disabled_modeled_pct": 0.0,
        "pass_under_1pct": bool(warm_attr_pct < 1.0),
    }
    log(
        f"attribution: {warm_attr_census} hooks/warm-query, disabled "
        f"modeled {warm_attr_pct:.4f}% warm / 0% rtt; enabled A/B "
        f"{profiler_overhead['warm_enabled_delta_pct']:+.2f}% warm"
    )

    # -- mesh recovery overhead (r23) ----------------------------------------
    # Disabled gate: on a single-axis (flat) mesh — the default — every
    # sharded dispatch crosses _mesh_dispatch exactly once and pays one
    # axis-count branch (len(mesh_config.axes) > 1) before calling the
    # program: no fault-site probes, no watchdog, no collective lock.
    # Census: dispatches per warm query counted by wrapping
    # _mesh_dispatch through one query; modeled disabled overhead =
    # dispatches * branch_ns / op_ns, gated <1%.
    def _mesh_probe_ns(iters: int = 1_000_000) -> float:
        cfg = dev.mesh_config
        t0 = time.perf_counter_ns()
        for _ in range(iters):
            if len(cfg.axes) > 1:
                raise AssertionError
        return (time.perf_counter_ns() - t0) / iters

    mesh_probe_ns = _mesh_probe_ns()
    mesh_calls = [0]
    _orig_md = type(dev)._mesh_dispatch

    def _counting_md(self, fn, what="fold", fold_sig=None):
        mesh_calls[0] += 1
        return _orig_md(self, fn, what, fold_sig=fold_sig)

    type(dev)._mesh_dispatch = _counting_md
    try:
        c.execute_query(query)
    finally:
        type(dev)._mesh_dispatch = _orig_md
    mesh_hooks = mesh_calls[0]
    mesh_modeled_pct = 100.0 * mesh_hooks * mesh_probe_ns / warm_idle_ns
    mesh_recovery_overhead = {
        "dispatch_probe_ns": round(mesh_probe_ns, 2),
        "warm_dispatches_per_query": int(mesh_hooks),
        "warm_disabled_modeled_pct": round(mesh_modeled_pct, 5),
        "pass_under_1pct": bool(mesh_modeled_pct < 1.0),
    }
    log(
        f"mesh recovery: {mesh_hooks} dispatches/warm-query at "
        f"{mesh_probe_ns:.1f}ns -> {mesh_modeled_pct:.4f}% disabled "
        f"modeled on the flat path"
    )

    # -- ingest-robustness overhead (r24) ------------------------------------
    # Disabled gate: with ``ingest_robustness`` off, every captured
    # event pays only bare branches — data_event's cached
    # ``self._robust`` check, the stream buffer's ledger-is-None guards
    # on add/consume, and the stale-duplicate position compare. No
    # ledger dict, no event-end bisect, no budget/quarantine
    # bookkeeping exists on that path. Census: 4 branches/event at the
    # measured idiom cost, over the measured per-event legacy pipe time
    # (feed -> reassemble -> parse -> stitch -> rows), gated <1%.
    # Enabled cost: the same replay with full r24 accounting, as an A/B.
    from pixie_tpu.ingest.capture_gen import build_conn_events
    from pixie_tpu.ingest.socket_tracer import (
        ConnId as _ConnId,
        SocketTraceConnector as _STC,
    )

    def _ingest_branch_ns(iters: int = 1_000_000) -> float:
        holder = type("H", (), {"robust": False})()
        t0 = time.perf_counter_ns()
        for _ in range(iters):
            if holder.robust:
                raise AssertionError
        return (time.perf_counter_ns() - t0) / iters

    ingest_branch_ns = _ingest_branch_ns()

    def _ingest_per_event_ns(robust: bool, conns: int = 120) -> float:
        saved = flags.get("ingest_robustness")
        flags.set("ingest_robustness", robust)
        try:
            src = _STC()
            src.init()
            events = []
            for j in range(conns):
                events.extend(
                    build_conn_events(
                        _ConnId("mb", j), "http", n_exchanges=4, start=j
                    )
                )
            n_data = sum(1 for e in events if e[0] == "data")
            t0 = time.perf_counter_ns()
            for ev in events:
                if ev[0] == "open":
                    src.conn_open(*ev[1:])
                elif ev[0] == "data":
                    src.data_event(*ev[1:])
                else:
                    src.conn_close(ev[1])
            src.transfer_data(None)
            return (time.perf_counter_ns() - t0) / n_data
        finally:
            flags.set("ingest_robustness", saved)

    _ingest_per_event_ns(False, conns=20)  # warm
    ingest_legacy_ns = _ingest_per_event_ns(False)
    ingest_robust_ns = _ingest_per_event_ns(True)
    ingest_checks_per_event = 4.0
    ingest_modeled_pct = (
        100.0 * ingest_checks_per_event * ingest_branch_ns
        / ingest_legacy_ns
    )
    ingest_overhead = {
        "ingest_branch_ns": round(ingest_branch_ns, 2),
        "disabled_checks_per_event": ingest_checks_per_event,
        "legacy_event_ns": round(ingest_legacy_ns, 1),
        "robust_event_ns": round(ingest_robust_ns, 1),
        "disabled_modeled_pct": round(ingest_modeled_pct, 5),
        "robust_on_delta_pct": round(
            100.0 * (ingest_robust_ns - ingest_legacy_ns)
            / ingest_legacy_ns, 2
        ),
        "pass_under_1pct": bool(ingest_modeled_pct < 1.0),
    }
    log(
        f"ingest: {ingest_legacy_ns:.0f}ns/event legacy pipe, "
        f"{ingest_checks_per_event:.0f} branches/event at "
        f"{ingest_branch_ns:.1f}ns -> {ingest_modeled_pct:.4f}% disabled "
        f"modeled; robust-on A/B "
        f"{ingest_overhead['robust_on_delta_pct']:+.1f}%"
    )

    # -- durability spill overhead (r14) -------------------------------------
    # Disabled gate: with no WAL attached, every durability hook on the
    # send/ack path is a bare ``wal is None`` attribute branch —
    # _AckWindow.add (wal check + mem-frame spill decision) and the ack
    # release (wal check). The warm QUERY path has zero durability
    # hooks (ring spill checks sit on the ingest path, not the staged
    # read path). Modeled like the fault gate: branches/op * branch_ns
    # / op_ns. Enabled cost: the same RTT with a live WAL under each
    # fsync policy — 'always' pays the fsync on every windowed frame,
    # 'never' pays only the write+flush (crash-safe, not powerloss-safe).
    import tempfile

    def _branch_ns(iters: int = 1_000_000) -> float:
        holder = type("H", (), {"w": None})()
        t0 = time.perf_counter_ns()
        for _ in range(iters):
            if holder.w is not None:
                raise AssertionError
        return (time.perf_counter_ns() - t0) / iters

    branch_ns = _branch_ns()
    dur_branches_per_rtt = 3.0  # add: wal + spill-bound; release: wal
    dur_disabled_pct = 100.0 * dur_branches_per_rtt * branch_ns / rtt_idle_ns

    wal_tmp = tempfile.mkdtemp(prefix="mb-wal-")

    def rtt_wal(policy: str, n: int) -> float:
        saved_fs = flags.get("wal_fsync")
        flags.set("wal_fsync", policy)
        try:
            rb = RemoteBus(
                server.address, wal_dir=os.path.join(wal_tmp, policy)
            )
            subw = bus.subscribe(f"mb/dur-{policy}")

            def go(k):
                t0 = time.perf_counter_ns()
                for i in range(k):
                    rb.publish(f"mb/dur-{policy}", {"i": i})
                    got = subw.get(timeout=5.0)
                    assert got is not None
                return (time.perf_counter_ns() - t0) / k

            go(50)
            out = go(n)
            rb.close()
            return out
        finally:
            flags.set("wal_fsync", saved_fs)

    rtt_dur_always_ns = rtt_wal("always", rtt_msgs)
    rtt_dur_never_ns = rtt_wal("never", rtt_msgs)
    durability_overhead = {
        "dur_branch_ns": round(branch_ns, 2),
        "disabled_branches_per_rtt": dur_branches_per_rtt,
        "warm_disabled_checks_per_query": 0,  # no hook on the read path
        "disabled_modeled_pct": round(dur_disabled_pct, 5),
        "rtt_disabled_us": round(rtt_idle_ns / 1e3, 2),
        "rtt_wal_fsync_always_us": round(rtt_dur_always_ns / 1e3, 2),
        "rtt_wal_fsync_never_us": round(rtt_dur_never_ns / 1e3, 2),
        "fsync_always_delta_pct": round(
            100.0 * (rtt_dur_always_ns - rtt_idle_ns) / rtt_idle_ns, 2
        ),
        "fsync_never_delta_pct": round(
            100.0 * (rtt_dur_never_ns - rtt_idle_ns) / rtt_idle_ns, 2
        ),
        "pass_under_1pct": bool(dur_disabled_pct < 1.0),
    }
    log(
        f"durability: disabled modeled {dur_disabled_pct:.5f}%, rtt "
        f"{durability_overhead['rtt_disabled_us']}us off vs "
        f"{durability_overhead['rtt_wal_fsync_never_us']}us fsync=never "
        f"({durability_overhead['fsync_never_delta_pct']:+.1f}%) vs "
        f"{durability_overhead['rtt_wal_fsync_always_us']}us fsync=always "
        f"({durability_overhead['fsync_always_delta_pct']:+.1f}%)"
    )

    # -- fragment-failover overhead (r17) ------------------------------------
    # Disabled gate: with ``fragment_failover`` off, the warm query path
    # pays exactly three bookkeeping hooks per fragment — the attempt-
    # cancelled probe plus exec-state track/untrack (each one lock
    # acquire + dict/set op in Carnot) — and the bridge push/poll token
    # branches (token is None). Modeled like the other gates: hooks/op
    # * probe_ns / op_ns, gated <1%. Enabled cost: a warm BROKER query
    # (where the retry/hedge slot bookkeeping actually lives) A/B'd
    # with the flag off vs on.
    def _probe_ns(iters: int = 200_000) -> float:
        t0 = time.perf_counter_ns()
        for _ in range(iters):
            c.attempt_cancelled("mb-none", None)
        return (time.perf_counter_ns() - t0) / iters

    probe_ns = _probe_ns()
    failover_hooks = 3  # per fragment; the warm local plan is 1 fragment
    failover_disabled_pct = (
        100.0 * failover_hooks * probe_ns / warm_idle_ns
    )

    from pixie_tpu.exec import BridgeRouter as _BR
    from pixie_tpu.vizier import Agent, QueryBroker
    from pixie_tpu.vizier.bus import MessageBus as _MB

    fo_bus = _MB()
    fo_router = _BR()
    fo_broker = QueryBroker(
        fo_bus, fo_router,
        table_relations={"http_events": rel},
    )
    fo_agents = [
        Agent(
            "fo-pem", fo_bus, fo_router, table_store=c.table_store,
            device_executor=dev,
        ),
        Agent("fo-kelvin", fo_bus, fo_router, is_kelvin=True),
    ]
    for a in fo_agents:
        a.start()
    deadline = time.time() + 10
    while time.time() < deadline and len(
        fo_broker.tracker.distributed_state().agents
    ) < 2:
        time.sleep(0.02)

    def run_broker_warm(k):
        times = []
        for _ in range(k):
            t0 = time.perf_counter_ns()
            r = fo_broker.execute_script(query, timeout_s=30)
            assert r.degraded is None
            times.append(time.perf_counter_ns() - t0)
        return float(np.median(times))

    saved_fo = flags.get("fragment_failover")
    flags.set("fragment_failover", False)
    run_broker_warm(3)
    broker_off_ns = run_broker_warm(warm_runs)
    flags.set("fragment_failover", True)
    run_broker_warm(3)
    broker_on_ns = run_broker_warm(warm_runs)
    flags.set("fragment_failover", saved_fo)

    # -- materialized-view probe overhead (r20) ------------------------------
    # The view probe sits ABOVE admission on every broker query. On the
    # NON-view path its steady-state cost is one flag check plus a
    # probe-cache lookup resolving to a cached miss entry (the compile
    # happens once per distinct script text). Modeled like the other
    # gates: per-probe ns on a warm cached miss — measured with a LIVE
    # registry holding a registered view the query does not match —
    # over the warm broker query time, gated <1%; plus an off-vs-on A/B
    # of the full broker query as the direct check.
    from pixie_tpu.vizier.datastore import Datastore as _Datastore

    saved_mv = flags.get("materialized_views")
    flags.set("materialized_views", False)
    run_broker_warm(3)
    views_off_ns = run_broker_warm(warm_runs)
    flags.set("materialized_views", True)
    fo_broker.start_views(c.table_store, datastore=_Datastore())
    # A decoy view over the same table with a different fold signature
    # and predicate digest: the measured query probes and MISSES.
    fo_broker.views.register(
        "df = px.DataFrame(table='http_events')\n"
        "df = df[df.service == 'a']\n"
        "s = df.groupby(['service']).agg(n=('latency', px.count))\n"
        "px.display(s, 'out')\n",
        name="mb-decoy",
    )
    r_probe = fo_broker.execute_script(query, timeout_s=30)
    assert r_probe.view is None, "decoy view must not serve the query"

    def _views_probe_ns(iters: int = 20_000) -> float:
        t0 = time.perf_counter_ns()
        for _ in range(iters):
            if fo_broker.views.try_serve(query) is not None:
                raise AssertionError
        return (time.perf_counter_ns() - t0) / iters

    _views_probe_ns(1_000)  # warm the probe cache's miss entry
    views_probe_ns = _views_probe_ns()
    run_broker_warm(3)
    views_on_ns = run_broker_warm(warm_runs)
    flags.set("materialized_views", saved_mv)
    views_modeled_pct = 100.0 * views_probe_ns / views_off_ns
    views_overhead = {
        "probe_miss_ns": round(views_probe_ns, 1),
        "warm_probes_per_query": 1,
        "warm_disabled_modeled_pct": round(views_modeled_pct, 5),
        "broker_query_views_off_ms": round(views_off_ns / 1e6, 3),
        "broker_query_views_on_ms": round(views_on_ns / 1e6, 3),
        "views_on_delta_pct": round(
            100.0 * (views_on_ns - views_off_ns) / views_off_ns, 3
        ),
        "pass_under_1pct": bool(views_modeled_pct < 1.0),
    }
    log(
        f"views: probe miss {views_probe_ns:.0f}ns -> "
        f"{views_modeled_pct:.4f}% modeled on the non-view path; broker "
        f"warm {views_overhead['broker_query_views_off_ms']}ms off vs "
        f"{views_overhead['broker_query_views_on_ms']}ms on "
        f"({views_overhead['views_on_delta_pct']:+.1f}%)"
    )

    fo_broker.stop()
    for a in fo_agents:
        a.stop()
    failover_overhead = {
        "probe_disabled_ns": round(probe_ns, 2),
        "warm_hooks_per_query": failover_hooks,
        "warm_disabled_modeled_pct": round(failover_disabled_pct, 5),
        "broker_query_off_ms": round(broker_off_ns / 1e6, 3),
        "broker_query_on_ms": round(broker_on_ns / 1e6, 3),
        "failover_on_delta_pct": round(
            100.0 * (broker_on_ns - broker_off_ns) / broker_off_ns, 3
        ),
        "pass_under_1pct": bool(failover_disabled_pct < 1.0),
    }
    log(
        f"failover: {failover_hooks} hooks/warm-query at "
        f"{probe_ns:.0f}ns -> {failover_disabled_pct:.4f}% disabled "
        f"modeled; broker warm {failover_overhead['broker_query_off_ms']}"
        f"ms off vs {failover_overhead['broker_query_on_ms']}ms on "
        f"({failover_overhead['failover_on_delta_pct']:+.1f}%)"
    )

    server.stop()
    ack_overhead = {
        "rtt_ack_us": round(rtt_idle_ns / 1e3, 2),
        "rtt_noack_us": round(rtt_noack_ns / 1e3, 2),
        "rtt_ack_delta_pct": round(
            100.0 * (rtt_idle_ns - rtt_noack_ns) / rtt_noack_ns, 2
        ),
        "thrpt_ack_msgs_s": round(thrpt_ack),
        "thrpt_noack_msgs_s": round(thrpt_noack),
        "thrpt_ack_delta_pct": round(
            100.0 * (thrpt_ack - thrpt_noack) / thrpt_noack, 2
        ),
        "noack_modeled_overhead_pct": round(noack_overhead_pct, 5),
        "pass_under_1pct": bool(noack_overhead_pct < 1.0),
    }
    log(
        f"ack window: rtt {ack_overhead['rtt_ack_us']}us acked vs "
        f"{ack_overhead['rtt_noack_us']}us disabled "
        f"({ack_overhead['rtt_ack_delta_pct']:+.1f}%), thrpt "
        f"{ack_overhead['thrpt_ack_msgs_s']}/s vs "
        f"{ack_overhead['thrpt_noack_msgs_s']}/s; disabled modeled "
        f"{ack_overhead['noack_modeled_overhead_pct']:.4f}%"
    )

    out = {
        "fault_check_disabled_ns": round(disabled_ns, 2),
        "fault_check_armed_elsewhere_ns": round(armed_ns, 2),
        "warm_query_ms": round(warm_idle_ns / 1e6, 3),
        "warm_checks_per_query": int(warm_checks),
        "warm_overhead_pct": round(warm_overhead_pct, 5),
        "warm_ab_delta_pct": round(warm_ab_pct, 3),
        "transport_rtt_us": round(rtt_idle_ns / 1e3, 2),
        "transport_checks_per_rtt": round(rtt_checks, 2),
        "transport_overhead_pct": round(rtt_overhead_pct, 5),
        "pass_under_1pct": bool(
            warm_overhead_pct < 1.0
            and rtt_overhead_pct < 1.0
            and ack_overhead["pass_under_1pct"]
            and trace_overhead["pass_under_1pct"]
            and durability_overhead["pass_under_1pct"]
            and profiler_overhead["pass_under_1pct"]
            and failover_overhead["pass_under_1pct"]
            and views_overhead["pass_under_1pct"]
            and mesh_recovery_overhead["pass_under_1pct"]
            and ingest_overhead["pass_under_1pct"]
        ),
        "platform": jax.devices()[0].platform,
    }
    out["ack_overhead"] = ack_overhead
    out["trace_overhead"] = trace_overhead
    out["durability_overhead"] = durability_overhead
    out["profiler_overhead"] = profiler_overhead
    out["failover_overhead"] = failover_overhead
    out["views_overhead"] = views_overhead
    out["mesh_recovery_overhead"] = mesh_recovery_overhead
    out["ingest_overhead"] = ingest_overhead
    print(json.dumps(out))

    if os.environ.get("MB_WRITE_BENCH_DETAIL") == "1":
        path = os.path.join(os.path.dirname(__file__), "..", "BENCH_DETAIL.json")
        with open(path) as f:
            detail = json.load(f)
        detail["fault_overhead"] = {
            k: v
            for k, v in out.items()
            if k not in (
                "ack_overhead", "trace_overhead",
                "durability_overhead", "profiler_overhead",
                "failover_overhead", "views_overhead",
                "mesh_recovery_overhead", "ingest_overhead",
            )
        }
        detail["ack_overhead"] = ack_overhead
        detail["trace_overhead"] = trace_overhead
        detail["durability_overhead"] = durability_overhead
        detail["profiler_overhead"] = profiler_overhead
        detail["failover_overhead"] = failover_overhead
        detail["views_overhead"] = views_overhead
        detail["mesh_recovery_overhead"] = mesh_recovery_overhead
        detail["ingest_overhead"] = ingest_overhead
        with open(path, "w") as f:
            json.dump(detail, f, indent=1)
            f.write("\n")
        log(
            "BENCH_DETAIL.json updated (fault_overhead, ack_overhead, "
            "trace_overhead, durability_overhead, profiler_overhead, "
            "failover_overhead, views_overhead, mesh_recovery_overhead, "
            "ingest_overhead)"
        )

    if not out["pass_under_1pct"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
