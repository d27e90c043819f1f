"""Serving soak: concurrent scripted clients at steady QPS (r12).

The acceptance harness for the multi-query serving engine: an in-process
cluster (broker + PEM-role agent with a device MeshExecutor + Kelvin
merger) serves N concurrent clients issuing signature-compatible PxL
scripts against shared hot tables at a steady per-client rate, with
admission control on and an HBM budget set. It reports:

- p50/p99 end-to-end latency and completed/rejected/degraded counts,
- shared-scan effectiveness: fold dispatches vs queries through the
  fold path (the ≥2x dispatch-reduction bar vs the 1-dispatch-per-query
  serial baseline) and the mean batch size,
- residency behavior: peak staged bytes (must stay ≤ hbm_budget_mb) and
  eviction counts,
- bit-identical correctness: every concurrent result is compared
  against the serially-executed baseline for its query.

Env knobs: SOAK_CLIENTS (64), SOAK_REQUESTS (4 per client), SOAK_QPS
(8.0 per client), SOAK_ROWS (100k), SOAK_HBM_BUDGET_MB (64),
SOAK_WINDOW_MS (25), SOAK_MAX_CONCURRENT (8), SOAK_CHAOS (0),
SOAK_PROFILE (0 — r15 attributed profiling through the concurrent
phase), SOAK_JSON (path to also write the report),
SOAK_WRITE_BENCH_DETAIL (1 = record the contention + profile blocks
into BENCH_DETAIL.json under ``serving_soak``).

Run: JAX_PLATFORMS=cpu python tools/soak_serving.py
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# Compatible script set on the r16 two-rung ladder. Within a predicate
# family only output names differ (identical fold signatures — rung 1);
# ACROSS families the predicates differ but normalize to comparison
# terms over one staged entry (rung 2: predicate batching), so a mixed
# arrival burst coalesces into ONE batched dispatch whose width the
# serving_shared_scan_batch_width histogram records. The first query
# (run first by the serial baseline) references every column, so its
# superset staging serves the whole set.
def compatible_queries() -> list[str]:
    out = []
    preds = (
        "df.resp_status == 200",
        "df.resp_status == 404",
        "df.resp_status == 500",
        "df.resp_status != 200",
        "df.latency > 20000000.0",
        # r18: IN-list family — normalizes to one LUT-lane membership
        # term, so it joins predicate batches with the families above.
        "df.resp_status in [200, 404]",
        None,  # unfiltered family (rung-1 only vs itself)
    )
    for pred in preds:
        for names in (("n", "total"), ("cnt", "s")):
            filt = f"df = df[{pred}]\n" if pred else ""
            out.append(
                "df = px.DataFrame(table='http_events')\n"
                + filt
                + "st = df.groupby(['service']).agg(\n"
                f"    {names[0]}=('time_', px.count),\n"
                f"    {names[1]}=('latency', px.sum),\n"
                ")\n"
                "px.display(st, 'out')\n"
            )
    # r19 join family: INNER/LEFT merges against the owners dim table,
    # aggregated per owner so the forwarded result stays small. These
    # are safe under the ORDER-SENSITIVE bit-identity gate: the host
    # equijoin emits matches in probe-stream order (deterministic per
    # bridge) with unmatched build rows trailing, and the device lane
    # reproduces that order exactly for INNER/LEFT. RIGHT/OUTER
    # interleave unmatched probe rows per batch and are excluded.
    for how in ("inner", "left"):
        out.append(
            "l = px.DataFrame(table='owners')\n"
            "r = px.DataFrame(table='http_events')\n"
            f"j = l.merge(r, how='{how}', left_on=['svc'],"
            " right_on=['service'], suffixes=['', '_r'])\n"
            "st = j.groupby(['owner']).agg(\n"
            "    n=('time_', px.count),\n"
            "    s=('latency', px.sum),\n"
            ")\n"
            "px.display(st, 'out')\n"
        )
    return out


# Fleet workload (r18): T hot tables, each with a HIGH-cardinality
# dict-encoded string key — staging one is expensive (np.unique +
# encode + host pack), a warm fold is cheap. The HBM budget is set so
# ONE agent can hold only a couple of staged entries: a 1-agent fleet
# LRU-thrashes (every query re-stages), while N placement-routed agents
# partition the tables (~T/N each) and serve every query from hot HBM.
# That working-set-vs-cluster-HBM gap, not parallel compute, is what
# the QPS-vs-agent-count scaling measures.
def fleet_queries(num_tables: int) -> list[str]:
    out = []
    for i in range(num_tables):
        for names in (("n", "total"), ("cnt", "s")):
            out.append(
                f"df = px.DataFrame(table='hot_{i}')\n"
                "st = df.groupby(['service']).agg(\n"
                f"    {names[0]}=('time_', px.count),\n"
                f"    {names[1]}=('latency', px.sum),\n"
                ")\n"
                "px.display(st, 'out')\n"
            )
    return out


# Dashboard workload (r20): a small fixed panel of aggregation scripts
# that clients re-run verbatim — the materialized-view plane's target
# shape. Every script is view-compatible (single table, FULL fold,
# normalizable predicates) and together they cover the r6 mergeable UDA
# lanes (count / sum / HLL / count-min), a multi-column group key, and
# the time-bucket special case. Latencies are integer-valued floats in
# views mode so px.sum stays exact under ANY fold grouping — carried
# state ⊕ tail delta is then bit-identical to a from-scratch fold.
def views_queries() -> list[str]:
    base = "df = px.DataFrame(table='http_events')\n"
    return [
        base
        + "st = df.groupby(['service']).agg(\n"
        "    n=('time_', px.count),\n"
        "    s=('latency', px.sum),\n"
        ")\n"
        "px.display(st, 'out')\n",
        base
        + "df = df[df.resp_status == 500]\n"
        "st = df.groupby(['service']).agg(\n"
        "    errors=('time_', px.count),\n"
        ")\n"
        "px.display(st, 'out')\n",
        base
        + "df = df[df.resp_status == 200]\n"
        "st = df.groupby(['service']).agg(\n"
        "    ok=('time_', px.count),\n"
        "    total=('latency', px.sum),\n"
        ")\n"
        "px.display(st, 'out')\n",
        base
        + "st = df.groupby(['service']).agg(\n"
        "    u=('resp_status', px.approx_count_distinct),\n"
        "    cm=('resp_status', px.count_min),\n"
        ")\n"
        "px.display(st, 'out')\n",
        base
        + "st = df.groupby(['service', 'resp_status']).agg(\n"
        "    n=('time_', px.count),\n"
        ")\n"
        "px.display(st, 'out')\n",
        # Windowed aggregation as a view: the time bucket is just a
        # composed group expression, one state row per bucket.
        base
        + "df.bucket = px.bin(df.time_, 10000000)\n"
        "st = df.groupby(['bucket']).agg(\n"
        "    n=('time_', px.count),\n"
        "    s=('latency', px.sum),\n"
        ")\n"
        "px.display(st, 'out')\n",
    ]


def _table_key(result) -> dict:
    from pixie_tpu.table.row_batch import RowBatch

    batches = [b for b in result.tables["out"] if b.num_rows]
    return RowBatch.concat(batches).to_pydict() if batches else {}


def _tables_equal(a: dict, b: dict) -> bool:
    if set(a) != set(b):
        return False
    for col in a:
        av, bv = np.asarray(a[col]), np.asarray(b[col])
        if av.dtype != bv.dtype or not np.array_equal(av, bv):
            return False
    return True


# Sites armed by --chaos, all of which fire on this in-process cluster
# (transport.* sites need a RemoteBus and are exercised by the
# test_durability/test_faults chaos suites instead). Probabilities are
# low: the soak's point is that a steady stream of injected failures
# — including the OWNER AGENT DYING outright mid-query — yields, with
# r17 fragment failover on, ZERO degraded results: every query
# completes bit-identical to the unfaulted run, with
# broker_fragment_retries_total proving failover (not luck) did it.
# When the flag-resolved mesh geometry is multi-axis, --chaos also arms
# mesh.host_loss (count=1, mid-phase) — the r23 degraded-geometry
# ladder, not broker failover, must carry that one (see _run_soak_inner).
CHAOS_SITES = {
    "serving.admission_reject": dict(p=0.03, seed=101),
    "agent.execute@pem1": dict(p=0.03, seed=102),
    "broker.forward": dict(p=0.01, seed=103),
    # r17: kill pem1 WHILE it holds fragments (heartbeats stop, results
    # withheld) partway into the concurrent phase — everything after
    # this lands on the replica agent via retry/promotion.
    "agent.kill_holding_fragment@pem1": dict(count=1, after=20, seed=106),
    # Checked when an eviction pass SKIPS a pinned entry: p=0 arming
    # makes it a pure census (fired stays 0, checks count pin holds).
    "serving.evict_pinned_attempt": dict(p=0.0, seed=105),
}


# Leaf frames that mean "parked, not burning CPU": Python stack sampling
# sees blocked threads too, so busy-CPU attribution excludes stacks whose
# leaf is a wait/poll primitive or a pool worker's idle loop (the r15
# profile block reports raw and busy-only attribution). A leaf INSIDE
# threading.py is lock/condition machinery (cv wait re-acquire, lock
# __enter__, notify) — blocked or about to be, not real work.
_WAIT_LEAVES = (
    "wait", "get", "poll", "select", "sleep", "accept", "recv",
    "read", "join", "_recv_loop", "serve_forever", "_worker",
)
_WAIT_LEAF_MODULES = ("threading",)
# Soak-harness frames (client pacing/bookkeeping loops): a real
# deployment's clients live in other processes — samples whose leaf is
# the harness itself are reported separately, not as engine busy time.
_HARNESS_LEAF_MODULE = "soak_serving"


def _profile_report(counts: dict, samples: int) -> dict:
    """Summarize attributed stack samples: overall + engine-busy-only
    attribution percentages and the top attributed stacks."""
    total = busy = attributed = busy_attr = harness = 0
    per_stack: dict = {}
    for (upid, folded, qid, tenant, phase), c in counts.items():
        total += c
        leaf = folded.rsplit(";", 1)[-1]
        leaf_mod, _, leaf_fn = leaf.rpartition(".")
        is_busy = (
            leaf_mod not in _WAIT_LEAF_MODULES
            and not any(w in leaf_fn for w in _WAIT_LEAVES)
        )
        if is_busy and leaf_mod == _HARNESS_LEAF_MODULE and not qid:
            harness += c
            is_busy = False
        busy += c if is_busy else 0
        if qid:
            attributed += c
            busy_attr += c if is_busy else 0
        per_stack[(folded, qid, tenant, phase)] = (
            per_stack.get((folded, qid, tenant, phase), 0) + c
        )
    top = sorted(per_stack.items(), key=lambda kv: -kv[1])[:10]
    return {
        "samples": samples,
        "stack_samples": total,
        "attributed_pct": round(100.0 * attributed / total, 1) if total else 0.0,
        "busy_stack_samples": busy,
        "harness_samples": harness,
        "busy_attributed_pct": (
            round(100.0 * busy_attr / busy, 1) if busy else 0.0
        ),
        "top_stacks": [
            {
                "stack": folded[-160:],
                "query_id": qid[:12],
                "tenant": tenant,
                "phase": phase,
                "count": c,
            }
            for (folded, qid, tenant, phase), c in top
        ],
    }


def run_soak(
    clients: int = 64,
    requests_per_client: int = 4,
    qps_per_client: float = 8.0,
    rows: int = 100_000,
    hbm_budget_mb: int = 64,
    window_ms: float = 25.0,
    max_concurrent: int = 8,
    seed: int = 11,
    chaos: bool = False,
    profile: bool = False,
    controller: bool = False,
    agents: int = 1,
    fleet_tables: int = 0,
    views: bool = False,
) -> dict:
    """Build the cluster, run the soak (serving flags pinned for the
    run, restored after), return the report dict. ``chaos`` arms
    CHAOS_SITES for the concurrent phase (r14 satellite): the report's
    ``contention.chaos`` block then carries recovered vs degraded vs
    rejected counts plus per-site fire stats. ``controller`` (r16)
    enables the closed-loop admission controller for the run — the
    report's ``controller`` block carries its actuation trail and
    final knob values. ``fleet_tables`` > 0 (r18) switches to the
    fleet workload (``fleet_tables`` hot tables, ``rows`` rows each)
    over ``agents`` data-plane agents with residency placement ON; the
    report gains a ``placement`` block (hit rate, per-agent shares,
    rebalancer trail). ``views`` (r20) switches to the dashboard-repeat
    workload: the ``views_queries`` panel is registered as materialized
    views after the serial baselines, and the concurrent phase measures
    view hit rate + fold-dispatch reduction vs the views-off cost of
    one full fold per request; the report gains a ``views`` block."""
    from pixie_tpu.utils import flags

    soak_flags = {
        "serving_enabled": True,
        "hbm_budget_mb": hbm_budget_mb,
        "shared_scans": True,
        "shared_scan_predicate_batching": True,
        "shared_scan_window_ms": window_ms,
        "admission_max_concurrent": max_concurrent,
        "admission_max_queue": max(4 * clients, 256),
        "admission_timeout_s": 60.0,
        "admission_tenant_weights": "dashboards:2.0,batch:1.0",
    }
    if controller:
        soak_flags.update(
            {
                "admission_controller": True,
                "admission_controller_interval_s": 0.5,
                "admission_controller_max_window_ms": max(
                    window_ms * 2.0, 25.0
                ),
            }
        )
    if chaos:
        # r17: chaos runs with transparent failover ON — the acceptance
        # bar is zero degraded results (bit-identical completion via
        # retry onto the replica agent), not structured degradation.
        soak_flags["fragment_failover"] = True
    if views:
        # r20 views mode: the bit-identity gate compares view-served
        # reads (host AggNode merge — the contract test-pinned in
        # tests/test_views.py) against baselines, so the baseline path
        # must be the SAME host fold lane: shared scans (the device
        # fold lane) stay off and the data-plane agent runs without a
        # device executor. What this soak measures is the view plane —
        # probe hit rate and fold-dispatch avoidance — not the device
        # coalescing the standard workload gates on.
        soak_flags.update(
            {
                "materialized_views": True,
                "view_refresh_interval_s": 0.25,
                "view_max_staleness_s": 30.0,
                "shared_scans": False,
                "shared_scan_predicate_batching": False,
            }
        )
    if fleet_tables > 0:
        # r18 fleet mode: placement routes at admission; the entry cap
        # is lifted above the table count so the BYTE budget is the
        # only residency rail (that's the thrash the 1-agent baseline
        # must hit); with >1 agent the rebalancer runs too, assigning
        # replica followers from placement heat.
        soak_flags.update(
            {
                "residency_placement": True,
                "fragment_failover": True,
                "staged_cache_cap": fleet_tables + 2,
                "ring_replication_factor": 2 if agents > 1 else 1,
                "ring_rebalance": agents > 1,
                "ring_rebalance_interval_s": 0.5,
                # The fleet harness serializes device offloads on one
                # clock (see _run_soak_inner) to meter per-chip time;
                # shared-scan joiners block INSIDE the offload waiting
                # for their leader, which would deadlock under that
                # serialization — and per-agent capacity must meter
                # un-coalesced folds anyway.
                "shared_scans": False,
                "shared_scan_predicate_batching": False,
            }
        )
    for name, value in soak_flags.items():
        flags.set(name, value)
    try:
        return _run_soak_inner(
            clients, requests_per_client, qps_per_client, rows,
            hbm_budget_mb, window_ms, seed, chaos, profile,
            agents, fleet_tables, views,
        )
    finally:
        # Restore env/default flag values so an embedding caller
        # (bench.py's concurrency config) is not left in serving mode.
        # The controller actuates some of these at runtime; reset()
        # restores the env/default either way.
        for name in soak_flags:
            flags.reset(name)


def _run_soak_inner(
    clients, requests_per_client, qps_per_client, rows,
    hbm_budget_mb, window_ms, seed, chaos=False, profile=False,
    n_agents=1, fleet_tables=0, views=False,
) -> dict:
    import jax

    from pixie_tpu.exec import BridgeRouter
    from pixie_tpu.parallel import MeshExecutor
    from pixie_tpu.serving.admission import AdmissionRejected
    from pixie_tpu.table.table_store import TableStore
    from pixie_tpu.types import DataType, Relation, SemanticType
    from pixie_tpu.utils import metrics_registry
    from pixie_tpu.vizier import Agent, MessageBus, QueryBroker

    F, I, S, T = (
        DataType.FLOAT64,
        DataType.INT64,
        DataType.STRING,
        DataType.TIME64NS,
    )
    rel = Relation.of(
        ("time_", T, SemanticType.ST_TIME_NS),
        ("service", S),
        ("resp_status", I),
        ("latency", F),
    )
    # r21: geometry comes from the mesh_axes flag (flat by default) so
    # the soak can exercise multi-host sub-meshes via
    # PIXIE_TPU_MESH_AXES=hosts:2,d:-1 without code changes.
    ex = MeshExecutor()
    store = TableStore()
    rng = np.random.default_rng(seed)
    fleet = fleet_tables > 0
    table_relations = {}
    if fleet:
        # r18 fleet workload: fleet_tables hot tables × rows each, with
        # a ~2000-value service key — dict-encoding it is the expensive
        # part of staging, so re-staging (1-agent thrash) vs warm HBM
        # (placement across N agents) is the measured contrast.
        services = [f"svc-{i}" for i in range(2000)]
        for i in range(fleet_tables):
            name = f"hot_{i}"
            table_relations[name] = rel
            ht = store.create_table(name, rel, size_limit=1 << 40)
            ht.write_pydict(
                {
                    "time_": np.arange(rows, dtype=np.int64) * 1000,
                    "service": rng.choice(services, rows).astype(object),
                    "resp_status": rng.choice([200, 404, 500], rows),
                    "latency": rng.exponential(3e7, rows),
                }
            )
            ht.compact()
            ht.stop()
    else:
        table_relations["http_events"] = rel
        t = store.create_table("http_events", rel, size_limit=1 << 40)
        chunk = 1 << 18
        for off in range(0, rows, chunk):
            m = min(chunk, rows - off)
            lat = rng.exponential(3e7, m)
            t.write_pydict(
                {
                    "time_": np.arange(off, off + m, dtype=np.int64)
                    * 1000,
                    "service": rng.choice(
                        [f"svc-{i}" for i in range(8)], m
                    ).astype(object),
                    "resp_status": rng.choice([200, 404, 500], m),
                    # Views mode: integer-valued floats keep px.sum
                    # exact under any fold grouping (see views_queries).
                    "latency": np.floor(lat) if views else lat,
                }
            )
        t.compact()
        if not views:
            # Views mode keeps the write path open: the post-phase
            # verify appends a delta and checks the maintained view
            # against a from-scratch fold.
            t.stop()
        # r19: the join family's dim side. One owner per service plus an
        # ownerless extra key, so LEFT joins exercise the unmatched-build
        # null padding through the serving path.
        owners_rel = Relation.of(("svc", S), ("owner", S))
        table_relations["owners"] = owners_rel
        to = store.create_table("owners", owners_rel, size_limit=1 << 30)
        to.write_pydict(
            {
                "svc": np.array(
                    [f"svc-{i}" for i in range(8)] + ["svc-unowned"],
                    dtype=object,
                ),
                "owner": np.array(
                    [f"team-{i % 3}" for i in range(8)] + ["team-none"],
                    dtype=object,
                ),
            }
        )
        to.compact()
        to.stop()

    from pixie_tpu.serving.admission import make_store_estimator

    bus = MessageBus()
    router = BridgeRouter()
    broker = QueryBroker(
        bus,
        router,
        table_relations=table_relations,
        # Fleet mode: admission's single-pool byte gate would judge the
        # whole fleet by pem1's pool — the 1-agent thrash baseline is
        # the POINT, so the broker-side residency gate stays off and
        # each agent's own ResidencyPool enforces its budget.
        residency=None if (fleet or views) else ex._staged_cache,
        # r13: metadata staging-bytes estimates gate admission BEFORE a
        # doomed cold stage (row count × encoded column widths).
        staging_estimator=(
            None if (fleet or views) else make_store_estimator(store)
        ),
    )
    agents = [
        # Views mode runs the data-plane agent host-only (no device
        # executor): baselines then take the same host AggNode fold
        # lane the view merge path uses — the bit-identity contract
        # tests/test_views.py pins (see run_soak's views branch).
        Agent("pem1", bus, router, table_store=store)
        if views
        else Agent(
            "pem1", bus, router, table_store=store, device_executor=ex
        ),
        Agent("kelvin", bus, router, is_kelvin=True),
    ]
    if fleet:
        # r18: N data-plane agents over the SHARED store — pem1 owns
        # every table (the planner's fallback target); pem2..pemN are
        # replica-capable (owned_tables=[]) with their OWN executors at
        # the same mesh geometry, so a placement-routed fold is
        # bit-identical wherever it lands (the r17 pem2 construction,
        # N-wide).
        for i in range(2, n_agents + 1):
            exn = MeshExecutor()  # same flag-resolved geometry as pem1
            agents.insert(
                i - 1,
                Agent(
                    f"pem{i}", bus, router, table_store=store,
                    device_executor=exn, owned_tables=[],
                ),
            )
    if chaos:
        # r17 replica agent: same (shared) table store, its own device
        # executor at the same mesh geometry (device folds stay
        # bit-identical), advertised as replica-only — the planner
        # never scans it, failover does.
        ex2 = MeshExecutor()  # same flag-resolved geometry as pem1
        agents.insert(
            1,
            Agent(
                "pem2", bus, router, table_store=store,
                device_executor=ex2, owned_tables=[],
            ),
        )
    # r18: per-agent device capacity meter. The N simulated chips share
    # ONE host core, so wall-clock QPS cannot show chip parallelism —
    # the same reason the kernel benches report rows/s/chip. A harness
    # lock serializes offloads (one chip's work in flight at a time), so
    # each agent's busy clock is EXCLUSIVE device time: per-agent
    # capacity = offloads / busy_s is what that chip sustains alone, and
    # the fleet aggregate is their sum — the throughput N independent
    # devices deliver in deployment. The 1-agent baseline's meter
    # naturally absorbs its re-staging thrash (the offload span covers
    # stage hit/miss + fold), which is exactly the contrast under test.
    agent_busy: dict = {}
    if fleet:
        device_clock = threading.Lock()

        def _meter(aid, dex):
            orig = dex.try_execute_fragment
            rec = agent_busy.setdefault(aid, [0, 0])

            def timed(*a, **k):
                with device_clock:
                    t0 = time.perf_counter_ns()
                    try:
                        return orig(*a, **k)
                    finally:
                        rec[0] += time.perf_counter_ns() - t0
                        rec[1] += 1

            dex.try_execute_fragment = timed

        for a in agents:
            dev = getattr(a.carnot, "device_executor", None)
            if dev is not None:
                _meter(a.agent_id, dev)
    for a in agents:
        a.start()
    time.sleep(0.3)

    if fleet:
        queries = fleet_queries(fleet_tables)
    elif views:
        queries = views_queries()
    else:
        queries = compatible_queries()
    reg = metrics_registry()
    dispatches = reg.counter("serving_shared_scan_dispatches_total")
    saved = reg.counter("serving_shared_scan_saved_dispatches_total")
    evictions = reg.counter("device_staged_cache_evictions_total")
    staged_bytes = reg.gauge("device_staged_bytes")
    # r16: predicate-batched dispatch width (the headline serving
    # metric) + demand-gated window skips.
    width_h = reg.histogram("serving_shared_scan_batch_width")
    pred_batched = reg.counter(
        "serving_shared_scan_predicate_batched_queries_total"
    )
    window_skips = reg.counter(
        "serving_shared_scan_window_skips_total"
    )

    # Serial baseline: each distinct script once, results recorded for
    # the bit-identical check; also warms the staged cache so the soak
    # measures the serving steady state, not N concurrent cold stages.
    baselines = []
    t0 = time.perf_counter()
    for q in queries:
        r = broker.execute_script(q, timeout_s=120, tenant="baseline")
        assert r.degraded is None, f"serial baseline degraded: {r.degraded}"
        baselines.append(_table_key(r))
    log(f"serial baseline: {len(queries)} queries in "
        f"{time.perf_counter() - t0:.2f}s")
    # r20: register the dashboard panel as materialized views AFTER the
    # baselines — baselines are from-scratch truth, every concurrent
    # view-served read is judged against them bit-for-bit. register()
    # runs the first maintenance synchronously, so the panel is warm
    # (watermark == end) before the first client arrives.
    if views:
        from pixie_tpu.vizier.datastore import Datastore

        broker.start_views(store, datastore=Datastore())
        v0 = time.perf_counter()
        for vi, q in enumerate(queries):
            broker.views.register(q, name=f"dash-{vi}")
        log(f"registered {len(queries)} views in "
            f"{time.perf_counter() - v0:.2f}s")
        # Post-registration fold snapshot: the concurrent-phase
        # fold-dispatch delta excludes the one-time registration folds.
        vrows0 = {
            vid: v.rows_folded
            for vid, v in broker.views._views.items()
        }
    d0, s0 = dispatches.value(), saved.value()
    w0_counts = width_h.merged_counts()
    pb0, ws0 = pred_batched.value(), window_skips.value()
    # r18: placement counters AFTER the serial baselines (which also
    # warm span affinity + per-agent residency) — the report's hit rate
    # and per-agent shares are concurrent-phase deltas.
    placement0 = (
        broker.placement.status() if broker.placement is not None else None
    )
    # Device-meter snapshot after the baselines: capacity is a
    # concurrent-phase delta like the placement counters above.
    busy0 = {aid: list(rec) for aid, rec in agent_busy.items()}

    retries_c = reg.counter("broker_fragment_retries_total")
    recovered_c = reg.counter("broker_recovered_queries_total")
    wasted_c = reg.counter("broker_hedge_both_complete_total")
    mesh_degrade_c = reg.counter("mesh_degrade_events_total")
    r0, rec0, w0, md0 = (
        retries_c.total(), recovered_c.total(), wasted_c.total(),
        mesh_degrade_c.total(),
    )
    # r23: the mesh phase only exists when the flag-resolved geometry is
    # multi-axis (PIXIE_TPU_MESH_AXES=hosts:2,d:-1) — a flat executor
    # never checks the mesh fault sites.
    mesh_chaos = chaos and len(ex.mesh_config.axes) > 1
    if chaos:
        # Armed AFTER the unfaulted baselines: every concurrent result
        # is still judged against clean truth.
        from pixie_tpu.utils import faults

        for site, kw in CHAOS_SITES.items():
            faults.arm(site, **kw)
        armed = sorted(CHAOS_SITES)
        if mesh_chaos:
            # r23 mesh phase: kill one simulated host mid-fold partway
            # into the concurrent phase. The executor's degradation
            # ladder must re-plan the fold onto the surviving geometry
            # bit-identically — the broker never sees the loss, so the
            # gate stays ZERO degraded while mesh_degrade_events_total
            # proves the ladder (not luck) carried the faulted fold.
            faults.arm("mesh.host_loss", count=1, after=10, seed=107)
            armed.append("mesh.host_loss")
        log(f"chaos armed: {armed}")

    # Continuous profiler (r15): sample this process's Python stacks —
    # broker/agent/worker threads carry their query attribution — through
    # the concurrent phase; device dispatches are read from the
    # attribution buffers afterwards.
    prof_conn = None
    prof_samples = [0]
    prof_stop = threading.Event()
    prof_thread = None
    if profile:
        from pixie_tpu.ingest.host_profiler import HostProfilerConnector
        from pixie_tpu.parallel import profiler as resattr

        resattr.clear()
        # skip_self: the dedicated sampling thread must not profile the
        # observer itself.
        prof_conn = HostProfilerConnector(
            sample_others=False, skip_self=True
        )
        prof_conn.init()

        def prof_loop():
            while not prof_stop.is_set():
                prof_conn.sample()
                prof_samples[0] += 1
                prof_stop.wait(0.01)

        prof_thread = threading.Thread(target=prof_loop, daemon=True)
        prof_thread.start()

    # Peak-residency sampler (the gauge is also asserted per insert in
    # tests; the sampler catches transients between client requests).
    peak = [0.0]
    stop = threading.Event()

    def sampler():
        while not stop.is_set():
            peak[0] = max(peak[0], staged_bytes.value())
            stop.wait(0.01)

    sampler_t = threading.Thread(target=sampler, daemon=True)
    sampler_t.start()

    latencies: list[float] = []
    rejected = [0]
    degraded = [0]
    mismatches = [0]
    completed = [0]
    view_hits = [0]
    view_latencies: list[float] = []
    lock = threading.Lock()
    barrier = threading.Barrier(clients)

    def client(i: int) -> None:
        crng = np.random.default_rng(1000 + i)
        tenant = "dashboards" if i % 2 == 0 else "batch"
        period = 1.0 / qps_per_client
        barrier.wait()
        # Jittered start so arrivals are steady, not phase-locked.
        time.sleep(float(crng.random()) * period)
        for r in range(requests_per_client):
            qi = int(crng.integers(0, len(queries)))
            q0 = time.perf_counter()
            try:
                res = broker.execute_script(
                    queries[qi], timeout_s=120, tenant=tenant
                )
                dt = time.perf_counter() - q0
                with lock:
                    completed[0] += 1
                    latencies.append(dt)
                    if getattr(res, "view", None) is not None:
                        view_hits[0] += 1
                        view_latencies.append(dt)
                    if res.degraded is not None:
                        # Structured partial (chaos / lost agents): rows
                        # are intentionally incomplete, so bit-identity
                        # is only asserted for clean completions.
                        degraded[0] += 1
                    elif not _tables_equal(baselines[qi], _table_key(res)):
                        mismatches[0] += 1
            except AdmissionRejected:
                with lock:
                    rejected[0] += 1
            sleep_left = period - (time.perf_counter() - q0)
            if sleep_left > 0:
                time.sleep(sleep_left)

    threads = [
        threading.Thread(target=client, args=(i,)) for i in range(clients)
    ]
    wall0 = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    wall = time.perf_counter() - wall0
    stop.set()
    sampler_t.join(timeout=2)
    profile_block = None
    if profile:
        prof_stop.set()
        prof_thread.join(timeout=2)
        from pixie_tpu.parallel import profiler as resattr

        with prof_conn._lock:
            stack_counts = dict(prof_conn._counts)
        profile_block = _profile_report(stack_counts, prof_samples[0])
        # Device-side attribution: every dispatch row carries the
        # (query_id, tenant) of the query that caused it.
        disp = resattr.drain_dispatches()
        dev_total = sum(d["duration_ns"] for d in disp)
        dev_attr = sum(d["duration_ns"] for d in disp if d["query_id"])
        per_prog: dict = {}
        for d in disp:
            k = (d["program"], d["kind"])
            agg = per_prog.setdefault(
                k, {"dispatches": 0, "device_ns": 0, "tenants": set()}
            )
            agg["dispatches"] += 1
            agg["device_ns"] += d["duration_ns"]
            if d["tenant"]:
                agg["tenants"].add(d["tenant"])
        top_programs = sorted(
            per_prog.items(), key=lambda kv: -kv[1]["device_ns"]
        )[:10]
        profile_block["device"] = {
            "dispatches": len(disp),
            "device_time_ms": round(dev_total / 1e6, 2),
            "attributed_pct": (
                round(100.0 * dev_attr / dev_total, 1) if dev_total else 0.0
            ),
            "top_programs": [
                {
                    "program": prog[:80],
                    "kind": kind,
                    "dispatches": agg["dispatches"],
                    "device_ms": round(agg["device_ns"] / 1e6, 2),
                    "tenants": sorted(agg["tenants"]),
                }
                for (prog, kind), agg in top_programs
            ],
        }
    chaos_stats = None
    if chaos:
        from pixie_tpu.utils import faults

        chaos_stats = faults.stats()
        faults.reset()  # teardown runs unfaulted
    controller_status = (
        broker.admission_controller.status()
        if broker.admission_controller is not None
        else None
    )
    # r18: concurrent-phase placement deltas + the rebalancer's trail.
    placement_block = None
    if broker.placement is not None and placement0 is not None:
        p1 = broker.placement.status()
        deltas = {
            k: int(p1["decisions"].get(k, 0))
            - int(placement0["decisions"].get(k, 0))
            for k in p1["decisions"]
        }
        total_d = sum(deltas.values())
        hits = deltas.get("ring_hit", 0) + deltas.get("replica_hit", 0)
        shares = {}
        for aid, st in p1["per_agent"].items():
            prev = placement0["per_agent"].get(aid, {}).get("placed", 0)
            delta = int(st["placed"]) - int(prev)
            if delta > 0:
                shares[aid] = delta
        # Per-agent device capacity (concurrent-phase delta): each
        # agent's exclusive device seconds and offload count under the
        # serialized device clock. qps_capacity sums per-chip rates —
        # what the fleet sustains when every agent folds on its own
        # device (in-sim, all chips share one host core, so wall-clock
        # queries_per_sec cannot show this; rows/s/chip convention).
        capacity = {}
        for aid, rec in sorted(agent_busy.items()):
            b0, o0 = busy0.get(aid, [0, 0])
            d_busy, d_off = rec[0] - b0, rec[1] - o0
            if d_off > 0 and d_busy > 0:
                capacity[aid] = {
                    "offloads": int(d_off),
                    "busy_s": round(d_busy / 1e9, 3),
                    "service_ms": round(d_busy / 1e6 / d_off, 2),
                    "qps_capacity": round(d_off / (d_busy / 1e9), 1),
                }
        placement_block = {
            "agents": n_agents,
            "decisions": deltas,
            "device_capacity": {
                "per_agent": capacity,
                "aggregate_qps_capacity": round(
                    sum(v["qps_capacity"] for v in capacity.values()), 1
                ),
            },
            "hit_rate": round(hits / total_d, 4) if total_d else None,
            "per_agent_share": shares,
            "balance_max_min": (
                round(max(shares.values()) / min(shares.values()), 2)
                if shares
                else None
            ),
            "rebalancer": (
                {
                    "assignments": broker.ring_rebalancer.status()[
                        "assignments"
                    ],
                    "actuations": broker.ring_rebalancer.status()[
                        "actuations"
                    ][-8:],
                }
                if broker.ring_rebalancer is not None
                else None
            ),
        }
    # r20 views block: hit rate, view-read latency, fold-dispatch
    # accounting, and the in-run bit-identity verify under watermark
    # advance — computed BEFORE teardown (the verify executes through
    # the live broker).
    views_block = None
    if views:
        from pixie_tpu.utils import flags

        vstat = broker.views.status()
        vh = view_hits[0]
        # Fold-dispatch accounting: views OFF, every completed request
        # launches one full fold over the table (the baseline cost the
        # serial phase paid per script). Views ON, only probe MISSES
        # fold at read time, plus maintenance ticks that actually read
        # new rows — a zero-delta tick on a static table reads nothing
        # and dispatches no fold. The one-time registration folds are
        # reported separately (amortized over the view's lifetime, not
        # a per-request cost).
        delta_folds = sum(
            1
            for vid, v in broker.views._views.items()
            if v.rows_folded > vrows0.get(vid, 0)
        )
        folds_on = (completed[0] - vh) + delta_folds
        vlat = sorted(view_latencies)

        def vpct(p: float) -> float:
            if not vlat:
                return 0.0
            return vlat[min(len(vlat) - 1, int(p * len(vlat)))]

        # In-run bit-identity verify under watermark advance: append a
        # delta, wait for maintenance to fold it (every watermark
        # reaches the new end), then check EVERY panel script's
        # view-served read against a from-scratch execution — values
        # AND group emission order, sketches included.
        extra = 5000
        vr = np.random.default_rng(seed + 7)
        t.write_pydict(
            {
                "time_": np.arange(rows, rows + extra, dtype=np.int64)
                * 1000,
                "service": vr.choice(
                    [f"svc-{i}" for i in range(8)], extra
                ).astype(object),
                "resp_status": vr.choice([200, 404, 500], extra),
                "latency": np.floor(vr.exponential(3e7, extra)),
            }
        )
        end = t.end_row_id()
        deadline = time.time() + 30
        while time.time() < deadline and any(
            v.watermark < end for v in broker.views._views.values()
        ):
            time.sleep(0.05)
        post_ok = all(
            v.watermark >= end for v in broker.views._views.values()
        )
        for q in queries:
            rv = broker.execute_script(q, timeout_s=120, tenant="verify")
            flags.set("materialized_views", False)
            try:
                rs = broker.execute_script(
                    q, timeout_s=120, tenant="verify"
                )
            finally:
                flags.set("materialized_views", True)
            post_ok = (
                post_ok
                and rv.view is not None
                and _tables_equal(_table_key(rv), _table_key(rs))
            )
        staleness_vals = [
            s["staleness_s"]
            for s in vstat["views"]
            if s.get("staleness_s") is not None
        ]
        views_block = {
            "queries": len(queries),
            "hits": int(vh),
            "misses": int(completed[0] - vh),
            "hit_rate": (
                round(vh / completed[0], 4) if completed[0] else None
            ),
            "read_p50_ms": round(vpct(0.50) * 1e3, 2),
            "read_p99_ms": round(vpct(0.99) * 1e3, 2),
            "registration_folds": len(queries),
            "maintenance_delta_folds": int(delta_folds),
            "fold_dispatches_views_on": int(folds_on),
            "fold_dispatches_views_off": int(completed[0]),
            "fold_dispatch_reduction_x": round(
                completed[0] / max(1, folds_on), 2
            ),
            "post_append_bit_identical": bool(post_ok),
            "max_staleness_s": (
                round(max(staleness_vals), 3) if staleness_vals else None
            ),
        }
    broker.stop()
    for a in agents:
        a.stop()

    d1, s1 = dispatches.value() - d0, saved.value() - s0
    fold_queries = d1 + s1  # queries that reached the fold path
    # r16: the batch-width distribution of THIS phase's dispatches.
    # Widths are integers landing exactly on bucket bounds, so the
    # quantile reads bucket UPPER edges (no sub-integer interpolation).
    w_delta = [
        c - p for c, p in zip(width_h.merged_counts(), w0_counts)
    ]

    def width_pct(q: float) -> float:
        total = sum(w_delta)
        if not total:
            return 0.0
        edges = list(width_h.buckets) + [width_h.buckets[-1] * 2]
        cum = 0
        for edge, cnt in zip(edges, w_delta):
            cum += cnt
            if cum >= q * total:
                return float(edge)
        return float(edges[-1])

    lat = sorted(latencies)

    def pct(p: float) -> float:
        if not lat:
            return 0.0
        return lat[min(len(lat) - 1, int(p * len(lat)))]

    report = {
        "clients": clients,
        "requests_per_client": requests_per_client,
        "qps_per_client": qps_per_client,
        "wall_s": round(wall, 2),
        "completed": completed[0],
        "rejected": rejected[0],
        "degraded": degraded[0],
        "bit_identical": mismatches[0] == 0,
        "queries_per_sec": round(completed[0] / wall, 1) if wall else 0,
        "latency_p50_ms": round(pct(0.50) * 1e3, 2),
        "latency_p99_ms": round(pct(0.99) * 1e3, 2),
        "shared_scan": {
            "fold_queries": int(fold_queries),
            "dispatches": int(d1),
            "saved": int(s1),
            "dispatch_reduction_x": (
                round(fold_queries / d1, 2) if d1 else None
            ),
            "mean_batch": (
                round(fold_queries / d1, 2) if d1 else None
            ),
            # r16: predicate-batched scan width (distinct predicate
            # slots per dispatch) — the new headline serving metric.
            "batch_width_p50": width_pct(0.5),
            "batch_width_p99": width_pct(0.99),
            "predicate_batched_queries": int(
                pred_batched.value() - pb0
            ),
            "window_skips": int(window_skips.value() - ws0),
        },
        "residency": {
            "peak_staged_bytes": int(peak[0]),
            "budget_bytes": hbm_budget_mb << 20,
            "within_budget": peak[0] <= (hbm_budget_mb << 20),
            "evictions": int(evictions.total()),
        },
        "admission": broker.admission.snapshot(),
        # Lock contention at depth (r13, the r12 follow-on profiling
        # item): admission queue/lock waits + bus publish lock waits —
        # the two serialization points every concurrent query crosses.
        "contention": {
            "admission_wait_p50_ms": round(
                reg.histogram("admission_wait_seconds").agg_quantile(0.5)
                * 1e3, 3,
            ),
            "admission_wait_p99_ms": round(
                reg.histogram("admission_wait_seconds").agg_quantile(0.99)
                * 1e3, 3,
            ),
            "admission_lock_wait_p99_ms": round(
                reg.histogram("admission_lock_wait_seconds").agg_quantile(
                    0.99
                ) * 1e3, 3,
            ),
            "bus_lock_wait_p99_ms": round(
                reg.histogram("bus_lock_wait_seconds").agg_quantile(0.99)
                * 1e3, 3,
            ),
        },
    }
    if placement_block is not None:
        report["placement"] = placement_block
    if views_block is not None:
        report["views"] = views_block
    if profile_block is not None:
        report["profile"] = profile_block
    if controller_status is not None:
        # r16: the closed-loop controller's actuation trail — what it
        # moved, from what, why, on which window signals.
        report["controller"] = controller_status
    if chaos:
        # r17: with fragment failover ON under live injection —
        # including the owner agent dying outright — the bar is ZERO
        # degraded results: every completed query is bit-identical to
        # the unfaulted baseline, and the broker's retry counter proves
        # failover (not luck) carried the faulted ones.
        report["contention"]["chaos"] = {
            "sites": {
                site: {"checks": c, "fired": f}
                for site, (c, f) in sorted((chaos_stats or {}).items())
            },
            "recovered": completed[0] - degraded[0] - mismatches[0],
            "degraded": degraded[0],
            "rejected": rejected[0],
            "mismatched": mismatches[0],
            "failover": {
                "fragment_retries": int(retries_c.total() - r0),
                "recovered_queries": int(recovered_c.total() - rec0),
                "hedge_both_complete": int(wasted_c.total() - w0),
            },
        }
        if mesh_chaos:
            # r23 mesh phase verdict: the host kill degraded geometry
            # (counter moved) and BOTH executors finished the run back
            # on their full configured geometry — recovery was internal
            # to the executor, invisible to the broker's accounting.
            report["contention"]["chaos"]["mesh"] = {
                "degrade_events": int(mesh_degrade_c.total() - md0),
                "owner": ex.mesh_recovery_snapshot(),
                "replica": ex2.mesh_recovery_snapshot(),
            }
    return report


def record_fleet_detail(report: dict, agents: int, path: str = None) -> None:
    """Merge one fleet soak run into BENCH_DETAIL.json's ``fleet`` block,
    keyed by agent count (read-modify-write: the other recorded blocks
    survive). Once a 1-agent baseline and an N-agent run are both
    present, each multi-agent run gains ``qps_scaling_x`` — aggregate
    device capacity vs the baseline's. Scaling is measured at the
    per-agent device level because the simulated chips share one host
    core (the same reason the kernel benches report rows/s/chip):
    wall-clock QPS cannot show chip parallelism in-process, exclusive
    per-chip busy time can."""
    bd_path = path or os.path.join(REPO, "BENCH_DETAIL.json")
    with open(bd_path) as f:
        detail = json.load(f)
    pb = report.get("placement") or {}
    cap = pb.get("device_capacity") or {}
    fleet = detail.get("fleet") or {}
    runs = fleet.get("runs") or {}
    runs[str(agents)] = {
        "agents": agents,
        "clients": report["clients"],
        "requests_per_client": report["requests_per_client"],
        "completed": report["completed"],
        "degraded": report["degraded"],
        "bit_identical": report["bit_identical"],
        "qps_wall": report["queries_per_sec"],
        "placement_hit_rate": pb.get("hit_rate"),
        "decisions": pb.get("decisions"),
        "per_agent_share": pb.get("per_agent_share"),
        "balance_max_min": pb.get("balance_max_min"),
        "per_agent_capacity": cap.get("per_agent"),
        "aggregate_qps_capacity": cap.get("aggregate_qps_capacity"),
        "rebalancer": pb.get("rebalancer"),
    }
    base_cap = (runs.get("1") or {}).get("aggregate_qps_capacity")
    for k, r in runs.items():
        if k != "1" and base_cap:
            r["qps_scaling_x"] = round(
                (r.get("aggregate_qps_capacity") or 0.0) / base_cap, 2
            )
    fleet["runs"] = runs
    fleet["capacity_model"] = (
        "per-agent device capacity on a serialized device clock "
        "(offloads / exclusive busy seconds, summed across agents); "
        "in-sim chips share one host core, so scaling is measured at "
        "the chip level like the rows/s/chip kernel benches"
    )
    detail["fleet"] = fleet
    with open(bd_path, "w") as f:
        json.dump(detail, f, indent=1)
        f.write("\n")
    log(f"BENCH_DETAIL.json updated (fleet, agents={agents})")


def record_views_detail(report: dict, path: str = None) -> None:
    """Merge one --views soak run into BENCH_DETAIL.json's ``views``
    block (read-modify-write: the other recorded blocks survive). The
    headline numbers are the r20 acceptance pair — view hit rate and
    fold-dispatch reduction vs the views-off cost of one full fold per
    request — plus the in-run bit-identity verdict."""
    bd_path = path or os.path.join(REPO, "BENCH_DETAIL.json")
    with open(bd_path) as f:
        detail = json.load(f)
    vb = report.get("views") or {}
    detail["views"] = {
        "clients": report["clients"],
        "requests_per_client": report["requests_per_client"],
        "completed": report["completed"],
        "bit_identical": report["bit_identical"],
        "latency_p50_ms": report["latency_p50_ms"],
        "latency_p99_ms": report["latency_p99_ms"],
        **vb,
        "dispatch_model": (
            "views off: one full fold per request; views on: probe "
            "misses + maintenance ticks that read new rows (zero-delta "
            "ticks dispatch no fold); one-time registration folds "
            "reported separately, amortized over the view's lifetime"
        ),
    }
    with open(bd_path, "w") as f:
        json.dump(detail, f, indent=1)
        f.write("\n")
    log("BENCH_DETAIL.json updated (views)")


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Serving soak: N concurrent scripted clients "
        "through admission + shared scans + HBM residency. "
        "--clients 1000 is the r13 scale target; the report's "
        "'contention' block carries admission/bus lock waits at depth."
    )
    ap.add_argument(
        "--clients", type=int,
        default=int(os.environ.get("SOAK_CLIENTS", 64)),
    )
    ap.add_argument(
        "--requests", type=int,
        default=int(os.environ.get("SOAK_REQUESTS", 4)),
    )
    ap.add_argument(
        "--qps", type=float,
        default=float(os.environ.get("SOAK_QPS", 8.0)),
    )
    ap.add_argument(
        "--rows", type=int,
        default=int(os.environ.get("SOAK_ROWS", 100_000)),
    )
    ap.add_argument(
        "--hbm-budget-mb", type=int,
        default=int(os.environ.get("SOAK_HBM_BUDGET_MB", 64)),
    )
    ap.add_argument(
        "--window-ms", type=float,
        default=float(os.environ.get("SOAK_WINDOW_MS", 25.0)),
    )
    ap.add_argument(
        "--max-concurrent", type=int,
        default=int(os.environ.get("SOAK_MAX_CONCURRENT", 8)),
    )
    ap.add_argument(
        "--chaos", action="store_true",
        default=bool(int(os.environ.get("SOAK_CHAOS", "0"))),
        help="Arm serving/agent fault sites (CHAOS_SITES) — incl. "
        "killing the owner agent mid-query — through the concurrent "
        "phase, with r17 fragment failover ON and a replica agent in "
        "the cluster. The pass gate requires ZERO degraded results "
        "(every query bit-identical to the unfaulted baseline) and "
        "broker_fragment_retries_total > 0 (failover, not luck). "
        "Under a multi-axis geometry (PIXIE_TPU_MESH_AXES="
        "hosts:2,d:-1) a mesh phase also kills one simulated host "
        "mid-fold: the gate additionally requires "
        "mesh_degrade_events_total > 0 with both executors back on "
        "their full geometry (r23).",
    )
    ap.add_argument(
        "--profile", action="store_true",
        default=bool(int(os.environ.get("SOAK_PROFILE", "0"))),
        help="Run the r15 continuous profiler through the concurrent "
        "phase: query-attributed CPU stack samples plus device dispatch "
        "attribution land in the report's 'profile' block (top "
        "attributed stacks and programs, attribution percentages).",
    )
    ap.add_argument(
        "--agents", type=int,
        default=int(os.environ.get("SOAK_AGENTS", 1)),
        help="r18: data-plane agent count for the fleet workload "
        "(pem1 owns every table; pem2..pemN are replica-capable with "
        "their own executors at the same mesh geometry). Only "
        "meaningful with --fleet-tables > 0.",
    )
    ap.add_argument(
        "--fleet-tables", type=int,
        default=int(os.environ.get("SOAK_FLEET_TABLES", 0)),
        help="r18: switch to the fleet workload — this many hot "
        "tables (--rows rows EACH, ~2000-value dict key) with "
        "residency placement ON. With --agents > 1 the pass gate "
        "becomes the placement criteria: bit-identical completion, "
        "hit-rate >= 0.7, per-agent share spread <= 2x; --agents 1 is "
        "the thrash baseline (gated on completion/bit-identity only).",
    )
    ap.add_argument(
        "--views", action="store_true",
        default=bool(int(os.environ.get("SOAK_VIEWS", "0"))),
        help="r20: dashboard-repeat workload — the views_queries panel "
        "is registered as materialized views after the serial "
        "baselines, and clients re-run the panel scripts. View hits "
        "bypass admission entirely (the probe sits ABOVE the ladder). "
        "The pass gate becomes the view criteria: hit rate >= 0.9, "
        "fold-dispatch reduction >= 5x vs one-full-fold-per-request, "
        "every read bit-identical to the from-scratch baseline, and "
        "the post-append verify (delta folded via maintenance, view "
        "== scratch) passing.",
    )
    ap.add_argument(
        "--controller", action="store_true",
        default=bool(int(os.environ.get("SOAK_CONTROLLER", "0"))),
        help="Enable the r16 closed-loop admission controller for the "
        "run (flag admission_controller at a 0.5s tick): the report's "
        "'controller' block carries the actuation trail — which knobs "
        "moved, from what, why, on which window signals.",
    )
    args = ap.parse_args()
    report = run_soak(
        clients=args.clients,
        requests_per_client=args.requests,
        qps_per_client=args.qps,
        rows=args.rows,
        hbm_budget_mb=args.hbm_budget_mb,
        window_ms=args.window_ms,
        max_concurrent=args.max_concurrent,
        chaos=args.chaos,
        profile=args.profile,
        controller=args.controller,
        agents=args.agents,
        fleet_tables=args.fleet_tables,
        views=args.views,
    )
    print(json.dumps(report, indent=1))
    path = os.environ.get("SOAK_JSON")
    if path:
        with open(path, "w") as f:
            json.dump(report, f, indent=1)
    if os.environ.get("SOAK_WRITE_BENCH_DETAIL") == "1" and (
        args.fleet_tables > 0
    ):
        # r18 fleet mode records under ``fleet`` (keyed by agent count)
        # and must not clobber the standard workload's serving_soak
        # numbers.
        record_fleet_detail(report, args.agents)
    elif os.environ.get("SOAK_WRITE_BENCH_DETAIL") == "1" and args.views:
        # r20 views mode records under ``views``, alongside (not over)
        # the standard workload's serving_soak numbers.
        record_views_detail(report)
    elif os.environ.get("SOAK_WRITE_BENCH_DETAIL") == "1":
        # ROADMAP serving follow-on (1): the ~1k-client run's contention
        # + profile blocks are recorded next to the bench configs.
        bd_path = os.path.join(REPO, "BENCH_DETAIL.json")
        with open(bd_path) as f:
            detail = json.load(f)
        # r16: carry the superseded run's p50 so the ledger shows the
        # before/after (the r15 1k-client run's ~18s admission-pacing
        # p50 is the number predicate batching + the controller attack).
        prev = detail.get("serving_soak") or {}
        prev_p50 = prev.get("latency_p50_ms")
        prev_before = prev.get("previous_latency_p50_ms")
        detail["serving_soak"] = {
            k: report[k]
            for k in (
                "clients", "requests_per_client", "wall_s", "completed",
                "rejected", "degraded", "queries_per_sec",
                "latency_p50_ms", "latency_p99_ms", "contention",
                # r16: dispatch reduction + batch_width_p50/p99 — the
                # predicate-batching acceptance evidence.
                "shared_scan",
            )
            if k in report
        }
        if prev_p50 is not None and prev.get("clients") == report.get(
            "clients"
        ):
            detail["serving_soak"]["previous_latency_p50_ms"] = prev_p50
        elif prev_before is not None:
            detail["serving_soak"]["previous_latency_p50_ms"] = prev_before
        if "profile" in report:
            detail["serving_soak"]["profile"] = report["profile"]
        if "controller" in report:
            # r16: final knob values + the last actuations.
            ctl = dict(report["controller"])
            ctl["actuations"] = ctl.get("actuations", [])[-12:]
            detail["serving_soak"]["controller"] = ctl
        with open(bd_path, "w") as f:
            json.dump(detail, f, indent=1)
            f.write("\n")
        log("BENCH_DETAIL.json updated (serving_soak)")
    ok = report["bit_identical"] and report["residency"]["within_budget"]
    fleet = args.fleet_tables > 0
    if not args.chaos and not fleet and not args.views:
        # The dispatch-reduction bar is the NORMAL-mode gate; a chaos
        # run kills the owner executor mid-phase, splitting dispatches
        # across two devices — it gates on failover outcomes instead,
        # the fleet workload (solo per-table families) gates on the
        # placement criteria below, and the views workload on the view
        # criteria. The bar is also WORKLOAD-AWARE: shared scans can
        # only coalesce queries that CO-ARRIVE inside one window, and
        # with jittered arrivals the expected overlap scales with total
        # offered load — a small run (e.g. 4 clients x 4 requests,
        # ~1.3x observed) measures its own sparsity, not the engine, so
        # the 2.0x bar would fail by construction. Small runs gate on
        # bit-identity / residency / degraded only.
        total_requests = args.clients * args.requests
        if total_requests >= 128:
            ok = ok and (
                (report["shared_scan"]["dispatch_reduction_x"] or 0)
                >= 2.0
            )
        else:
            log(
                f"dispatch-reduction gate waived: {total_requests} "
                "total requests (< 128) offer no reliable co-arrival "
                "for the shared-scan window to coalesce"
            )
    if args.views:
        # r20 acceptance: dashboards read merged partial-agg state —
        # hit rate >= 0.9, >= 5x fewer fold dispatches than the
        # views-off one-fold-per-request cost, and the post-append
        # in-run verify (maintenance folded the delta; view-served
        # read == from-scratch fold, bit for bit) must pass.
        vb = report.get("views") or {}
        ok = ok and (vb.get("hit_rate") or 0.0) >= 0.9
        ok = ok and (vb.get("fold_dispatch_reduction_x") or 0.0) >= 5.0
        ok = ok and vb.get("post_append_bit_identical") is True
    if fleet:
        # r18 acceptance (multi-agent): every query bit-identical,
        # placement hit-rate >= 70% on the hot-table workload, and
        # every agent carried a share with max/min spread <= 2x. The
        # 1-agent run is the THRASH BASELINE — its hit rate is supposed
        # to be low — so it gates on completion/bit-identity only.
        pb = report.get("placement") or {}
        if args.agents > 1:
            ok = ok and (pb.get("hit_rate") or 0.0) >= 0.7
            ok = ok and len(pb.get("per_agent_share") or {}) == args.agents
            ok = ok and (pb.get("balance_max_min") or 99.0) <= 2.0
    if args.chaos:
        # r17 acceptance: with failover on, injected failures — incl.
        # the owner agent dying mid-query — must yield ZERO degraded
        # results (every query completes bit-identical), and the retry
        # counter must prove failover actually carried faulted queries.
        chaos_block = report["contention"]["chaos"]
        ok = (
            ok
            and report["degraded"] == 0
            and chaos_block["recovered"] > 0
            and chaos_block["failover"]["fragment_retries"] > 0
        )
        mesh_blk = chaos_block.get("mesh")
        if mesh_blk is not None:
            # r23 acceptance: under a multi-axis geometry
            # (PIXIE_TPU_MESH_AXES=hosts:2,d:-1) the armed host kill
            # must have actually degraded geometry (counter moved) AND
            # every executor must finish back on its full configured
            # geometry — zero degraded above already proved the
            # recovery was bit-identical.
            ok = ok and mesh_blk["degrade_events"] > 0
            for side in ("owner", "replica"):
                ok = ok and not mesh_blk[side]["degraded"]
    else:
        ok = ok and report["degraded"] == 0
    log(f"soak {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
