"""Residency-aware fleet placement suite (r18).

Pins the placement contracts:
- the admission scorer is deterministic: device residency (ring_hit)
  beats replica-ring coverage (replica_hit) beats the r11 fold-latency
  fallback beats the agent name, with span affinity and WFQ-weighted
  load breaking ties inside a rung;
- placement and r17 failover share ONE scorer: best_failover_candidate
  reproduces the r17 rank (role match, ownership, replica warmth, lag,
  name) on the same coverage function decide() uses;
- routing stays bit-identical when the placed agent dies mid-query —
  placement picks the owner at admission, the r17 reaper fails the
  fragment over, and the answer carries a recovered annotation with
  rows equal to the baseline;
- the ring rebalancer never exceeds the HBM rails (followers above
  ring_rebalance_high_pct of their advertised budget are skipped) and
  HOLDS on an empty heat window or replication factor 1 — no signal,
  no actuation — and publishes only on assignment CHANGE;
- a 2-agent fleet smoke: with residency_placement on, queries route to
  their owners, the decision counters/hit gauge/status section fill in,
  and inflight occupancy drains back to zero;
- r18 IN-lists: ``col in [..]`` lowers to the OR-of-equals the engine
  already executes, ``not in`` to AND-of-not-equals, and IN-heavy
  concurrent queries ride the predicate-batched fold's per-term LUT
  lanes bit-identically (the batched counter moves).
"""

import threading
import time

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from pixie_tpu.engine import Carnot
from pixie_tpu.exec import BridgeRouter
from pixie_tpu.parallel import MeshExecutor
from pixie_tpu.serving.placement import (
    OUTCOMES,
    PlacementPlane,
    RingRebalancer,
    agent_latency,
    best_failover_candidate,
    classify,
    coverage,
    eligible,
)
from pixie_tpu.table.row_batch import RowBatch
from pixie_tpu.table.table_store import TableStore
from pixie_tpu.types import DataType, Relation, SemanticType
from pixie_tpu.utils import faults, flags, metrics_registry
from pixie_tpu.vizier import Agent, MessageBus, QueryBroker
from pixie_tpu.vizier import agent as agent_mod
from pixie_tpu.vizier import broker as broker_mod

F, I, S, T = (
    DataType.FLOAT64,
    DataType.INT64,
    DataType.STRING,
    DataType.TIME64NS,
)


@pytest.fixture(scope="module")
def mesh():
    devs = np.array(jax.devices("cpu"))
    assert devs.size == 8, "conftest must provide 8 virtual devices"
    return Mesh(devs, ("d",))


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture
def flagset():
    saved = {}

    def set_(name, value):
        if name not in saved:
            saved[name] = flags.get(name)
        flags.set(name, value)

    yield set_
    for name, value in saved.items():
        flags.set(name, value)


def _agent(
    aid,
    tables=(),
    replica_tables=(),
    is_kelvin=False,
    staged=(),
    rings=(),
    replicas=None,
    used=0,
    budget=0,
):
    """A fake AgentTracker.failover_view() entry."""
    return {
        "agent_id": aid,
        "tables": frozenset(tables),
        "replica_tables": frozenset(replica_tables),
        "is_kelvin": is_kelvin,
        "health": {
            "residency": {
                "tables": list(staged),
                "used_bytes": used,
                "budget_bytes": budget,
            },
            "resident_ingest": list(rings),
            "replicas": replicas or {},
        },
    }


NEEDED = frozenset({"http_events"})


# -- scorer determinism ------------------------------------------------------


def test_coverage_classifies_the_residency_ladder():
    hot = _agent("a", tables=NEEDED, staged=["http_events"])
    ringy = _agent("b", tables=NEEDED, rings=["http_events"])
    warm = _agent(
        "c",
        replica_tables=NEEDED,
        replicas={"http_events": {"windows": 3, "lag": 1}},
    )
    cold = _agent("d", tables=NEEDED)
    assert classify(coverage(hot, NEEDED)) == "ring_hit"
    assert classify(coverage(ringy, NEEDED)) == "ring_hit"
    assert classify(coverage(warm, NEEDED)) == "replica_hit"
    assert classify(coverage(cold, NEEDED)) is None
    cov = coverage(warm, NEEDED)
    assert cov["hot"] == 3 and cov["lag"] == 1 and not cov["owned"]


def test_decide_residency_beats_replica_beats_cold():
    """The full outcome ladder on one view: staged residency wins over
    replica windows wins over no coverage at all."""
    plane = PlacementPlane()
    view = [
        _agent("pem3", tables=NEEDED),  # cold, alphabetically last
        _agent(
            "pem2",
            replica_tables=NEEDED,
            replicas={"http_events": {"windows": 2, "lag": 0}},
        ),
        _agent("pem1", tables=NEEDED, staged=["http_events"]),
        _agent("kelvin", tables=NEEDED, staged=["http_events"], is_kelvin=True),
    ]
    assert plane.decide(view, NEEDED) == ("pem1", "ring_hit")
    assert plane.decide(view[:2], NEEDED) == ("pem2", "replica_hit")
    assert plane.decide(view[:1], NEEDED) == ("pem3", "cold")
    # Kelvin never serves scans, a non-covering agent is ineligible.
    assert plane.decide([view[3], _agent("x")], NEEDED) == (None, None)
    assert plane.decide(view, frozenset()) == (None, None)


def test_decide_latency_beats_name():
    """Within the no-residency rung the r11 fold-latency view ranks:
    pem2's lower mean p50 beats pem1's alphabetical advantage."""
    plane = PlacementPlane()
    view = [_agent("pem1", tables=NEEDED), _agent("pem2", tables=NEEDED)]
    lat = {
        "progA": {
            "pem1": {"p50_ms": 50.0, "p99_ms": 80.0, "n": 9},
            "pem2": {"p50_ms": 5.0, "p99_ms": 9.0, "n": 9},
        }
    }
    assert agent_latency(lat) == {"pem1": 50.0, "pem2": 5.0}
    assert plane.decide(view, NEEDED, fold_latency=lat) == (
        "pem2",
        "latency_fallback",
    )
    # No latency history at all: name is the last tie-break.
    assert plane.decide(view, NEEDED) == ("pem1", "cold")


def test_decide_affinity_and_wfq_load_break_ties():
    plane = PlacementPlane()
    view = [_agent("pem1", tables=NEEDED), _agent("pem2", tables=NEEDED)]
    # Span affinity: the span's last placement wins the tie even though
    # pem2 loses the name tie-break.
    plane.commit("pem2", "cold", NEEDED)
    plane.release("pem2")
    assert plane.decide(view, NEEDED) == ("pem2", "cold")
    # WFQ load: pile weighted load onto pem2 via a DIFFERENT span (so
    # affinity doesn't apply) — the lighter agent takes the next query.
    other = frozenset({"other_table"})
    for _ in range(3):
        plane.commit("pem2", "cold", other, weight=0.5)  # cost 2.0 each
        plane.release("pem2")
    plane._affinity.pop(NEEDED)
    assert plane.decide(view, NEEDED) == ("pem1", "cold")


def test_failover_rank_is_the_r17_tuple():
    """best_failover_candidate on the shared scorer: role match first,
    then ownership, then replica warmth (windows), then lag, then name."""
    owner = _agent("z-owner", tables=NEEDED)
    warm = _agent(
        "a-warm",
        replica_tables=NEEDED,
        replicas={"http_events": {"windows": 5, "lag": 2}},
    )
    warmer = _agent(
        "b-warmer",
        replica_tables=NEEDED,
        replicas={"http_events": {"windows": 9, "lag": 7}},
    )
    kel = _agent("kelvin", tables=NEEDED, is_kelvin=True)
    view = [warm, warmer, owner, kel]
    # Ownership beats warmth; skip is honored; warmth beats name.
    assert best_failover_candidate(view, NEEDED, [], False) == "z-owner"
    assert (
        best_failover_candidate(view, NEEDED, ["z-owner"], False)
        == "b-warmer"
    )
    assert (
        best_failover_candidate(view, NEEDED, ["z-owner", "b-warmer"], False)
        == "a-warm"
    )
    # Role match outranks everything else.
    assert best_failover_candidate(view, NEEDED, [], True) == "kelvin"
    assert best_failover_candidate([warm], NEEDED, ["a-warm"], False) is None
    assert not eligible(_agent("none"), NEEDED)


def test_commit_release_status_and_metrics():
    plane = PlacementPlane()
    dec = metrics_registry().counter("broker_placement_decisions_total")
    before = dec.total()
    plane.commit("pem1", "ring_hit", NEEDED)
    plane.commit("pem1", "cold", frozenset({"b"}))
    plane.commit("pem2", "replica_hit", NEEDED)
    assert dec.total() == before + 3
    st = plane.status()
    assert set(st["decisions"]) == set(OUTCOMES)
    assert st["total"] == 3 and st["hit_rate"] == round(2 / 3, 4)
    assert st["per_agent"]["pem1"]["placed"] == 2
    assert st["per_agent"]["pem1"]["inflight"] == 2
    assert st["balance_max_min"] == 2.0
    assert st["table_heat"] == {"http_events": 2, "b": 1}
    plane.release("pem1")
    plane.release("pem1")
    plane.release("pem2")
    assert all(
        a["inflight"] == 0 for a in plane.status()["per_agent"].values()
    )
    # The heat window drains (rebalancer feed) but table_heat persists.
    assert plane.drain_heat() == {"http_events": 2, "b": 1}
    assert plane.drain_heat() == {}
    assert plane.status()["table_heat"] == {"http_events": 2, "b": 1}


# -- ring rebalancer rails ---------------------------------------------------


def _rebalancer(view, heat, published):
    return RingRebalancer(
        publish=published.append,
        view_fn=lambda: view,
        heat_fn=lambda: dict(heat),
    )


def test_rebalancer_holds_on_empty_heat_and_factor_one(flagset):
    published = []
    view = [_agent("pem2", replica_tables=NEEDED)]
    flagset("ring_replication_factor", 2)
    rb = _rebalancer(view, {}, published)
    assert rb.tick() == []  # empty heat window: hold
    flagset("ring_replication_factor", 1)
    rb2 = _rebalancer(view, {"http_events": 10}, published)
    assert rb2.tick() == []  # factor 1: no followers to place
    assert published == []
    assert rb.status()["assignments"] == {}


def test_rebalancer_never_exceeds_hbm_rail(flagset):
    """A follower above high_pct of its advertised HBM budget is never
    assigned; one with headroom (or an unlimited pool) is."""
    flagset("ring_replication_factor", 3)  # up to 2 followers
    flagset("ring_rebalance_high_pct", 0.9)
    full = _agent(
        "pem-full", replica_tables=NEEDED, used=95, budget=100
    )
    roomy = _agent(
        "pem-roomy", replica_tables=NEEDED, used=10, budget=100
    )
    unlimited = _agent("pem-unlim", replica_tables=NEEDED, used=10**9)
    leader = _agent("pem-owner", tables=NEEDED)  # leaders replicate out
    published = []
    rb = _rebalancer(
        [full, roomy, unlimited, leader], {"http_events": 7}, published
    )
    (move,) = rb.tick()
    followers = rb.status()["assignments"]["http_events"]
    assert "pem-full" not in followers and "pem-owner" not in followers
    assert sorted(followers) == ["pem-roomy", "pem-unlim"]
    assert move["knob"] == "replica_assign:http_events"
    assert move["reason"] == "query_heat"
    assert move["signals"] == {"heat": 7, "candidates": 2}
    (msg,) = published
    assert msg["type"] == "ring_replica_assign"
    assert msg["table"] == "http_events"
    assert sorted(msg["followers"]) == ["pem-roomy", "pem-unlim"]
    rails = rb.status()["rails"]
    assert rails == {"replication_factor": 3, "high_pct": 0.9}


def test_rebalancer_publishes_only_on_change(flagset):
    flagset("ring_replication_factor", 2)
    published = []
    view = [
        _agent("pem2", replica_tables=NEEDED, used=1, budget=100),
        _agent("pem3", replica_tables=NEEDED, used=2, budget=100),
    ]
    rb = _rebalancer(view, {"http_events": 5}, published)
    moves = metrics_registry().counter("broker_ring_rebalance_moves_total")
    m0 = moves.total()
    assert len(rb.tick()) == 1  # first assignment: pem2 (least used)
    assert rb.status()["assignments"]["http_events"] == ["pem2"]
    assert rb.tick() == []  # same heat, same pick: no re-publish
    assert len(published) == 1 and moves.total() == m0 + 1
    # The follower fills up past the rail: the assignment MOVES.
    view[0]["health"]["residency"]["used_bytes"] = 99
    (move,) = rb.tick()
    assert move["from"] == ["pem2"] and move["to"] == ["pem3"]
    assert len(published) == 2
    assert rb.status()["actuations"][-1]["to"] == ["pem3"]


# -- placement + failover interplay (bit-identical under a kill) -------------

REL = Relation.of(("time_", T), ("service", S), ("latency", F))
TABLES = {"http_events": REL}

AGG_QUERY = (
    "df = px.DataFrame(table='http_events')\n"
    "stats = df.groupby(['service']).agg(\n"
    "    total=('latency', px.sum), n=('latency', px.count))\n"
    "px.display(stats, 'out')\n"
)


def _make_store(n=2000):
    rng = np.random.default_rng(7)
    ts = TableStore()
    t = ts.create_table("http_events", REL)
    t.write_pydict(
        {
            "time_": np.arange(n),
            "service": rng.choice(["a", "b", "c"], n).astype(object),
            # Integer-valued latencies: float sums are exact regardless
            # of reduction order, so retried rows compare bit-equal.
            "latency": rng.integers(1, 100, n).astype(np.float64),
        }
    )
    t.stop()
    return ts


def _sorted_rows(res, name="out"):
    batches = [b for b in res.tables.get(name, []) if b.num_rows]
    if not batches:
        return []
    d = RowBatch.concat(batches).to_pydict()
    cols = sorted(d)
    return sorted(zip(*[d[c] for c in cols]))


def _wait_agents(broker, count, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if len(broker.tracker.distributed_state().agents) >= count:
            return
        time.sleep(0.02)
    pytest.fail(f"{count} agents never registered")


@pytest.fixture
def placed_cluster(monkeypatch, flagset):
    """The r17 failover topology with r18 placement ROUTING ON: pem1
    owns http_events, pem2 is a replica agent over the same store,
    kelvin merges. The flag must be set before the broker exists (the
    placement plane is constructed in __init__)."""
    monkeypatch.setattr(agent_mod, "HEARTBEAT_INTERVAL_S", 0.05)
    flagset("fragment_failover", True)
    flagset("residency_placement", True)
    store = _make_store()
    bus = MessageBus()
    router = BridgeRouter()
    broker = QueryBroker(bus, router, table_relations=TABLES)
    assert broker.placement is not None
    agents = [
        Agent("pem1", bus, router, table_store=store),
        Agent("pem2", bus, router, table_store=store, owned_tables=[]),
        Agent("kelvin", bus, router, is_kelvin=True),
    ]
    for a in agents:
        a.start()
    _wait_agents(broker, 3)
    yield broker, agents
    broker.stop()
    for a in agents:
        a.stop()


def test_placed_query_survives_agent_kill_bit_identical(
    placed_cluster, monkeypatch
):
    """Placement routes the scan to pem1 at admission; pem1 dies holding
    the fragment; the r17 reaper fails it over to pem2. The answer is
    FULL and bit-identical, carries a recovered annotation, and the
    placement plane recorded both decisions and drained its inflight."""
    broker, _ = placed_cluster
    monkeypatch.setattr(broker_mod, "AGENT_EXPIRY_S", 0.4)
    baseline_res = broker.execute_script(AGG_QUERY, timeout_s=30)
    assert baseline_res.degraded is None and baseline_res.recovered is None
    baseline = _sorted_rows(baseline_res)
    assert baseline, "baseline produced no rows"
    st0 = broker.placement.status()
    assert st0["per_agent"]["pem1"]["placed"] >= 1  # routed to the owner
    faults.arm("agent.kill_holding_fragment@pem1", count=1)
    res = broker.execute_script(AGG_QUERY, timeout_s=20)
    assert res.degraded is None, res.degraded
    assert res.recovered is not None
    (entry,) = res.recovered["retried"]
    assert entry["reason"] == "agent_lost"
    assert entry["from"] == "pem1" and entry["to"] == "pem2"
    assert _sorted_rows(res) == baseline
    st = broker.placement.status()
    assert st["total"] == st0["total"] + 1
    assert all(a["inflight"] == 0 for a in st["per_agent"].values())


def test_mesh_placed_query_survives_agent_kill_bit_identical(
    placed_cluster, monkeypatch
):
    """r23: the ``__mesh__`` placement rung joins the r17 failover path.
    A span too big for any single agent commits under the ``__mesh__``
    pseudo agent and plans across the fleet; an agent dying mid-query is
    then an ordinary r17 fragment failover — the result is FULL,
    bit-identical, and carries a recovered annotation, never a degraded
    one, and the ``__mesh__`` inflight accounting drains."""
    broker, _ = placed_cluster
    monkeypatch.setattr(broker_mod, "AGENT_EXPIRY_S", 0.4)
    # Force the mesh_fold outcome (the rung itself is pinned by
    # test_mesh_fold_rung_refuses_oversized_span): every query's span
    # exceeds every advertised HBM budget.
    monkeypatch.setattr(
        broker.placement, "decide", lambda *a, **k: (None, "mesh_fold")
    )
    baseline_res = broker.execute_script(AGG_QUERY, timeout_s=30)
    assert baseline_res.degraded is None and baseline_res.recovered is None
    baseline = _sorted_rows(baseline_res)
    assert baseline, "baseline produced no rows"
    st0 = broker.placement.status()
    assert st0["per_agent"]["__mesh__"]["placed"] >= 1
    assert st0["decisions"].get("mesh_fold", 0) >= 1
    faults.arm("agent.kill_holding_fragment@pem1", count=1)
    res = broker.execute_script(AGG_QUERY, timeout_s=20)
    assert res.degraded is None, res.degraded
    assert res.recovered is not None
    (entry,) = res.recovered["retried"]
    assert entry["reason"] == "agent_lost"
    assert entry["from"] == "pem1" and entry["to"] == "pem2"
    assert _sorted_rows(res) == baseline
    st = broker.placement.status()
    assert all(a["inflight"] == 0 for a in st["per_agent"].values())


# -- 2-agent fleet smoke -----------------------------------------------------

SMOKE_TABLES = {"events_a": REL, "events_b": REL}


def test_two_agent_placement_smoke(monkeypatch, flagset):
    """Fast fleet smoke for tier-1: two data-plane agents each owning
    one table, placement on — queries land on their owners, the
    decision counters/hit gauge move, and the status section exposes
    per-agent shares with zero residual inflight."""
    monkeypatch.setattr(agent_mod, "HEARTBEAT_INTERVAL_S", 0.05)
    flagset("residency_placement", True)
    store = TableStore()
    rng = np.random.default_rng(3)
    for name in SMOKE_TABLES:
        t = store.create_table(name, REL)
        t.write_pydict(
            {
                "time_": np.arange(300),
                "service": rng.choice(["a", "b"], 300).astype(object),
                "latency": rng.integers(1, 50, 300).astype(np.float64),
            }
        )
        t.stop()
    bus = MessageBus()
    router = BridgeRouter()
    broker = QueryBroker(bus, router, table_relations=SMOKE_TABLES)
    agents = [
        Agent("pem1", bus, router, table_store=store,
              owned_tables=["events_a"]),
        Agent("pem2", bus, router, table_store=store,
              owned_tables=["events_b"]),
        Agent("kelvin", bus, router, is_kelvin=True),
    ]
    for a in agents:
        a.start()
    try:
        _wait_agents(broker, 3)
        dec = metrics_registry().counter("broker_placement_decisions_total")
        before = dec.total()
        for name in ("events_a", "events_b", "events_a"):
            q = AGG_QUERY.replace("http_events", name)
            res = broker.execute_script(q, timeout_s=30)
            assert res.degraded is None, res.degraded
            assert _sorted_rows(res)
        assert dec.total() == before + 3
        st = broker.placement.status()
        assert st["per_agent"]["pem1"]["placed"] == 2
        assert st["per_agent"]["pem2"]["placed"] == 1
        assert all(
            a["inflight"] == 0 for a in st["per_agent"].values()
        )
        assert st["table_heat"] == {"events_a": 2, "events_b": 1}
        assert metrics_registry().gauge(
            "broker_placement_hit_rate"
        ).value() >= 0.0
    finally:
        broker.stop()
        for a in agents:
            a.stop()


# -- r18 IN-lists: compiler lowering + LUT-lane batching ---------------------

SERVE_REL = Relation.of(
    ("time_", T, SemanticType.ST_TIME_NS),
    ("service", S),
    ("resp_status", I),
    ("latency", F),
)


def _make_table(carnot, name="http_events", n=4000, seed=7):
    t = carnot.table_store.create_table(name, SERVE_REL)
    rng = np.random.default_rng(seed)
    t.write_pydict(
        {
            "time_": np.arange(n) * 10**6,
            "service": rng.choice(
                ["a", "b", "c"], n, p=[0.5, 0.3, 0.2]
            ).astype(object),
            "resp_status": rng.choice([200, 400, 500], n, p=[0.8, 0.1, 0.1]),
            "latency": rng.exponential(30.0, n),
        }
    )
    t.compact()
    t.stop()


def _pred_query(pred: str, names=("n", "total")) -> str:
    return (
        "df = px.DataFrame(table='http_events')\n"
        f"df = df[{pred}]\n"
        "s = df.groupby(['service']).agg(\n"
        f"    {names[0]}=('time_', px.count),\n"
        f"    {names[1]}=('latency', px.sum),\n"
        ")\n"
        "px.display(s, 'out')\n"
    )


def _assert_tables_identical(a, b):
    assert set(a) == set(b)
    for col in a:
        av, bv = np.asarray(a[col]), np.asarray(b[col])
        assert av.dtype == bv.dtype and np.array_equal(av, bv), col


def test_in_list_lowers_to_or_of_equals(mesh):
    ex = MeshExecutor(mesh=mesh, block_rows=1024)
    c = Carnot(device_executor=ex)
    _make_table(c)
    got = c.execute_query(
        _pred_query("df.resp_status in [200, 500]")
    ).table("out")
    want = c.execute_query(
        _pred_query("(df.resp_status == 200) | (df.resp_status == 500)")
    ).table("out")
    _assert_tables_identical(want, got)
    # String IN-lists compare in dictionary-code space like ==.
    got_s = c.execute_query(
        _pred_query("df.service in ['a', 'zzz-unseen']")
    ).table("out")
    want_s = c.execute_query(_pred_query("df.service == 'a'")).table("out")
    _assert_tables_identical(want_s, got_s)


def test_not_in_lowers_to_and_of_not_equals(mesh):
    ex = MeshExecutor(mesh=mesh, block_rows=1024)
    c = Carnot(device_executor=ex)
    _make_table(c)
    got = c.execute_query(
        _pred_query("df.resp_status not in [400, 500]")
    ).table("out")
    want = c.execute_query(
        _pred_query("df.resp_status == 200")  # statuses are {200,400,500}
    ).table("out")
    _assert_tables_identical(want, got)


def test_in_list_over_column_requires_nonempty_constants(mesh):
    ex = MeshExecutor(mesh=mesh, block_rows=1024)
    c = Carnot(device_executor=ex)
    _make_table(c)
    with pytest.raises(Exception, match="non-empty"):
        c.execute_query(_pred_query("df.resp_status in []"))


def test_in_list_queries_predicate_batch_bit_identical(mesh):
    """IN-heavy concurrent queries join ONE predicate batch via op-6
    LUT lanes and come back bit-identical to their serial baselines."""
    ex = MeshExecutor(mesh=mesh, block_rows=1024)
    c = Carnot(device_executor=ex)
    _make_table(c)
    queries = [
        _pred_query("df.resp_status in [200, 500]"),
        _pred_query("df.resp_status in [400, 500]", names=("cnt", "s")),
        _pred_query("df.service in ['a', 'c']"),
        _pred_query("df.resp_status not in [400]"),
        _pred_query("df.latency > 25.0"),  # mixes with non-IN terms
    ]
    serials = [c.execute_query(q).table("out") for q in queries]
    batched = metrics_registry().counter(
        "serving_shared_scan_predicate_batched_queries_total"
    )
    flags.set("shared_scans", True)
    flags.set("shared_scan_predicate_batching", True)
    flags.set("shared_scan_window_ms", 200.0)
    try:
        before = batched.value()
        results = [None] * len(queries)
        errors = []
        barrier = threading.Barrier(len(queries))

        def run(i):
            try:
                barrier.wait()
                results[i] = c.execute_query(queries[i]).table("out")
            except Exception as e:  # pragma: no cover - assertion aid
                errors.append(e)

        ts = [
            threading.Thread(target=run, args=(i,))
            for i in range(len(queries))
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not errors, errors
        for serial, got in zip(serials, results):
            _assert_tables_identical(serial, got)
        assert batched.value() > before  # a width>1 dispatch happened
        assert not ex.fallback_errors, ex.fallback_errors
    finally:
        flags.reset("shared_scan_window_ms")
        flags.reset("shared_scan_predicate_batching")
        flags.reset("shared_scans")


# -- r22: LUT-backed host-func predicates in the normalizer ------------------

LUT_REL = Relation.of(
    ("time_", T, SemanticType.ST_TIME_NS),
    ("service", S),
    ("blob", S),
    ("latency", F),
)


def _make_lut_table(carnot, name="lut_events", n=4000, seed=3):
    t = carnot.table_store.create_table(name, LUT_REL)
    rng = np.random.default_rng(seed)
    codes = rng.choice([200, 400, 500], n, p=[0.7, 0.2, 0.1])
    t.write_pydict(
        {
            "time_": np.arange(n) * 10**6,
            "service": rng.choice(["a", "b", "c"], n).astype(object),
            "blob": np.array(
                [f'{{"code": {int(k)}}}' for k in codes], dtype=object
            ),
            "latency": rng.exponential(30.0, n),
        }
    )
    t.compact()
    t.stop()


def _lut_query(pred, names=("n", "total")):
    return (
        "df = px.DataFrame(table='lut_events')\n"
        f"df = df[{pred}]\n"
        "s = df.groupby(['service']).agg(\n"
        f"    {names[0]}=('time_', px.count),\n"
        f"    {names[1]}=('latency', px.sum),\n"
        ")\n"
        "px.display(s, 'out')\n"
    )


def test_host_func_lut_predicate_device_solo(mesh):
    """A dict_compatible host func (pluck) in a FILTER traces on the
    device through its per-dictionary-value LUT — no host fallback."""
    ex = MeshExecutor(mesh=mesh, block_rows=1024)
    c = Carnot(device_executor=ex)
    _make_lut_table(c)
    got = c.execute_query(
        _lut_query("px.pluck_int64(df.blob, 'code') == 200")
    ).table("out")
    assert not ex.fallback_errors, ex.fallback_errors
    # Python-side truth: 0.7 of 4000 rows carry code 200.
    assert sum(got["n"]) == 2778


def test_host_func_lut_predicate_batch_bit_identical(mesh):
    """r22 normalizer carry-over: host-func predicates join the op-6
    predicate batch as kept-code membership terms and come back
    bit-identical to their serial baselines."""
    ex = MeshExecutor(mesh=mesh, block_rows=1024)
    c = Carnot(device_executor=ex)
    _make_lut_table(c)
    queries = [
        _lut_query("px.pluck_int64(df.blob, 'code') == 200"),
        _lut_query(
            "px.pluck_int64(df.blob, 'code') != 500", names=("cnt", "s")
        ),
        _lut_query("px.pluck_int64(df.blob, 'code') >= 400"),
        _lut_query("df.latency > 25.0"),  # mixes with non-LUT terms
    ]
    serials = [c.execute_query(q).table("out") for q in queries]
    batched = metrics_registry().counter(
        "serving_shared_scan_predicate_batched_queries_total"
    )
    flags.set("shared_scans", True)
    flags.set("shared_scan_predicate_batching", True)
    flags.set("shared_scan_window_ms", 200.0)
    try:
        before = batched.value()
        results = [None] * len(queries)
        errors = []
        barrier = threading.Barrier(len(queries))

        def run(i):
            try:
                barrier.wait()
                results[i] = c.execute_query(queries[i]).table("out")
            except Exception as e:  # pragma: no cover - assertion aid
                errors.append(e)

        ts = [
            threading.Thread(target=run, args=(i,))
            for i in range(len(queries))
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not errors, errors
        for serial, got in zip(serials, results):
            _assert_tables_identical(serial, got)
        assert batched.value() > before  # a width>1 dispatch happened
        assert not ex.fallback_errors, ex.fallback_errors
    finally:
        flags.reset("shared_scan_window_ms")
        flags.reset("shared_scan_predicate_batching")
        flags.reset("shared_scans")


def test_hedge_placement_and_admission_read_their_rules(monkeypatch):
    """The hedge delay is the ``hedge_quantile`` of the heartbeats'
    fold-latency view, however often it was read before; an agent with
    no latency history ranks ``cold``; admission carries no predicted
    seconds, only the staging-bytes estimate."""
    import types

    from pixie_tpu.serving.admission import AdmissionController

    monkeypatch.setattr(broker_mod, "fragment_program_key", lambda f: f)
    saved = flags.get("hedge_delay_ms"), flags.get("hedge_quantile")
    flags.set("hedge_delay_ms", 0.0)
    flags.set("hedge_quantile", 0.5)
    try:
        views = [
            {"pk1": {"a0": {"p50_ms": 100.0}, "a1": {"p50_ms": 40.0}}},
            {"pk1": {"a0": {"p50_ms": 4.0}}},
        ]
        for view in views:
            fake = types.SimpleNamespace(
                tracker=types.SimpleNamespace(fold_latency_view=lambda v=view: v)
            )
            plan = types.SimpleNamespace(fragments=["pk1"])
            want = max(st["p50_ms"] for st in view["pk1"].values()) / 1e3
            for _ in range(5):
                got = broker_mod.QueryBroker._hedge_delay_s(fake, plan)
                assert got == pytest.approx(want)
        empty = types.SimpleNamespace(
            tracker=types.SimpleNamespace(fold_latency_view=lambda: {})
        )
        assert broker_mod.QueryBroker._hedge_delay_s(empty, plan) is None
    finally:
        flags.set("hedge_delay_ms", saved[0])
        flags.set("hedge_quantile", saved[1])

    plane = PlacementPlane()
    view = [_agent("pem1", tables=NEEDED), _agent("pem2", tables=NEEDED)]
    lat = {"progA": {"pem2": {"p50_ms": 5.0, "p99_ms": 9.0, "n": 9}}}
    assert plane.decide(view[1:], NEEDED, fold_latency=lat) == (
        "pem2", "latency_fallback",
    )
    assert plane.decide(view[:1], NEEDED, fold_latency=lat) == ("pem1", "cold")
    # cold and latency_fallback share a rung: unmeasured pem1 ranks at
    # latency 0, not at a predicted one.
    assert plane.decide(view, NEEDED, fold_latency=lat) == ("pem1", "cold")

    ctl = AdmissionController(max_concurrent=2, max_queue=2, timeout_s=1.0)
    with pytest.raises(TypeError):
        ctl.acquire("t", estimated_bytes=0, estimated_seconds=1.0)
    ticket = ctl.acquire("t", estimated_bytes=1 << 20)
    assert not hasattr(ticket, "estimated_seconds")
    assert not any("predicted" in k for k in ctl.snapshot())
    ticket.release()
    assert ctl.snapshot()["active"] == 0
