"""Lane gates are rules of their inputs, not of the process's history.

The sort–compact gate reads the platform, the row count and the segment
count; the device-join gate reads ``device_join_min_rows``. Neither
moves after any number of timed dispatches of either lane, so one
process compiles the same programs as the next.
"""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from pixie_tpu.engine import Carnot
from pixie_tpu.ops import segment
from pixie_tpu.parallel import MeshExecutor
from pixie_tpu.types import DataType, Relation, SemanticType
from pixie_tpu.utils import flags

F, I, S, T = (
    DataType.FLOAT64,
    DataType.INT64,
    DataType.STRING,
    DataType.TIME64NS,
)
MIN = segment.SORTED_MIN_ROWS


@pytest.fixture(scope="module")
def mesh():
    devs = np.array(jax.devices("cpu"))
    assert devs.size == 8, "conftest must provide 8 virtual devices"
    return Mesh(devs, ("d",))


@pytest.fixture
def flagset():
    """flags.set with automatic restore."""
    saved = {}

    def set_(name, value):
        if name not in saved:
            saved[name] = flags.get(name)
        flags.set(name, value)

    yield set_
    for name, value in saved.items():
        flags.set(name, value)


GATE_CASES = [
    ("tpu", 2 * MIN, None, True),
    ("tpu", MIN, None, True),
    ("tpu", MIN - 1, None, False),
    ("tpu", 2 * MIN, 16, True),
    ("tpu", 2 * MIN, 2 * MIN // 4, True),
    ("tpu", 2 * MIN, 2 * MIN // 4 + 1, False),
    ("tpu", None, None, True),
    ("cpu", 8 * MIN, 16, False),
]


def _gate_table():
    with segment.platform_hint("tpu"):
        tpu = [segment.sorted_strategy(n, s) for _, n, s, _ in GATE_CASES]
    with segment.platform_hint("cpu"):
        cpu = [segment.sorted_strategy(n, s) for _, n, s, _ in GATE_CASES]
    return tpu, cpu


@pytest.mark.parametrize(
    "platform, n_rows, nseg, want",
    GATE_CASES,
    ids=[f"{p}-{n}-{s}" for p, n, s, _ in GATE_CASES],
)
def test_sort_compact_gate_rule(platform, n_rows, nseg, want):
    """Sorted on a TPU-class platform from SORTED_MIN_ROWS rows, unless
    the segments are more than a quarter of the rows; never on the CPU."""
    with segment.platform_hint(platform):
        assert segment.sorted_strategy(n_rows, nseg) is want


def _flows(c, n, seed):
    rel = Relation.of(
        ("time_", T, SemanticType.ST_TIME_NS), ("src", S), ("bytes", I)
    )
    t = c.table_store.create_table("flows", rel)
    rng = np.random.default_rng(seed)
    data = {
        "time_": np.arange(n) * 10**6,
        "src": rng.choice([f"s{i}" for i in range(40)], n).astype(object),
        "bytes": rng.integers(0, 1 << 20, n),
    }
    t.write_pydict(data)
    return t, data


_MAX_Q = (
    "df = px.DataFrame(table='flows')\n"
    "s = df.groupby(['src']).agg(hi=('bytes', px.max))\n"
    "px.display(s, 'out')\n"
)


def _time_both_sorted_lanes(mesh, n=50):
    """n restaging folds on each sort–compact lane, each lane forced;
    every answer is numpy's."""
    for forced in (True, False):
        segment.set_sorted_strategy(forced)
        try:
            c = Carnot(device_executor=MeshExecutor(mesh=mesh, block_rows=256))
            t, data = _flows(c, 2048, seed=int(forced))
            for i in range(n):
                more = {k: v[:64] for k, v in data.items()}
                more["time_"] = more["time_"] + (i + 1) * 10**9
                t.write_pydict(more)
                rows = c.execute_query(_MAX_Q).table("out")
                assert not c.device_executor.fallback_errors
                assert max(rows["hi"]) == int(data["bytes"].max())
        finally:
            segment.set_sorted_strategy(None)


def _join_tables(c, nl, nr):
    rng = np.random.default_rng(3)
    for name, key, n in (("lj", "k", nl), ("rj", "k2", nr)):
        rel = Relation.of(
            ("time_", T, SemanticType.ST_TIME_NS), (key, S), ("v", F)
        )
        t = c.table_store.create_table(name, rel)
        t.write_pydict(
            {
                "time_": np.arange(n) * 10,
                key: rng.choice([f"k{i}" for i in range(9)], n).astype(object),
                "v": rng.normal(0.0, 1.0, n),
            }
        )
        t.compact()
        t.stop()


_JOIN_Q = (
    "l = px.DataFrame(table='lj')\n"
    "r = px.DataFrame(table='rj')\n"
    "j = l.merge(r, how='inner', left_on=['k'], right_on=['k2'],"
    " suffixes=['', '_r'])\n"
    "px.display(j, 'out')\n"
)
NL, NR = 120, 80


def _join_lane(mesh) -> bool:
    """True when a fresh executor answers the join on the device."""
    c = Carnot(device_executor=MeshExecutor(mesh=mesh, block_rows=256))
    _join_tables(c, NL, NR)
    c.execute_query(_JOIN_Q)
    assert not c.device_executor.fallback_errors
    return any(
        s.startswith("join|") for s in c.device_executor._program_cache
    )


def _join_gates(mesh, flagset):
    out = []
    for min_rows in (NL + NR + 1, NL + NR):
        flagset("device_join_min_rows", min_rows)
        out.append(_join_lane(mesh))
    return out


def _time_both_join_lanes(mesh, flagset, n=50):
    for min_rows in (0, 1 << 30):  # device lane, then host lane
        flagset("device_join_min_rows", min_rows)
        c = Carnot(device_executor=MeshExecutor(mesh=mesh, block_rows=256))
        _join_tables(c, NL, NR)
        for _ in range(n):
            c.execute_query(_JOIN_Q)
        assert not c.device_executor.fallback_errors


@pytest.mark.parametrize("gate", ["sorted_lane", "device_join"])
def test_gate_unchanged_after_timed_dispatches(mesh, flagset, gate):
    """50 timed dispatches of each lane leave the gate where its rule
    puts it."""
    if gate == "sorted_lane":
        before = _gate_table()
        _time_both_sorted_lanes(mesh)
        assert _gate_table() == before
    else:
        before = _join_gates(mesh, flagset)
        assert before == [False, True]
        _time_both_join_lanes(mesh, flagset)
        assert _join_gates(mesh, flagset) == before
