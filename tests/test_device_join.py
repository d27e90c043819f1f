"""Device sort-merge join lane (r19) on the 8-virtual-device CPU mesh.

The contract under test: the device lane is BIT-IDENTICAL to the host
EquijoinNode for INNER/LEFT/RIGHT/OUTER across duplicate keys on both
sides, unmatched keys in both directions, string (dictionary-code) and
int keys, and ragged tails — and the planner falls back to the host
engine below the row gate, on unsupported shapes, and when the flag is
off.
"""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from pixie_tpu.engine import Carnot
from pixie_tpu.parallel import MeshExecutor
from pixie_tpu.types import DataType, Relation, SemanticType
from pixie_tpu.utils import flags

F, I, S, T = (
    DataType.FLOAT64,
    DataType.INT64,
    DataType.STRING,
    DataType.TIME64NS,
)

NL, NR = 5000, 3100  # not block-aligned: ragged padded tails on 8 devices


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


REL_L = Relation.of(
    ("time_", T, SemanticType.ST_TIME_NS),
    ("svc", S),
    ("code", I),
    ("lat", F),
)
REL_R = Relation.of(
    ("time_", T, SemanticType.ST_TIME_NS),
    ("svc2", S),
    ("code2", I),
    ("cost", F),
)


def _data(rng, n, keys, key_ints):
    return {
        "time_": np.arange(n, dtype=np.int64) * 10,
        # Duplicate keys on both sides + keys unique to each side.
        "svc": rng.choice(keys, n).astype(object),
        "code": rng.choice(key_ints, n),
        "lat": rng.normal(100.0, 10.0, n),
    }


def build_carnot(device_executor, nl=NL, nr=NR):
    rng = np.random.default_rng(7)
    c = Carnot(device_executor=device_executor)
    dl = _data(rng, nl, [f"s{i}" for i in range(18)], [1, 2, 3, 4, 99])
    dr = _data(rng, nr, [f"s{i}" for i in range(12, 30)], [2, 3, 4, 5, 77])
    tl = c.table_store.create_table("lhs", REL_L)
    if nl:
        tl.write_pydict(dl)
    tl.compact()
    tl.stop()
    tr = c.table_store.create_table("rhs", REL_R)
    if nr:
        tr.write_pydict(
            {
                "time_": dr["time_"],
                "svc2": dr["svc"],
                "code2": dr["code"],
                "cost": dr["lat"],
            }
        )
    tr.compact()
    tr.stop()
    return c


def _join_query(how, on=("svc", "svc2")):
    return (
        "l = px.DataFrame(table='lhs')\n"
        "r = px.DataFrame(table='rhs')\n"
        f"j = l.merge(r, how='{how}', left_on=['{on[0]}'],"
        f" right_on=['{on[1]}'], suffixes=['', '_r'])\n"
        "px.display(j, 'out')\n"
    )


def _canon(rows):
    """Order-insensitive canonical form: rows as sorted tuples."""
    names = sorted(rows)
    return sorted(zip(*[rows[n] for n in names])), names


def run_both(mesh, q, nl=NL, nr=NR):
    cd = build_carnot(MeshExecutor(mesh=mesh, block_rows=512), nl, nr)
    ch = build_carnot(None, nl, nr)
    res_d = cd.execute_query(q)
    res_h = ch.execute_query(q)
    assert not cd.device_executor.fallback_errors, (
        cd.device_executor.fallback_errors
    )
    return cd, res_d.table("out"), res_h.table("out")


@pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
def test_device_join_bit_identical_string_key(mesh, flagset, how):
    flagset("device_join_min_rows", 0)
    cd, rows_d, rows_h = run_both(mesh, _join_query(how))
    assert any(
        s.startswith("join|") for s in cd.device_executor._program_cache
    ), "join did not offload"
    canon_d, names = _canon(rows_d)
    canon_h, _ = _canon(rows_h)
    assert canon_d == canon_h
    assert len(canon_d) > 0
    if how in ("inner", "left"):
        # INNER/LEFT device row ORDER matches the host engine exactly
        # (probe-row-major matches, stable build order within key, then
        # unmatched build rows); the outer-probe variants interleave
        # unmatched probe rows per host probe batch, so only the
        # multiset is the contract there.
        for n in names:
            assert rows_d[n] == rows_h[n]


@pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
def test_device_join_bit_identical_int_key(mesh, flagset, how):
    flagset("device_join_min_rows", 0)
    cd, rows_d, rows_h = run_both(
        mesh, _join_query(how, on=("code", "code2"))
    )
    assert any(
        s.startswith("join|") for s in cd.device_executor._program_cache
    )
    assert _canon(rows_d)[0] == _canon(rows_h)[0]


def test_device_join_all_unmatched_outer(mesh, flagset):
    """Disjoint key spaces: OUTER output is both sides null-padded."""
    flagset("device_join_min_rows", 0)
    q = (
        "l = px.DataFrame(table='lhs')\n"
        "r = px.DataFrame(table='rhs')\n"
        "j = l.merge(r, how='outer', left_on=['code'], right_on=['time_'],"
        " suffixes=['', '_r'])\n"
        "px.display(j, 'out')\n"
    )
    cd, rows_d, rows_h = run_both(mesh, q)
    assert _canon(rows_d)[0] == _canon(rows_h)[0]
    assert len(rows_d["svc"]) == NL + NR


def test_device_join_empty_build_side_falls_back(mesh, flagset):
    """Zero-row build side: the lane declines (host hash join wins
    outright) and the host result comes back unchanged."""
    flagset("device_join_min_rows", 0)
    cd, rows_d, rows_h = run_both(mesh, _join_query("outer"), nl=0)
    assert not any(
        s.startswith("join|") for s in cd.device_executor._program_cache
    )
    assert _canon(rows_d)[0] == _canon(rows_h)[0]
    assert len(rows_d["svc"]) == NR


def test_device_join_row_gate_falls_back(mesh, flagset):
    """Below device_join_min_rows the join stays on the host engine."""
    flagset("device_join_min_rows", 1 << 18)
    cd, rows_d, rows_h = run_both(mesh, _join_query("inner"))
    assert not any(
        s.startswith("join|") for s in cd.device_executor._program_cache
    )
    assert _canon(rows_d)[0] == _canon(rows_h)[0]


def test_device_join_flag_off_falls_back(mesh, flagset):
    flagset("device_join", False)
    flagset("device_join_min_rows", 0)
    cd, rows_d, rows_h = run_both(mesh, _join_query("left"))
    assert not any(
        s.startswith("join|") for s in cd.device_executor._program_cache
    )
    assert _canon(rows_d)[0] == _canon(rows_h)[0]


@pytest.mark.parametrize("how", ["inner", "left", "outer"])
def test_device_join_prejoin_filter_pushdown(mesh, flagset, how):
    """r20: normalizable pre-join predicates no longer refuse — each
    side filters on the host (same FilterNode mask, same order) before
    staging, and the device merge runs on the filtered sides,
    bit-identical to the host plan."""
    flagset("device_join_min_rows", 0)
    q = (
        "l = px.DataFrame(table='lhs')\n"
        "r = px.DataFrame(table='rhs')\n"
        "l = l[l.code == 2]\n"
        "r = r[r.cost > 100.0]\n"
        f"j = l.merge(r, how='{how}', left_on=['svc'],"
        " right_on=['svc2'], suffixes=['', '_r'])\n"
        "px.display(j, 'out')\n"
    )
    cd, rows_d, rows_h = run_both(mesh, q)
    assert any(
        s.startswith("join|") for s in cd.device_executor._program_cache
    )
    canon_d = _canon(rows_d)
    canon_h = _canon(rows_h)
    assert canon_d[0] == canon_h[0]
    if how in ("inner", "left"):
        # Row-order exactness survives the pushdown for the ordered
        # variants (boolean-mask selection is stable).
        assert {k: list(v) for k, v in rows_d.items()} == {
            k: list(v) for k, v in rows_h.items()
        }


def test_device_join_prejoin_filter_unsupported_falls_back(mesh, flagset):
    """A pre-join predicate outside the normalizable class (column vs
    column) still refuses to the host engine, bit-identical."""
    flagset("device_join_min_rows", 0)
    q = (
        "l = px.DataFrame(table='lhs')\n"
        "r = px.DataFrame(table='rhs')\n"
        "r = r[r.cost > r.time_]\n"
        "j = l.merge(r, how='inner', left_on=['svc'], right_on=['svc2'],"
        " suffixes=['', '_r'])\n"
        "px.display(j, 'out')\n"
    )
    cd, rows_d, rows_h = run_both(mesh, q)
    assert not any(
        s.startswith("join|") for s in cd.device_executor._program_cache
    )
    assert _canon(rows_d)[0] == _canon(rows_h)[0]


def test_device_join_host_suffix_agg(mesh, flagset):
    """A non-decomposable suffix below the join (groupby quantiles is
    not in the join-agg decomposition set) runs on the host against the
    spliced device join batch."""
    flagset("device_join_min_rows", 0)
    q = (
        "l = px.DataFrame(table='lhs')\n"
        "r = px.DataFrame(table='rhs')\n"
        "j = l.merge(r, how='inner', left_on=['svc'], right_on=['svc2'],"
        " suffixes=['', '_r'])\n"
        "s = j.groupby(['svc']).agg(q=('cost', px.quantiles),"
        " n=('time_', px.count))\n"
        "px.display(s, 'out')\n"
    )
    cd, rows_d, rows_h = run_both(mesh, q)
    assert any(
        s.startswith("join|") for s in cd.device_executor._program_cache
    )
    assert _canon(rows_d)[0] == _canon(rows_h)[0]


def test_device_join_staged_sides_accounted(mesh, flagset):
    """Both staged sides land in the ResidencyPool with byte accounting,
    and a repeat query reuses them (no re-staging)."""
    flagset("device_join_min_rows", 0)
    cd = build_carnot(MeshExecutor(mesh=mesh, block_rows=512))
    q = _join_query("inner")
    cd.execute_query(q)
    pool = cd.device_executor._staged_cache
    tags = [k[6] for k, _v in pool.items() if isinstance(k, tuple)]
    assert any(":joindevL:" in t for t in tags)
    assert any(":joindevR:" in t for t in tags)
    n_programs = len(cd.device_executor._program_cache)
    cd.execute_query(q)
    assert len(cd.device_executor._program_cache) == n_programs
    assert not cd.device_executor.fallback_errors


def test_device_join_f64_payload_bit_exact(mesh, flagset):
    """f64 payload rides the device as int64 bit patterns (the TPU's
    emulated f64 changed low mantissa bits of gathered values): signs,
    -0.0, infinities, NaN, subnormals and full-precision values all come
    back with the host engine's exact bits."""
    flagset("device_join_min_rows", 0)
    special = np.array(
        [-0.0, np.inf, -np.inf, np.nan, 5e-324, -1.7976931348623157e308,
         19846197.675742373]
    )

    def build(ex):
        c = build_carnot(ex)
        rng = np.random.default_rng(11)
        n = 512
        lat = rng.normal(0.0, 1e6, n)
        lat[: special.size] = special
        svc = rng.choice([f"s{i}" for i in range(18)], n).astype(object)
        svc[: special.size] = "s12"  # a key the right side holds
        t = c.table_store.create_table("lhs2", REL_L)
        t.write_pydict(
            {
                "time_": np.arange(n, dtype=np.int64) * 10,
                "svc": svc,
                "code": rng.choice([1, 2, 3], n),
                "lat": lat,
            }
        )
        t.compact()
        t.stop()
        return c

    q = _join_query("inner").replace("table='lhs'", "table='lhs2'")
    cd = build(MeshExecutor(mesh=mesh, block_rows=512))
    rows_d = cd.execute_query(q).table("out")
    rows_h = build(None).execute_query(q).table("out")
    assert any(
        s.startswith("join|") for s in cd.device_executor._program_cache
    ), "join did not offload"
    assert not cd.device_executor.fallback_errors
    bits = lambda rows: np.asarray(rows["lat"], np.float64).view(np.int64)
    np.testing.assert_array_equal(bits(rows_d), bits(rows_h))
    assert set(special.view(np.int64)) <= set(bits(rows_d))


def _has_join_program(cd) -> bool:
    return any(
        s.startswith("join|") for s in cd.device_executor._program_cache
    )


@pytest.mark.parametrize(
    "offset, device", [(1, False), (0, True), (-1, True)],
    ids=["below", "at", "above"],
)
def test_device_join_gate_at_min_rows(mesh, flagset, offset, device):
    """The gate is the flag comparison: the device lane runs once the
    two sides' rows reach ``device_join_min_rows``, and the result is
    the host engine's either way."""
    nl, nr = 300, 200
    flagset("device_join_min_rows", nl + nr + offset)
    cd, rows_d, rows_h = run_both(mesh, _join_query("inner"), nl, nr)
    assert _has_join_program(cd) is device
    assert _canon(rows_d)[0] == _canon(rows_h)[0]


def test_device_join_flag_zero_forces_device_lane(mesh, flagset):
    """device_join_min_rows=0 means the device lane always, down to a
    70-row join."""
    flagset("device_join_min_rows", 0)
    cd, rows_d, rows_h = run_both(mesh, _join_query("inner"), 40, 30)
    assert _has_join_program(cd)
    assert _canon(rows_d)[0] == _canon(rows_h)[0]


def test_cost_routed_join_bit_identical_whichever_lane(mesh, flagset):
    """Each lane forced through ``device_join_min_rows``: the host lane
    just above the two sides' rows, the device lane at them; both
    return rows bit-identical to the host engine."""
    nl, nr = 900, 600
    q = _join_query("inner")
    want = _canon(build_carnot(None, nl, nr).execute_query(q).table("out"))
    for min_rows, device in ((nl + nr + 1, False), (nl + nr, True)):
        flagset("device_join_min_rows", min_rows)
        cd = build_carnot(MeshExecutor(mesh=mesh, block_rows=512), nl, nr)
        got = _canon(cd.execute_query(q).table("out"))
        assert _has_join_program(cd) is device
        assert not cd.device_executor.fallback_errors
        assert got == want
