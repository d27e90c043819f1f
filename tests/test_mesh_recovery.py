"""Degraded-geometry mesh execution (r23): host loss mid-fold recovers
bit-identically.

The contract under test: losing a host of the multi-axis mesh mid-fold
is NOT a failure of the query — the executor walks a geometry
degradation ladder (hosts:4,d:2 -> hosts:2,d:4 -> d:8 -> host engine),
re-plans the SAME fold on the surviving rung, and the retried answer is
bit-for-bit the unfaulted one because every rung keeps the total device
count and the r21 invariant makes any factorization of the same device
set fold identically (values, sketch states, group emission order).
Window-boundary checkpoints (flag ``mesh_fold_checkpoint``) let a
mid-stream failure RESUME — only the windows after the last checkpoint
refold; a corrupt checkpoint is discarded and the fold restarts from
scratch, never resuming bad carry state. A hung collective is detected
by a watchdog deadline instead of hanging the query, and a per-geometry
circuit breaker routes repeat offenders straight to the surviving rung
until a cooldown admits the half-open trial back toward full geometry.

Every scenario drives the seeded r9 injection sites (``mesh.host_loss``,
``mesh.collective_timeout``, ``mesh.checkpoint_corrupt``) so nothing
here flakes on scheduling.
"""

import time

import numpy as np
import pytest

from pixie_tpu.distributed.mesh import MeshConfig, MeshGeometryError
from pixie_tpu.engine import Carnot
from pixie_tpu.parallel import MeshExecutor
from pixie_tpu.types import DataType, Relation
from pixie_tpu.utils import faults, flags, metrics_registry

F, I, S = DataType.FLOAT64, DataType.INT64, DataType.STRING


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


AGG_QUERY = (
    "df = px.DataFrame(table='http')\n"
    "df = df[df.status >= 1]\n"
    "g = df.groupby('service').agg("
    "n=('lat', px.count), s=('lat', px.sum),"
    " mn=('lat', px.min), mx=('lat', px.max),"
    " u=('service', px.approx_count_distinct),"
    " cm=('status', px.count_min))\n"
    "px.display(g, 'out')\n"
)


def _carnot(cfg, n=2048, nsvc=11, seed=7, integer_lat=False):
    ex = MeshExecutor(block_rows=256, mesh_config=cfg)
    carnot = Carnot(device_executor=ex)
    rel = Relation.of(("service", S), ("status", I), ("lat", F))
    t = carnot.table_store.create_table("http", rel)
    rng = np.random.default_rng(seed)
    t.write_pydict(
        {
            "service": np.array(
                [f"svc{i}" for i in rng.integers(0, nsvc, n)]
            ),
            "status": rng.integers(0, 5, n),
            # Integer-valued latencies when the test compares HOST vs
            # device rows (float sums exact regardless of reduction
            # order); mesh-rung-to-rung comparisons are bit-identical
            # even for irrational floats (the r21 invariant).
            "lat": (
                rng.integers(1, 100, n).astype(np.float64)
                if integer_lat
                else rng.standard_normal(n)
            ),
        }
    )
    return carnot, ex


def _fold(cfg, **kw):
    carnot, ex = _carnot(cfg, **kw)
    out = carnot.execute_query(AGG_QUERY).table("out")
    return out, ex


def _assert_same(a, b, ctx=""):
    assert list(a.keys()) == list(b.keys()), ctx
    for k in a:
        x, y = np.asarray(a[k]), np.asarray(b[k])
        # Values AND group emission order, sketch states included.
        assert np.array_equal(x, y), (ctx, k, x[:5], y[:5])


# -- the degradation ladder (pure geometry) ----------------------------------


def test_degrade_ladder_signatures():
    lad = MeshConfig.parse("hosts:4,d:2", 8).ladder()
    assert [
        c.signature() if c else "host" for c in lad
    ] == ["hosts:4,d:2", "hosts:2,d:4", "d:8", "host"]
    lad = MeshConfig.parse("hosts:2,d:4", 8).ladder()
    assert [
        c.signature() if c else "host" for c in lad
    ] == ["hosts:2,d:4", "d:8", "host"]
    # Flat geometry has no hosts to lose: the ladder is itself + host.
    lad = MeshConfig.flat(8).ladder()
    assert [c.signature() if c else "host" for c in lad] == ["d:8", "host"]
    # Every rung keeps the total device count (shape invariance is what
    # makes checkpoints and staged shards portable across rungs).
    for cfg in MeshConfig.parse("hosts:4,d:2", 8).ladder():
        if cfg is not None:
            assert cfg.total_devices == 8


def test_mesh_geometry_error_kinds():
    e = MeshGeometryError("host_loss", "h3 died")
    assert e.recoverable and e.kind == "host_loss"
    assert not MeshGeometryError("signature_mismatch").recoverable
    assert not MeshGeometryError("checkpoint_corrupt").recoverable
    assert MeshGeometryError("collective_timeout").recoverable
    with pytest.raises(AssertionError):
        MeshGeometryError("not_a_kind")


# -- host loss: rung-by-rung bit-identity ------------------------------------


def test_host_loss_recovers_bit_identical_one_rung():
    flat, _ = _fold(MeshConfig.flat(8))
    faults.arm("mesh.host_loss", count=1)
    out, ex = _fold(MeshConfig.parse("hosts:4,d:2", 8))
    assert not ex.fallback_errors, ex.fallback_errors
    _assert_same(flat, out, "hosts:4,d:2 -> hosts:2,d:4")
    snap = ex.mesh_recovery_snapshot()
    assert snap["geometry"] == "hosts:2,d:4"
    assert snap["degraded"] and snap["degrade_events"] == 1
    assert snap["recovered_folds"] >= 1


def test_host_loss_walks_the_whole_ladder():
    """Two consecutive host losses push the fold down two rungs to the
    flat mesh; the answer never changes."""
    flat, _ = _fold(MeshConfig.flat(8))
    faults.arm("mesh.host_loss", count=2)
    out, ex = _fold(MeshConfig.parse("hosts:4,d:2", 8))
    assert not ex.fallback_errors, ex.fallback_errors
    _assert_same(flat, out, "hosts:4,d:2 -> d:8")
    snap = ex.mesh_recovery_snapshot()
    assert snap["geometry"] == "d:8"
    assert snap["degrade_events"] == 2
    assert metrics_registry().counter(
        "mesh_degrade_events_total"
    ).total() >= 2


def test_collective_timeout_site_recovers_bit_identical():
    flat, _ = _fold(MeshConfig.flat(8))
    faults.arm("mesh.collective_timeout", count=1)
    out, ex = _fold(MeshConfig.parse("hosts:2,d:4", 8))
    assert not ex.fallback_errors, ex.fallback_errors
    _assert_same(flat, out, "hung collective -> d:8")
    assert ex.mesh_recovery_snapshot()["geometry"] == "d:8"


def test_geometry_restores_on_next_fold_after_transient():
    """A one-off host loss degrades ONE fold; the next fold starts back
    at the full geometry (the breaker is below threshold) and succeeds,
    clearing the degraded state."""
    carnot, ex = _carnot(MeshConfig.parse("hosts:2,d:4", 8))
    flat, _ = _fold(MeshConfig.flat(8))
    faults.arm("mesh.host_loss", count=1)
    out1 = carnot.execute_query(AGG_QUERY).table("out")
    faults.reset()
    assert ex.mesh_recovery_snapshot()["degraded"]
    out2 = carnot.execute_query(AGG_QUERY).table("out")
    assert not ex.fallback_errors, ex.fallback_errors
    _assert_same(flat, out1, "degraded fold")
    _assert_same(flat, out2, "restored fold")
    snap = ex.mesh_recovery_snapshot()
    assert snap["geometry"] == "hosts:2,d:4" and not snap["degraded"]
    assert snap["breaker"] == {}  # success closed it


def test_warm_staged_cache_repartitions_onto_the_new_rung():
    """The second (warm) query's staged blocks were committed on the
    FULL mesh; after a mid-warm-fold host loss the retry on the flat
    rung must repartition them onto the surviving mesh — still
    bit-identical, no host fallback."""
    carnot, ex = _carnot(MeshConfig.parse("hosts:2,d:4", 8))
    flat, _ = _fold(MeshConfig.flat(8))
    out_cold = carnot.execute_query(AGG_QUERY).table("out")
    faults.arm("mesh.host_loss", count=1)
    out_warm = carnot.execute_query(AGG_QUERY).table("out")
    faults.reset()
    assert not ex.fallback_errors, ex.fallback_errors
    _assert_same(flat, out_cold, "cold")
    _assert_same(flat, out_warm, "warm across repartition")
    snap = ex.mesh_recovery_snapshot()
    assert snap["degraded"] and snap["geometry"] == "d:8"
    # And a THIRD query folds warm on the degraded rung without new
    # degrade events.
    out3 = carnot.execute_query(AGG_QUERY).table("out")
    _assert_same(flat, out3, "warm on degraded rung")


# -- window checkpoints: resume, not refold ----------------------------------


def test_host_kill_at_every_window_boundary_resumes(flagset):
    """Kill the host at EVERY stream-window boundary (and past the last
    window, at the merge): the resumed fold adopts the last checkpoint,
    refolds only the later windows, and stays bit-identical."""
    flagset("streaming_window_rows", 512)
    n_windows = 4  # 2048 rows / 512
    flat, _ = _fold(MeshConfig.flat(8))
    for boundary in range(n_windows + 1):
        faults.arm("mesh.host_loss", count=1, after=boundary)
        out, ex = _fold(MeshConfig.parse("hosts:2,d:4", 8))
        faults.reset()
        assert not ex.fallback_errors, ex.fallback_errors
        _assert_same(flat, out, f"killed at window boundary {boundary}")
        snap = ex.mesh_recovery_snapshot()
        assert snap["degrade_events"] == 1, boundary
        assert snap["checkpoints_held"] == 0, "must not outlive the fold"
        if boundary == 0:
            # Died before any window folded: nothing to resume.
            assert snap["checkpoint_resumes"] == 0
            assert ex.last_resume_stats is None
        else:
            assert snap["checkpoint_resumes"] == 1, boundary
            assert ex.last_resume_stats == {
                "resumed_from_window": boundary,
                "refolded_windows": n_windows - boundary,
                "total_windows": n_windows,
            }


def test_mid_window_timeout_resumes_from_last_checkpoint(flagset):
    """A collective that hangs MID-window (fold dispatched, never
    completed) resumes from the last completed window's checkpoint —
    the half-folded window refolds in full on the new rung."""
    flagset("streaming_window_rows", 512)
    flat, _ = _fold(MeshConfig.flat(8))
    faults.arm("mesh.collective_timeout", count=1, after=2)
    out, ex = _fold(MeshConfig.parse("hosts:2,d:4", 8))
    assert not ex.fallback_errors, ex.fallback_errors
    _assert_same(flat, out, "mid-window hang")
    assert ex.last_resume_stats == {
        "resumed_from_window": 2,
        "refolded_windows": 2,
        "total_windows": 4,
    }


def test_corrupt_checkpoint_discards_and_refolds(flagset):
    """Acceptance: a corrupt checkpoint is discarded — the resumed fold
    restarts from window 0 on the new rung (never resurrects bad carry
    state) and the answer is still bit-identical."""
    flagset("streaming_window_rows", 512)
    flat, _ = _fold(MeshConfig.flat(8))
    faults.arm("mesh.host_loss", count=1, after=2)
    faults.arm("mesh.checkpoint_corrupt", count=1)
    out, ex = _fold(MeshConfig.parse("hosts:2,d:4", 8))
    assert faults.stats()["mesh.checkpoint_corrupt"][1] == 1, (
        "the resume path must have consulted (and corrupted) the "
        "checkpoint"
    )
    faults.reset()
    assert not ex.fallback_errors, ex.fallback_errors
    _assert_same(flat, out, "refold after corrupt checkpoint")
    snap = ex.mesh_recovery_snapshot()
    assert snap["checkpoint_resumes"] == 0, "must NOT resume corrupt state"
    assert ex.last_resume_stats is None
    assert snap["checkpoints_held"] == 0


def test_checkpointing_off_still_recovers_by_refolding(flagset):
    flagset("streaming_window_rows", 512)
    flagset("mesh_fold_checkpoint", False)
    flat, _ = _fold(MeshConfig.flat(8))
    faults.arm("mesh.host_loss", count=1, after=2)
    out, ex = _fold(MeshConfig.parse("hosts:2,d:4", 8))
    assert not ex.fallback_errors, ex.fallback_errors
    _assert_same(flat, out, "refold with checkpointing off")
    snap = ex.mesh_recovery_snapshot()
    assert snap["checkpoint_windows"] == 0
    assert snap["checkpoint_resumes"] == 0


# -- collective watchdog -----------------------------------------------------


def test_watchdog_deadline_trips_on_hung_dispatch(flagset):
    flagset("mesh_dispatch_timeout_s", 0.05)
    ex = MeshExecutor(
        block_rows=256, mesh_config=MeshConfig.parse("hosts:2,d:4", 8)
    )
    with pytest.raises(MeshGeometryError) as ei:
        ex._mesh_dispatch(lambda: time.sleep(0.6) or 7, what="test")
    assert ei.value.kind == "collective_timeout"
    # A fast dispatch sails through the same deadline.
    assert ex._mesh_dispatch(lambda: 7, what="test") == 7


def test_watchdog_disabled_paths(flagset):
    # Negative flag disables the watchdog outright.
    flagset("mesh_dispatch_timeout_s", -1.0)
    ex = MeshExecutor(
        block_rows=256, mesh_config=MeshConfig.parse("hosts:2,d:4", 8)
    )
    assert ex._watchdog_deadline() is None
    assert ex._mesh_dispatch(lambda: time.sleep(0.06) or 3) == 3
    # Flat meshes have no cross-host collectives: no watchdog even with
    # an aggressive deadline (and no fault-site checks either).
    flagset("mesh_dispatch_timeout_s", 0.01)
    ex_flat = MeshExecutor(block_rows=256, mesh_config=MeshConfig.flat(8))
    faults.arm("mesh.host_loss", count=1)
    assert ex_flat._mesh_dispatch(lambda: time.sleep(0.05) or 5) == 5
    assert faults.stats()["mesh.host_loss"][0] == 0, (
        "flat mesh must not even check the host-loss site"
    )


SIG = "fold|mesh:hosts:2,d:4|x"
OTHER = "bfold|mesh:hosts:2,d:4|y"


@pytest.mark.parametrize(
    "flag, walls, want",
    [
        (0.0, [], None),
        (0.0, [(OTHER, 0.05)], None),
        (0.0, [(SIG, 0.05), (SIG, 0.02)], 0.05 * 32.0),
        (0.0, [(SIG, 1e-4)], 0.25),
        (0.0, [(SIG, 0.01), (OTHER, 0.5)], 0.5 * 4.0),
        (2.5, [], 2.5),
        (-1.0, [(SIG, 0.05)], None),
    ],
    ids=[
        "cold", "cold-other-warm", "signature-slowest-wall", "floor",
        "overall-slowest-wall", "positive-flag-wins", "negative-flag-off",
    ],
)
def test_watchdog_deadline_from_completed_walls(flagset, flag, walls, want):
    """Flag 0 (the default): a signature that has completed a dispatch
    gets max(0.25 s, rail x its slowest wall, 4 x the slowest wall of
    any signature); a cold one gets no watchdog. A positive flag wins,
    a negative one turns the watchdog off."""
    flagset("mesh_dispatch_timeout_s", flag)
    flagset("mesh_watchdog_rail_factor", 32.0)
    ex = MeshExecutor(
        block_rows=256, mesh_config=MeshConfig.parse("hosts:2,d:4", 8)
    )
    for sig, wall in walls:
        ex._note_dispatch_wall(wall, sig)
    got = ex._watchdog_deadline(SIG)
    assert got == (None if want is None else pytest.approx(want))


def test_watchdog_deadline_arms_after_a_completed_dispatch(flagset):
    flagset("mesh_dispatch_timeout_s", 0.0)
    ex = MeshExecutor(
        block_rows=256, mesh_config=MeshConfig.parse("hosts:2,d:4", 8)
    )
    assert ex._watchdog_deadline(SIG) is None
    assert ex._mesh_dispatch(lambda: 7, what="test", fold_sig=SIG) == 7
    assert ex._watchdog_deadline(SIG) >= 0.25
    assert ex._watchdog_deadline(OTHER) is None


def test_watchdog_timeout_recovers_through_the_ladder(flagset, monkeypatch):
    """End-to-end: a genuinely HUNG first-rung dispatch (not an injected
    error) trips the watchdog deadline and the ladder recovers the fold
    bit-identically on the flat rung."""
    flat, _ = _fold(MeshConfig.flat(8))
    carnot, ex = _carnot(MeshConfig.parse("hosts:2,d:4", 8))
    flagset("mesh_dispatch_timeout_s", 0.2)
    orig = ex.__class__._watchdog_run
    hung = {"n": 0}

    def hang_once_on_full(self, deadline, fn, what):
        if self._mesh_sig == "hosts:2,d:4" and hung["n"] == 0:
            hung["n"] += 1
            return orig(
                self, deadline, lambda: time.sleep(deadline + 0.3) or fn(),
                what,
            )
        return orig(self, deadline, fn, what)

    monkeypatch.setattr(ex.__class__, "_watchdog_run", hang_once_on_full)
    out = carnot.execute_query(AGG_QUERY).table("out")
    assert not ex.fallback_errors, ex.fallback_errors
    assert hung["n"] == 1
    _assert_same(flat, out, "watchdog-detected hang")
    snap = ex.mesh_recovery_snapshot()
    assert snap["degrade_events"] >= 1 and snap["geometry"] == "d:8"


# -- per-geometry breaker ----------------------------------------------------


def _expire_breaker(ex, sig):
    """Rewind the breaker's cooldown clock (deterministic half-open,
    no wall-clock sleeps: a fold on the degraded rung can legitimately
    outlast any short real cooldown while it compiles)."""
    with ex._geom_lock:
        ex._geom_breaker[sig][1] = time.monotonic() - 0.01


def test_breaker_trips_skips_rung_and_half_open_recovers(flagset):
    """Acceptance: N consecutive geometry failures open the breaker —
    later folds skip straight to the surviving rung WITHOUT probing the
    dead geometry; the cooldown's expiry admits a half-open trial that
    restores full geometry on success."""
    flagset("mesh_breaker_threshold", 2)
    flagset("mesh_breaker_cooldown_s", 30.0)
    carnot, ex = _carnot(MeshConfig.parse("hosts:2,d:4", 8))
    flat, _ = _fold(MeshConfig.flat(8))

    for i in range(2):  # two consecutive host losses -> breaker opens
        faults.arm("mesh.host_loss", count=1)
        out = carnot.execute_query(AGG_QUERY).table("out")
        _assert_same(flat, out, f"failure {i}")
    faults.reset()
    br = ex.mesh_breaker_snapshot()["hosts:2,d:4"]
    assert br["state"] == "open" and br["failures"] == 2
    assert br["open_remaining_s"] > 0

    # Open: the full rung is skipped outright — the host-loss site is
    # never even checked (the fold starts on d:8).
    faults.arm("mesh.host_loss", p=0)  # census arming: counts checks only
    out = carnot.execute_query(AGG_QUERY).table("out")
    assert faults.stats()["mesh.host_loss"][0] == 0, (
        "open breaker must not dispatch on the dead geometry"
    )
    faults.reset()
    _assert_same(flat, out, "fold with breaker open")
    assert ex.mesh_recovery_snapshot()["geometry"] == "d:8"

    _expire_breaker(ex, "hosts:2,d:4")  # cooldown expires -> half-open
    assert ex.mesh_breaker_snapshot()["hosts:2,d:4"]["state"] == "half_open"
    out = carnot.execute_query(AGG_QUERY).table("out")  # trial succeeds
    assert not ex.fallback_errors, ex.fallback_errors
    _assert_same(flat, out, "half-open trial")
    snap = ex.mesh_recovery_snapshot()
    assert snap["geometry"] == "hosts:2,d:4" and not snap["degraded"]
    assert snap["breaker"] == {}, "trial success closes the breaker"


def test_breaker_reopens_on_failed_half_open_trial(flagset):
    flagset("mesh_breaker_threshold", 1)
    flagset("mesh_breaker_cooldown_s", 30.0)
    carnot, ex = _carnot(MeshConfig.parse("hosts:2,d:4", 8))
    flat, _ = _fold(MeshConfig.flat(8))
    faults.arm("mesh.host_loss", count=1)
    _assert_same(
        flat, carnot.execute_query(AGG_QUERY).table("out"), "trip"
    )
    _expire_breaker(ex, "hosts:2,d:4")
    faults.arm("mesh.host_loss", count=1)  # the half-open trial fails too
    _assert_same(
        flat, carnot.execute_query(AGG_QUERY).table("out"), "failed trial"
    )
    br = ex.mesh_breaker_snapshot()["hosts:2,d:4"]
    assert br["state"] == "open" and br["failures"] == 2


# -- structured errors + observability ---------------------------------------


def test_flat_rung_is_immune_to_host_loss_sites():
    """The flat rung has no hosts left to lose: even an UNLIMITED armed
    host-loss site cannot touch it (single-axis dispatches skip the
    mesh fault sites), so the ladder always terminates there with the
    bit-identical answer and exactly one degrade per multi-axis rung."""
    flat, _ = _fold(MeshConfig.flat(8))
    faults.arm("mesh.host_loss")  # unlimited: every multi-axis rung dies
    out, ex = _fold(MeshConfig.parse("hosts:4,d:2", 8))
    faults.reset()
    assert not ex.fallback_errors, ex.fallback_errors
    _assert_same(flat, out, "flat rung under unlimited host loss")
    snap = ex.mesh_recovery_snapshot()
    assert snap["geometry"] == "d:8"
    assert snap["degrade_events"] == 2  # hosts:4,d:2 and hosts:2,d:4


def test_exhausted_ladder_falls_back_to_host_bit_identical(monkeypatch):
    """Every mesh rung failing is still not a query failure: the ladder
    exhausts, the executor's host fallback runs the fragment, and the
    rows match (the r9 contract, now geometry-aware)."""
    flat, _ = _fold(MeshConfig.flat(8), integer_lat=True)
    carnot, ex = _carnot(MeshConfig.parse("hosts:4,d:2", 8), integer_lat=True)

    def die(*a, **k):
        raise MeshGeometryError("host_loss", "every geometry is gone")

    monkeypatch.setattr(ex, "_try_execute_fragment", die)
    out = carnot.execute_query(AGG_QUERY).table("out")
    assert ex.fallback_errors, "the host engine must have run this"
    assert any(
        "host_loss" in k for k in ex.fallback_errors
    ), ex.fallback_errors

    # Order-insensitive vs the device baseline: the host engine may emit
    # groups in a different order (the r9 fallback contract), but every
    # value — integer-exact sums included — must match.
    def rows(d):
        cols = sorted(d)
        return sorted(zip(*[np.asarray(d[c]).tolist() for c in cols]))

    assert rows(out) == rows(flat), "host fallback rows differ"
    assert ex.mesh_recovery_snapshot()["degrade_events"] == 3  # every rung


def test_health_snapshot_carries_mesh_section():
    _, ex = _fold(MeshConfig.parse("hosts:2,d:4", 8))
    mesh = ex.health_snapshot()["mesh"]
    assert mesh["geometry"] == "hosts:2,d:4"
    assert mesh["full_geometry"] == "hosts:2,d:4"
    assert not mesh["degraded"]
    assert mesh["ladder"] == ["hosts:2,d:4", "d:8", "host"]
