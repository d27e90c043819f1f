"""Closed-loop admission control (r16): a controller, not a knob.

Ref posture: Monarch/GWP close the monitoring loop all the way to
actuation — the r15 attribution/SLO plane made this engine's serving
signals first-class (admission-wait quantiles on ``admission_wait_
seconds``, queue depth, per-dispatch device wall time in the
``device_dispatches`` ring, HBM residency snapshots), and this module
feeds them back into the three serving knobs the r15 1000-client soak
proved latency actually lives behind:

- ``admission_max_concurrent`` — MIMD (multiplicative increase ×2 /
  decrease ÷2) inside hard guard rails
  [``admission_controller_min_concurrent``,
  ``admission_controller_max_concurrent``]: raise while admitted
  queries spend more than ``admission_controller_wait_target_ms`` at
  p50 in the queue AND the residency pool has headroom; halve on HBM
  pressure (pinned past 90% of budget); decay one step toward the
  flag-default baseline when the engine idles far below target.
- ``shared_scan_window_ms`` — additive ±step within
  [0, ``admission_controller_max_window_ms``]: deepen the batching
  window while the queue has depth (a longer window widens
  predicate-batched scans, multiplying effective concurrency), shrink
  it when the queue drains (the leader-side queue-depth gate already
  skips an idle window entirely).
- ``hbm_budget_mb`` — raise 25% per window that saw ``hbm_budget``
  admission rejections, never past
  ``admission_controller_max_hbm_mb``; shrink 25% (never below the
  flag-default baseline) after a long stretch of <30% utilization.
  With no configured budget or no ceiling rail the controller refuses
  to touch HBM at all.

Stability contracts (test-pinned in tests/test_slo.py): an EMPTY
window — zero admitted queries, zero rejections — holds every knob
(signal absence is not evidence of idleness: the engine may be wedged
upstream); every actuation is clamped to its rails; and each change is
recorded on an actuation TRAIL (knob, from, to, reason, window
signals) surfaced at /statusz and by tools/soak_serving.py.

The loop rides the existing cron machinery exactly like the r15
SLOManager: one persisted ``CronScript`` whose ticker calls ``step()``
through the runner's executor hook, so the controller survives broker
restarts and ticks at ``admission_controller_interval_s``.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Optional

from pixie_tpu.utils import flags, metrics_registry
from pixie_tpu.vizier.slo import CounterWindow, HistogramWindow

_M = metrics_registry()
_ACTUATIONS = _M.counter(
    "admission_controller_actuations_total",
    "Admission-controller knob changes, by knob and direction.",
)
_TICKS = _M.counter(
    "admission_controller_ticks_total",
    "Admission-controller evaluation ticks (incl. hold decisions).",
)
_KNOB = _M.gauge(
    "admission_controller_knob",
    "Current controller-actuated knob values, by knob.",
)


class AdmissionControlLoop:
    """Reads the serving telemetry window, actuates the serving flags.

    ``residency_fn`` returns a ResidencyPool.snapshot()-shaped dict
    (used_bytes/pinned_bytes/budget_bytes); ``queue_depth_fn`` the live
    admission queue depth. Both default to the broker's wiring when
    attached via ``QueryBroker.start_admission_controller``."""

    _SCRIPT_ID = "admission-controller"

    def __init__(
        self,
        residency_fn=None,
        queue_depth_fn=None,
        registry=None,
    ):
        self._residency_fn = residency_fn
        self._queue_depth_fn = queue_depth_fn
        reg = registry or metrics_registry()
        self._lock = threading.Lock()
        # Window views over the r15 planes: admitted-query wait
        # quantiles, admissions, hbm_budget rejections.
        self._wait = HistogramWindow("admission_wait_seconds", reg)
        self._admitted = CounterWindow("admission_admitted_total", reg)
        self._hbm_rejects = CounterWindow(
            "admission_rejected_total", reg, reason="hbm_budget"
        )
        self._dispatch_after_ns = time.time_ns()
        # Baselines: the operator-configured flag values at attach time;
        # decay pulls back toward these, and the hbm shrink floor is the
        # configured budget.
        self._base_concurrent = max(int(flags.admission_max_concurrent), 1)
        self._base_hbm_mb = int(flags.hbm_budget_mb)
        self._idle_windows = 0
        self._low_hbm_windows = 0
        # Post-brake hold-down (r17 satellite): windows remaining in
        # which concurrency RAISES are suppressed after an HBM-pressure
        # halving, so the MIMD law observes the brake's effect instead
        # of immediately re-climbing into the same pressure (the
        # 8->128->floor->16 thrash from the 1k-client trail).
        self._holddown = 0
        self.trail: "collections.deque[dict]" = collections.deque(
            maxlen=256
        )
        self._runner = None

    # -- cron riding (the SLOManager pattern) -------------------------------
    def attach(self, broker, datastore=None) -> "AdmissionControlLoop":
        """Persist the controller as a CronScript and start its ticker
        (restart survival rides the datastore like SLO rules)."""
        from pixie_tpu.vizier.cron import (
            CronScript, CronScriptStore, ScriptRunner,
        )
        from pixie_tpu.vizier.datastore import Datastore

        store = CronScriptStore(datastore or Datastore())
        self._runner = ScriptRunner(
            broker, store, executor=lambda _script: self.step()
        )
        self._runner.upsert_script(
            CronScript(
                self._SCRIPT_ID,
                "",
                float(flags.admission_controller_interval_s),
                configs={"admission_controller": True},
            )
        )
        return self

    def stop(self) -> None:
        if self._runner is not None:
            self._runner.stop()
            self._runner = None

    # -- signals -------------------------------------------------------------
    def _device_busy_s(self) -> float:
        """Device wall-seconds dispatched since the last tick, from the
        r15 device_dispatches attribution ring (peeked, not drained —
        the self-telemetry flush stays the single consumer). Rows the
        flush drained before we looked just under-report; the control
        law only uses this as a brake, so under-reporting is safe."""
        from pixie_tpu.parallel import profiler as resattr

        after = self._dispatch_after_ns
        self._dispatch_after_ns = time.time_ns()
        try:
            rows = resattr.dispatches_snapshot()
        except Exception:
            return 0.0
        return sum(
            r["duration_ns"] for r in rows if r["time_ns"] >= after
        ) / 1e9

    def _signals(self) -> dict:
        delta = self._wait.tick()
        admitted = self._admitted.tick()
        snap = {}
        if self._residency_fn is not None:
            try:
                snap = self._residency_fn() or {}
            except Exception:
                snap = {}
        depth = 0
        if self._queue_depth_fn is not None:
            try:
                depth = int(self._queue_depth_fn())
            except Exception:
                depth = 0
        return {
            "admitted": admitted,
            "wait_p50_ms": (
                self._wait.quantile(0.5, delta) * 1e3 if delta else 0.0
            ),
            "wait_p99_ms": (
                self._wait.quantile(0.99, delta) * 1e3 if delta else 0.0
            ),
            "queue_depth": depth,
            "hbm_rejects": self._hbm_rejects.tick(),
            "used_bytes": int(snap.get("used_bytes") or 0),
            "pinned_bytes": int(snap.get("pinned_bytes") or 0),
            "budget_bytes": int(snap.get("budget_bytes") or 0),
            "device_busy_s": self._device_busy_s(),
        }

    # -- actuation -----------------------------------------------------------
    def _actuate(self, knob: str, new, reason: str, sig: dict) -> None:
        old = getattr(flags, knob)
        if new == old:
            return
        flags.set(knob, new)
        _ACTUATIONS.inc(
            knob=knob, direction="up" if new > old else "down"
        )
        _KNOB.set(float(new), knob=knob)
        self.trail.append(
            {
                "time_ns": time.time_ns(),
                "knob": knob,
                "from": old,
                "to": new,
                "reason": reason,
                "signals": {
                    k: round(v, 3) if isinstance(v, float) else v
                    for k, v in sig.items()
                },
            }
        )

    def step(self) -> Optional[dict]:
        """One control-law evaluation over the window since the last
        tick. Returns the observed signals (None = flag off). Safe to
        call from tests without any cron machinery."""
        if not flags.admission_controller:
            return None
        with self._lock:
            _TICKS.inc()
            sig = self._signals()
            if sig["admitted"] <= 0 and sig["hbm_rejects"] <= 0 and (
                sig["queue_depth"] == 0
            ):
                # Empty window: no evidence — hold every knob.
                return sig
            self._step_concurrency(sig)
            self._step_window(sig)
            self._step_hbm(sig)
            return sig

    def _hbm_pressure(self, sig: dict) -> bool:
        budget = sig["budget_bytes"]
        return budget > 0 and sig["pinned_bytes"] > 0.9 * budget

    def _hbm_headroom(self, sig: dict) -> bool:
        budget = sig["budget_bytes"]
        return budget <= 0 or sig["used_bytes"] < 0.8 * budget

    def _step_concurrency(self, sig: dict) -> None:
        cur = max(int(flags.admission_max_concurrent), 1)
        floor = max(int(flags.admission_controller_min_concurrent), 1)
        ceil = max(int(flags.admission_controller_max_concurrent), floor)
        target_ms = float(flags.admission_controller_wait_target_ms)
        if self._hbm_pressure(sig):
            # Brake first: admitting more folds into a pool whose
            # pinned bytes crowd the budget converts latency into OOM
            # rejections. Arm the hold-down: no raises until the
            # brake's effect has been observed.
            self._actuate(
                "admission_max_concurrent",
                max(cur // 2, floor),
                "hbm_pressure",
                sig,
            )
            self._idle_windows = 0
            self._holddown = max(
                int(flags.admission_controller_holddown_windows), 0
            )
            return
        if (
            sig["admitted"] > 0
            and sig["wait_p50_ms"] > target_ms
            and self._hbm_headroom(sig)
        ):
            self._idle_windows = 0
            if self._holddown > 0:
                # Post-brake hold-down (r17): the wait signal still
                # reflects the pre-brake queue — re-climbing now is the
                # oscillation. Hold, burn one window, record why.
                self._holddown -= 1
                self.trail.append(
                    {
                        "time_ns": time.time_ns(),
                        "knob": "admission_max_concurrent",
                        "from": cur,
                        "to": cur,
                        "reason": "holddown_after_brake",
                        "signals": {
                            k: round(v, 3) if isinstance(v, float) else v
                            for k, v in sig.items()
                        },
                    }
                )
                return
            self._actuate(
                "admission_max_concurrent",
                min(cur * 2, ceil),
                "wait_p50_over_target",
                sig,
            )
            return
        if self._holddown > 0:
            # Quiet window: the hold-down still decays — evidence of a
            # calmer system counts toward releasing the brake.
            self._holddown -= 1
        if sig["admitted"] > 0 and sig["queue_depth"] == 0 and (
            sig["wait_p50_ms"] < target_ms / 10.0
        ):
            # Sustained idle: decay one halving step toward the
            # configured baseline (never below it, never below floor).
            self._idle_windows += 1
            if self._idle_windows >= 3 and cur > self._base_concurrent:
                self._actuate(
                    "admission_max_concurrent",
                    max(cur // 2, self._base_concurrent, floor),
                    "idle_decay",
                    sig,
                )
                self._idle_windows = 0
        else:
            self._idle_windows = 0

    def _step_window(self, sig: dict) -> None:
        cur = float(flags.shared_scan_window_ms)
        ceil = max(float(flags.admission_controller_max_window_ms), 0.0)
        step = max(ceil / 10.0, 1.0)
        if sig["queue_depth"] > 0 and cur < ceil:
            self._actuate(
                "shared_scan_window_ms",
                min(round(cur + step, 3), ceil),
                "queue_depth",
                sig,
            )
        elif sig["queue_depth"] == 0 and cur > 0:
            self._actuate(
                "shared_scan_window_ms",
                max(round(cur - step, 3), 0.0),
                "queue_drained",
                sig,
            )

    def _step_hbm(self, sig: dict) -> None:
        cur = int(flags.hbm_budget_mb)
        ceil = int(flags.admission_controller_max_hbm_mb)
        if cur <= 0 or ceil <= 0:
            return  # no budget / no rail: HBM is not ours to move
        if sig["hbm_rejects"] > 0 and cur < ceil:
            self._low_hbm_windows = 0
            self._actuate(
                "hbm_budget_mb",
                min(max(cur + cur // 4, cur + 1), ceil),
                "hbm_budget_rejections",
                sig,
            )
            return
        floor = max(self._base_hbm_mb, 1)
        if sig["budget_bytes"] > 0 and (
            sig["used_bytes"] < 0.3 * sig["budget_bytes"]
        ):
            self._low_hbm_windows += 1
            if self._low_hbm_windows >= 5 and cur > floor:
                self._actuate(
                    "hbm_budget_mb",
                    max(cur - cur // 4, floor),
                    "hbm_underused",
                    sig,
                )
                self._low_hbm_windows = 0
        else:
            self._low_hbm_windows = 0

    # -- status --------------------------------------------------------------
    def status(self) -> dict:
        with self._lock:
            return {
                "enabled": bool(flags.admission_controller),
                "knobs": {
                    "admission_max_concurrent": int(
                        flags.admission_max_concurrent
                    ),
                    "shared_scan_window_ms": float(
                        flags.shared_scan_window_ms
                    ),
                    "hbm_budget_mb": int(flags.hbm_budget_mb),
                },
                "rails": {
                    "min_concurrent": int(
                        flags.admission_controller_min_concurrent
                    ),
                    "max_concurrent": int(
                        flags.admission_controller_max_concurrent
                    ),
                    "max_window_ms": float(
                        flags.admission_controller_max_window_ms
                    ),
                    "max_hbm_mb": int(
                        flags.admission_controller_max_hbm_mb
                    ),
                },
                "baselines": {
                    "admission_max_concurrent": self._base_concurrent,
                    "hbm_budget_mb": self._base_hbm_mb,
                },
                # r17: windows left in the post-brake hold-down (raises
                # suppressed while > 0).
                "holddown_windows_left": self._holddown,
                "actuations": list(self.trail)[-32:],
            }
