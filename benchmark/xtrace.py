"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

The harness wraps its window in a ``bench.window`` span and each call
into the program in ``bench.*`` spans (``jax.profiler.TraceAnnotation``),
so the trace carries the host's side on the device's clock. From it:

- busy: the union of the intervals in which an operation ran on a
  device, clipped to the window, averaged over the devices;
- per span: the device time inside each ``bench.query`` span;
- breakdown: the device operations that took most time (self time, by
  program and op), and the longest idle gaps named by the host spans
  open at their middle.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os

WINDOW = "bench.window"
QUERY = "bench.query"
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
_TOP = 10


@dataclasses.dataclass
class Summary:
    window: tuple[float, float]  # ns, on the trace's clock
    busy: list[list[tuple[float, float]]]  # merged intervals per device
    ops: dict[str, float]  # device op name -> total ns inside the window
    host: list[tuple[str, float, float, int]]  # (name, start, end, line)
    queries: list[tuple[float, float]]  # bench.query spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Device busy seconds, averaged over the devices."""
        if not self.busy:
            return 0.0
        total = sum(_length(iv) for iv in self.busy)
        return total / len(self.busy) / 1e9

    def device_s_in(self, spans) -> float:
        """Device busy seconds inside ``spans``, averaged over devices."""
        if not self.busy:
            return 0.0
        total = 0.0
        for iv in self.busy:
            for a, b in spans:
                total += _length(_clip(iv, a, b))
        return total / len(self.busy) / 1e9

    def breakdown(self) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:_TOP]
        return {
            "device_ops": [[name, ns / 1e9] for name, ns in ops],
            "idle_gaps": [
                [self._host_at((a + b) / 2), (b - a) / 1e9]
                for a, b in self.gaps()[:_TOP]
            ],
        }

    def gaps(self) -> list[tuple[float, float]]:
        """Idle intervals of the first device in the window, longest
        first."""
        lo, hi = self.window
        out, t = [], lo
        for a, b in self.busy[0] if self.busy else []:
            if a > t:
                out.append((t, a))
            t = max(t, b)
        if hi > t:
            out.append((t, hi))
        return sorted(out, key=lambda ab: ab[0] - ab[1])

    def _host_at(self, t: float) -> str:
        """The innermost ``bench.*`` span open at ``t`` and, on its
        thread, the innermost other host event open then."""
        open_ = [h for h in self.host if h[1] <= t < h[2]]
        bench = [h for h in open_ if h[0].startswith("bench.") and h[0] != WINDOW]
        if not bench:
            return "host: between calls"
        inner = max(bench, key=lambda h: h[1])
        under = [
            h
            for h in open_
            if h[3] == inner[3] and not h[0].startswith("bench.")
        ]
        if under:
            return f"{inner[0]} > {max(under, key=lambda h: h[1])[0]}"
        return inner[0]


def _length(iv) -> float:
    return sum(b - a for a, b in iv)


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _merge(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _self_times(events, lo, hi):
    """(name, self ns in [lo, hi)) of each op: its time in the window
    less that of the ops nested directly inside it (a loop's body ops
    lie inside the loop's own event)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    stack, out = [], []
    for name, a, b in evs:
        t = max(min(b, hi) - max(a, lo), 0.0)
        while stack and stack[-1][1] <= a:
            stack.pop()
        if stack:
            out[stack[-1][0]][1] -= t
        out.append([name, t])
        stack.append((len(out) - 1, b))
    return [(name, t) for name, t in out if t > 0]


def _short(name: str) -> str:
    """An HLO op's or program's name without its text or fingerprint:
    ``%while.5 = (...) while(...)`` -> ``%while.5``,
    ``jit_fold(5959...)`` -> ``jit_fold``."""
    return name.split(" = ", 1)[0].split("(", 1)[0]


def _op_label(name, modules, starts, t) -> str:
    """``<program> <op>``: the op under the program running at ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    op = _short(name)
    return f"{modules[i][1]} {op}" if i >= 0 else op


def _is_device(plane) -> bool:
    """A device that runs XLA programs (not the host, nor a plane of
    runtime events such as ``/device:CUSTOM:Megascale Trace``)."""
    return plane.name.startswith("/device:") and any(
        ln.name == _OPS_LINE for ln in plane.lines
    )


def summarize(pd) -> Summary:
    """Reduce a ``jax.profiler.ProfileData``. Raises ValueError when the
    trace holds no ``bench.window`` span."""
    host, window = [], None
    devices = []
    for plane in pd.planes:
        if _is_device(plane):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == _OPS_LINE]
            modules = sorted(
                (e.start_ns, _short(e.name))
                for ln in lines
                if ln.name == _MODULES_LINE
                for e in ln.events
            )
            starts = [m[0] for m in modules]
            devices.append(
                [
                    (
                        _op_label(e.name, modules, starts, e.start_ns),
                        e.start_ns,
                        e.start_ns + e.duration_ns,
                    )
                    for ln in ops
                    for e in ln.events
                ]
            )
            continue
        for li, line in enumerate(plane.lines):
            key = hash((plane.name, li))
            for e in line.events:
                span = (e.name, e.start_ns, e.start_ns + e.duration_ns, key)
                if e.name == WINDOW:
                    window = (span[1], span[2])
                host.append(span)
    if window is None:
        raise ValueError(f"trace has no {WINDOW!r} span")
    lo, hi = window
    busy, ops = [], {}
    for events in devices:
        busy.append(
            _merge(
                (max(a, lo), min(b, hi)) for _, a, b in events if b > lo and a < hi
            )
        )
        for name, t in _self_times(events, lo, hi):
            ops[name] = ops.get(name, 0.0) + t
    queries = sorted((h[1], h[2]) for h in host if h[0] == QUERY)
    return Summary(window, busy, ops, host, queries)


def load(trace_dir: str) -> Summary:
    """Summarize the one ``.xplane.pb`` a trace directory holds."""
    from jax.profiler import ProfileData

    paths = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}: {paths}")
    return summarize(ProfileData.from_file(paths[0]))


def idle_pct(run):
    """The device's idle share of the traced window, in percent, or None
    where the run took no trace or the trace saw no device."""
    t = run.trace
    if t is None or not t.busy or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
