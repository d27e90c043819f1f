"""One run of one cell of ``BENCHMARK.json``.

Everything that belongs to a cell is found by name: the cell's entry
names its configuration and traffic mix; the configuration's file names
its dataset module (``benchmark/datasets/<dataset>.py``: schema,
generator, query and plain reference); the traffic mix is
``benchmark/traffic/<traffic>.json``; each metric is read by
``benchmark/metrics/<metric>.py``. Adding a cell adds files and entries
and edits none.

A run: generate the table from ``--seed`` on the host, load it through
the program's table store, drive ``Carnot(device_executor=MeshExecutor)``
with the traffic mix (warm-up counted as set-up), measure for
``--seconds``, then compare what the timed queries returned with the
plain reference over exactly the rows each covered.
"""

from __future__ import annotations

import bisect
import concurrent.futures
import dataclasses
import hashlib
import importlib
import importlib.util
import json
import math
import os
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTERS = (
    "device_offload_total",
    "device_offload_unmatched_total",
    "device_offload_fallback_total",
    "device_offload_fallback_breaker_trips_total",
    "device_offload_fallback_breaker_open_total",
    "mesh_degrade_events_total",
)
WARMUP_MIN = 2  # the cold query stages and compiles; the next compiles the warm fold
WARMUP_MAX = 8
CHECK_MAX = 64  # answers compared per run, drawn from the seed
LATE_GRACE_S = 60.0  # how long past the window a due answer is waited for
_NS = 10**9


class BenchError(SystemExit):
    """A run that cannot measure: exits non-zero and prints no result."""

    def __init__(self, msg: str):
        super().__init__(f"benchmark: {msg}")


# ---- finding a cell by name ---------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    dataset: object
    end_to_end: list
    per_layer: list
    root: str

    def metric_reader(self, name: str):
        path = os.path.join(self.root, "benchmark", "metrics", f"{name}.py")
        spec = importlib.util.spec_from_file_location(f"_metric_{name}", path)
        if spec is None or not os.path.exists(path):
            raise BenchError(f"no reader {path} for metric {name!r}")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise BenchError(f"cannot read {path}: {e}") from None


def load_cell(workload: str, root: str = ROOT) -> Cell:
    spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == entry["config"])
    config = _read_json(os.path.join(root, conf["file"]))
    traffic = _read_json(
        os.path.join(root, "benchmark", "traffic", f"{entry['traffic']}.json")
    )
    dataset = importlib.import_module(f"benchmark.datasets.{config['dataset']}")

    def listed(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in spec["end_to_end"] if listed(m)]
    names = {m["name"] for m in e2e}
    per_layer = [
        m for m in spec["per_layer"] if listed(m) and m["moves"] in names
    ]
    return Cell(
        workload, entry["chips"], config, traffic, dataset, e2e, per_layer, root
    )


# ---- the chip -----------------------------------------------------------


def require_chip(chips: int, root: str = ROOT):
    """(devices, peaks of their kind), or exit: the benchmark runs on a
    TPU with the cell's chips and a known device kind, or not at all."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(
            f"JAX found no TPU (first device is {devices[0].platform!r});"
            " the benchmark never falls back to it"
        )
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX has {len(devices)}")
    peaks = _read_json(os.path.join(root, "benchmark", "peaks.json"))["devices"]
    kind = devices[0].device_kind
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in benchmark/peaks.json")
    return devices, peaks[kind]


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent cache at the checkout's fixed ``.jax_cache``, for
    every program however short its compile, so that only a checkout's
    first run compiles. The program's own cache module reads the same
    directory from the environment."""
    import jax

    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileWatch:
    """Counts programs JAX compiles or loads from its persistent cache."""

    def __init__(self):
        import jax

        self.programs = self.hits = self.misses = 0
        self.names: list[str] = []
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event, duration, fun_name="", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.names.append(fun_name)


# ---- the table ----------------------------------------------------------


class Timeline:
    """The rows of a run in time order: the retained table, then what the
    writer appends. Row ``i`` has timestamp ``base + i * 1e9 // rate``,
    from the configuration's event rate."""

    def __init__(self, cell: Cell, seed: int, ingest_rows: int):
        cfg, ds = cell.config, cell.dataset
        self.base = cfg["time_base_ns"]
        self.rate = cfg["events_per_s"]
        self.n = cfg["rows"]
        self.retained = ds.generate(cfg, self.n, np.random.default_rng([seed, 0]))
        self.ingest = ds.generate(
            cfg, ingest_rows, np.random.default_rng([seed, 1])
        )

    def time_of(self, i):
        return self.base + (np.asarray(i, np.int64) * _NS) // self.rate

    def first_at_or_after(self, t: int) -> int:
        return max(-(-(t - self.base) * self.rate // _NS), 0)

    def rows(self, lo: int, hi: int) -> dict:
        """Columns of rows [lo, hi), across the retained and ingested."""
        parts = []
        if lo < self.n:
            parts.append({k: v[lo : min(hi, self.n)] for k, v in self.retained.items()})
        if hi > self.n:
            a, b = max(lo - self.n, 0), hi - self.n
            parts.append({k: v[a:b] for k, v in self.ingest.items()})
        out = (
            parts[0]
            if len(parts) == 1
            else {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
        )
        return {**out, "time_": self.time_of(np.arange(lo, hi))}


def load_table(store, cell: Cell, tl: Timeline, chunk: int = 1 << 24):
    """The retained rows through the program's write path, compacted into
    its cold batches as a store that has held them a while would be.
    Under a writer (the mix's ``hot_s``), the last ``hot_s`` seconds are
    written after the compaction in the writer's pushes, as a table that
    agents have been writing, and nothing has compacted since, holds
    them."""
    ds, mix = cell.dataset, cell.traffic
    table = store.create_table(ds.TABLE, ds.relation(), size_limit=1 << 42)
    ds.identity_codes(table, cell.config)

    def write(lo, hi):
        table.write_pydict(
            ds.pydict(table, tl.retained, lo, hi, tl.time_of(np.arange(lo, hi)))
        )

    cold = tl.n
    if "hot_s" in mix:
        # Whole pushes that end where the writer's first one starts.
        per_push = round(mix["ingest_events_per_s"] * mix["push_period_s"])
        pushes = math.ceil(mix["hot_s"] * tl.rate / per_push)
        cold = max(tl.n - pushes * per_push, 0)
    for lo in range(0, cold, chunk):
        write(lo, min(lo + chunk, cold))
    table.compact()
    for lo in range(cold, tl.n, per_push if cold < tl.n else 1):
        write(lo, min(lo + per_push, tl.n))
    return table


class Writer(threading.Thread):
    """Appends the timeline's ingest rows at the traffic's event rate, in
    pushes every ``push_period_s``, through the table's write path."""

    def __init__(self, table, cell: Cell, tl: Timeline, traffic: dict):
        super().__init__(name="bench-writer", daemon=True)
        self.table, self.ds, self.tl = table, cell.dataset, tl
        self.period = traffic["push_period_s"]
        self.per_push = round(traffic["ingest_events_per_s"] * self.period)
        self.stop_event = threading.Event()
        self.late: list[float] = []
        # (monotonic time the push returned, last row index written)
        self._done_t = [-math.inf]
        self._mark = [tl.n - 1]
        self.exhausted = False

    def run(self):
        import jax

        t0, k = time.perf_counter(), 0
        n_ingest = len(next(iter(self.tl.ingest.values())))
        while not self.stop_event.is_set():
            due = t0 + k * self.period
            wait = due - time.perf_counter()
            if wait > 0 and self.stop_event.wait(wait):
                break
            lo, hi = k * self.per_push, (k + 1) * self.per_push
            if hi > n_ingest:
                self.exhausted = True
                break
            self.late.append(time.perf_counter() - due)
            idx = np.arange(self.tl.n + lo, self.tl.n + hi)
            with jax.profiler.TraceAnnotation("bench.push"):
                self.table.write_pydict(
                    self.ds.pydict(
                        self.table, self.tl.ingest, lo, hi, self.tl.time_of(idx)
                    )
                )
            self._done_t.append(time.perf_counter())
            self._mark.append(self.tl.n + hi - 1)
            k += 1

    def await_push(self, timeout: float = 60.0) -> None:
        """Returns once a push has landed since the call."""
        n = len(self._mark)
        t_end = time.perf_counter() + timeout
        while len(self._mark) == n:
            if time.perf_counter() > t_end or not self.is_alive():
                raise BenchError("the writer stopped pushing")
            time.sleep(0.005)

    def watermark_at(self, t: float) -> int:
        """Index of the last row whose push had returned by time ``t``."""
        return self._mark[bisect.bisect_right(self._done_t, t) - 1]

    def stop(self):
        self.stop_event.set()
        self.join(timeout=30)


# ---- queries ------------------------------------------------------------


@dataclasses.dataclass
class Record:
    due: float  # when the query was due (closed loop: when it started)
    start: float
    end: float
    lo: int  # rows [lo, hi) of the timeline the query covers
    hi: int
    rows: dict | None  # the answer, None when none came
    compile_ns: int = 0
    profile: dict = dataclasses.field(default_factory=dict)
    failed: str | None = None

    @property
    def latency_s(self) -> float:
        return self.end - self.due


class Session:
    """The program under test: one Carnot over the cell's mesh."""

    def __init__(self, cell: Cell, devices, store):
        from jax.sharding import Mesh

        from pixie_tpu.engine import Carnot
        from pixie_tpu.parallel import MeshExecutor

        self.cell = cell
        self.ex = MeshExecutor(
            mesh=Mesh(np.array(devices[: cell.chips]), ("d",)),
            block_rows=cell.config["block_rows"],
        )
        self.carnot = Carnot(table_store=store, device_executor=self.ex)

    def _errors(self) -> int:
        ex = self.ex
        return (
            len(ex.fallback_errors)
            + len(ex.stream_fallback_errors)
            + len(ex.prewarm_errors)
        )

    def execute(self, tl: Timeline, lo: int, hi: int, due=None) -> Record:
        """One query over timeline rows [lo, hi), which must offload
        cleanly; a query that falls to the host or errors is failed."""
        import jax

        from pixie_tpu.parallel.staging import reset_cold_profile
        from pixie_tpu.utils import metrics_registry

        reg = metrics_registry()
        ds = self.cell.dataset
        pxl = ds.query(self.cell.config, int(tl.time_of(lo)), int(tl.time_of(hi - 1)))
        before = [reg.counter(c).value() for c in COUNTERS]
        errors = self._errors()
        reset_cold_profile()
        start = time.perf_counter()
        rows, compile_ns, failed = None, 0, None
        try:
            with jax.profiler.TraceAnnotation("bench.query"):
                res = self.carnot.execute_query(pxl)
                compile_ns = res.compile_time_ns
            with jax.profiler.TraceAnnotation("bench.materialize"):
                rows = res.table(ds.OUT)
        except Exception as e:  # a query that errors is a failed query
            failed = f"error: {type(e).__name__}: {e}"
        end = time.perf_counter()
        profile = reset_cold_profile()
        delta = dict(
            zip(COUNTERS, (reg.counter(c).value() - b for c, b in zip(COUNTERS, before)))
        )
        if failed is None:
            if self._errors() > errors:
                failed = "device path error"
            elif delta["device_offload_total"] < 1:
                failed = "not offloaded"
            elif any(v for c, v in delta.items() if c != "device_offload_total"):
                failed = f"fell off the device: {delta}"
        return Record(
            start if due is None else due,
            start,
            end,
            lo,
            hi,
            rows,
            compile_ns,
            profile,
            failed,
        )


# ---- traffic ------------------------------------------------------------


class Traffic:
    """The one generator every traffic mix is read by. A mix sets:

    - ``loop``: ``closed`` (one client, back to back) or ``open`` (due
      times at ``rate_per_s``, served in order, each timed from when it
      was due);
    - ``span_s``: each query covers the ``span_s`` seconds of event time
      that end at the writer's watermark when it is due; without it, each
      covers the whole retained table;
    - ``align_ns``: the span ends instead at the last multiple of
      ``align_ns`` at or before the watermark (exclusive), so that it
      holds whole windows of that length;
    - ``ingest_events_per_s`` and ``push_period_s``: a writer appends
      rows at that rate during warm-up and window; without them nobody
      writes;
    - ``hot_s``: the seconds at the end of the retained table that were
      written in the writer's pushes and not compacted (see load_table).
    """

    def __init__(self, cell: Cell, session: Session, tl: Timeline, writer):
        self.cell, self.session, self.tl, self.writer = cell, session, tl, writer
        self.mix = cell.traffic
        self.idle_late: list[float] = []

    def rows_for(self, t: float) -> tuple[int, int]:
        if "span_s" not in self.mix:
            return 0, self.tl.n
        hi = self.writer.watermark_at(t) + 1 if self.writer else self.tl.n
        end_t = int(self.tl.time_of(hi - 1))
        span = int(self.mix["span_s"] * _NS)
        if "align_ns" in self.mix:
            end_t -= end_t % self.mix["align_ns"]
            hi = self.tl.first_at_or_after(end_t)
            return self.tl.first_at_or_after(end_t - span), hi
        return self.tl.first_at_or_after(end_t - span), hi

    def one(self, due=None) -> Record:
        now = time.perf_counter()
        lo, hi = self.rows_for(now if due is None else due)
        return self.session.execute(self.tl, lo, hi, due)

    def warm_up(self, watch: CompileWatch) -> list[dict]:
        """Queries of the window's own shape until one needs no program
        JAX has not loaded yet (at least WARMUP_MIN, at most WARMUP_MAX),
        then rounds of the executor's background compiles and one more
        query of each kind, until a round compiles nothing: a landed
        background program changes the path the next query takes, and
        that query may start another."""
        out = self._warm(watch, WARMUP_MIN)
        for _ in range(WARMUP_MAX):
            before = watch.programs
            self._await_background()
            out += self._warm(watch, 1)
            if self.writer:
                # A refresh with no push since the last one hits the
                # staged cache, which takes a path of its own.
                out += self._warm(watch, 1, new_version=False)
            self._await_background()
            if watch.programs == before:
                return out
        raise BenchError("set-up never stopped compiling")

    def _await_background(self) -> None:
        # The executor compiles some programs speculatively on a worker
        # thread (the warm and batched folds). The program offers no
        # public signal for this, so its private map is read, and a
        # rename stops the run here.
        futures = getattr(self.session.ex, "_aot_futures", None)
        if not isinstance(futures, dict):
            raise BenchError(
                "MeshExecutor._aot_futures is gone: set-up cannot wait for"
                " the executor's background compiles"
            )
        concurrent.futures.wait(list(futures.values()), timeout=600)

    def _warm(self, watch: CompileWatch, at_least: int, new_version=True):
        out = []
        for i in range(WARMUP_MAX):
            if self.writer and new_version:
                # A new table version, as most refreshes in the window see.
                self.writer.await_push()
            before = watch.programs
            rec = self.one()
            fresh = watch.programs - before
            out.append(
                {"s": rec.end - rec.start, "programs": fresh, "failed": rec.failed}
            )
            if rec.failed and rec.rows is None:
                raise BenchError(f"warm-up query failed: {rec.failed}")
            if i + 1 >= at_least and fresh == 0:
                break
        return out

    def window(self, seconds: float) -> tuple[list[Record], float, int]:
        """(records, window seconds, due queries never served)."""
        import jax

        with jax.profiler.TraceAnnotation("bench.window"):
            if self.mix["loop"] == "closed":
                return self._closed(seconds)
            return self._open(seconds)

    def _closed(self, seconds):
        t0 = time.perf_counter()
        recs = []
        while time.perf_counter() - t0 < seconds:
            recs.append(self.one())
        # All the work and all the time: to the end of the last query.
        return recs, recs[-1].end - t0, 0

    def _open(self, seconds):
        interval = 1.0 / self.mix["rate_per_s"]
        t0 = time.perf_counter()
        n_due = math.ceil(seconds / interval)
        recs = []
        for k in range(n_due):
            due = t0 + k * interval
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
                self.idle_late.append(time.perf_counter() - due)
            if time.perf_counter() > t0 + seconds + LATE_GRACE_S:
                return recs, seconds, n_due - k
            recs.append(self.one(due))
        return recs, seconds, 0


# ---- correctness ---------------------------------------------------------


def check(cell: Cell, tl: Timeline, recs: list[Record], seed: int, precision="exact"):
    """Compare a seed-drawn sample of the window's answers (all of them,
    up to CHECK_MAX) with the plain reference over the rows each covered.
    Returns (numbers, checked): each number the worst over the sample.
    ``precision="low"`` puts the control in the program's place."""
    ds, cfg = cell.dataset, cell.config
    answered = [r for r in recs if r.rows is not None and r.failed is None]
    rng = np.random.default_rng([seed, 2])
    if len(answered) > CHECK_MAX:
        pick = rng.choice(len(answered), CHECK_MAX, replace=False)
        answered = [answered[i] for i in sorted(pick)]
    refs: dict = {}

    def ref(r, p):
        if (r.lo, r.hi, p) not in refs:
            refs[r.lo, r.hi, p] = ds.reference(cfg, tl.rows(r.lo, r.hi), p)
        return refs[r.lo, r.hi, p]

    worst: dict = {}
    seen = set()
    for r in answered:
        # Answers alike to the byte over the same rows compare alike.
        key = (r.lo, r.hi, _digest(r.rows))
        if key in seen:
            continue
        seen.add(key)
        want = ref(r, "exact")
        got = (
            ds.as_reference(r.rows, cfg)
            if precision == "exact"
            else ref(r, precision)
        )
        for k, v in ds.compare(got, want).items():
            worst[k] = max(worst.get(k, 0), v)
    return worst, len(answered)


def _digest(rows: dict) -> str:
    h = hashlib.sha256()
    for k in sorted(rows):
        v = np.asarray(rows[k])
        h.update(k.encode())
        h.update(
            "\0".join(map(str, v)).encode() if v.dtype == object else v.tobytes()
        )
    return h.hexdigest()


def verdict(cell: Cell, numbers: dict, unanswered: int, failed: int = 0,
            compiled: int = 0) -> tuple[bool, dict]:
    """Correct when every due query was answered on the device (none
    unanswered, none failed: not offloaded, fallen to the host, breaker,
    device-path error), nothing compiled in the window, and each compared
    number is within its limit."""
    limits = {
        "queries_unanswered": 0,
        "queries_failed": 0,
        "programs_compiled_in_window": 0,
        **cell.config["limits"],
    }
    numbers = {
        **numbers,
        "queries_unanswered": unanswered,
        "queries_failed": failed,
        "programs_compiled_in_window": compiled,
    }
    values = {k: numbers.get(k, math.inf) for k in limits}
    ok = all(values[k] <= limits[k] for k in limits)
    # JSON has no infinity: a number that could not be read prints as text.
    checks = {
        k: {"value": v if math.isfinite(v) else str(v), "limit": limits[k]}
        for k, v in values.items()
    }
    return ok, checks


# ---- one run ------------------------------------------------------------


@dataclasses.dataclass
class RunView:
    """What a metric reader sees."""

    cell: Cell
    records: list
    window_s: float
    setup_s: float
    peaks: dict
    trace: object = None

    @property
    def done(self) -> list:
        """The queries answered on the device: a failed one counts in no
        metric."""
        return [r for r in self.records if r.rows is not None and r.failed is None]


def read_metrics(view: RunView, entries) -> dict:
    out = {}
    for m in entries:
        value = view.cell.metric_reader(m["name"])(view)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def say(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


@dataclasses.dataclass
class Prepared:
    """A cell set up for one seed: table loaded, program warm, writer on."""

    cell: Cell
    timeline: Timeline
    session: Session
    traffic: Traffic
    writer: Writer | None
    watch: CompileWatch
    setup: dict

    def close(self):
        if self.writer:
            self.writer.stop()


def prepare(cell: Cell, seed: int, seconds: float, devices) -> Prepared:
    """Generate, load, start the writer and warm up: the set-up."""
    from pixie_tpu.table import TableStore

    watch = CompileWatch()
    mix = cell.traffic
    ingest_rows = 0
    if "ingest_events_per_s" in mix:
        # Enough for warm-up, the window and its grace, with room.
        horizon = seconds + LATE_GRACE_S + 240
        ingest_rows = math.ceil(mix["ingest_events_per_s"] * horizon)
    t0 = time.perf_counter()
    tl = Timeline(cell, seed, ingest_rows)
    t1 = time.perf_counter()
    store = TableStore()
    table = load_table(store, cell, tl)
    t2 = time.perf_counter()
    session = Session(cell, devices, store)
    writer = Writer(table, cell, tl, mix) if ingest_rows else None
    if writer:
        writer.start()
    traffic = Traffic(cell, session, tl, writer)
    prep = Prepared(cell, tl, session, traffic, writer, watch, {})
    try:
        warm = traffic.warm_up(watch)
    except BaseException:
        prep.close()
        raise
    prep.setup = {
        "generate_s": t1 - t0,
        "load_s": t2 - t1,
        "warmup": warm,
        "compile_cache_hits": watch.hits,
        "compile_cache_misses": watch.misses,
        "programs": watch.programs,
    }
    return prep


@dataclasses.dataclass
class Outcome:
    result: dict
    timeline: Timeline
    records: list
    window_s: float


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices, peaks,
        t_start: float) -> Outcome:
    """One run: set-up, the window, then the check and the metrics."""
    import jax

    prep = prepare(cell, seed, seconds, devices)
    watch, traffic, writer, tl = prep.watch, prep.traffic, prep.writer, prep.timeline
    try:
        setup_s = time.perf_counter() - t_start
        say(setup={"setup_s": setup_s, **prep.setup})
        trace_dir = None
        if trace:
            import tempfile

            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        programs0 = watch.programs
        recs, window_s, unserved = traffic.window(seconds)
        if trace:
            jax.profiler.stop_trace()
    finally:
        prep.close()
    peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in devices[: cell.chips]
    )
    summary = None
    if trace:
        import shutil

        from benchmark import xtrace

        try:
            summary = xtrace.load(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    lat = sorted(r.latency_s for r in recs)
    t_win = recs[0].due if recs else 0.0
    slowest = sorted(recs, key=lambda r: -r.latency_s)[:5]
    say(
        window={
            "seconds": window_s,
            "queries": len(recs),
            "unserved": unserved,
            "failed": sorted({r.failed for r in recs if r.failed}),
            "programs_loaded_in_window": watch.names[programs0:],
            "latency_s_min_max": [lat[0], lat[-1]] if lat else None,
            "latency_s_p95": float(np.percentile(lat, 95)) if lat else None,
            # (seconds into the window it was due, latency): where stalls sit
            "slowest": [[r.due - t_win, r.latency_s] for r in slowest],
            "generator_late_s_max": max(traffic.idle_late, default=0.0),
            "writer_late_s_p50_max": (
                [float(np.median(writer.late)), max(writer.late)]
                if writer and writer.late
                else None
            ),
            "writer_exhausted": bool(writer and writer.exhausted),
        }
    )
    del prep, traffic, writer
    t_check = time.perf_counter()
    numbers, checked = check(cell, tl, recs, seed)
    say(check={"seconds": time.perf_counter() - t_check, "answers": checked})
    unanswered = unserved + sum(r.rows is None for r in recs)
    failed = sum(r.rows is not None and r.failed is not None for r in recs)
    correct, checks = verdict(
        cell, numbers, unanswered, failed, len(watch.names) - programs0
    )
    view = RunView(cell, recs, window_s, setup_s, peaks, summary)
    entries = cell.per_layer if trace else cell.end_to_end
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    result = {
        "correct": correct,
        "attempted": len(recs) + unserved,
        "failed": unserved + sum(r.failed is not None for r in recs),
        "metrics": read_metrics(view, entries),
        "device": device,
    }
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    result["checked_answers"] = checked
    result["checks"] = checks
    return Outcome(result, tl, recs, window_s)


def report_checks(checks: dict) -> None:
    """The numbers compared, each beside its limit: the last lines on
    standard error."""
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
