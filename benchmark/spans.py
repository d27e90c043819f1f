"""The program's spans in a profiler trace, per query.

The program runs each of its with-block spans (``pixie_tpu/utils/
trace.py``: ``query``, ``compile``, ``fragment``, ``device.*``, ``exec``)
as a ``jax.profiler.TraceAnnotation`` of the span's name, on the thread
that runs the query. These helpers take, for each ``bench.query`` span of
a ``benchmark/xtrace.py`` summary, the host events on the same line
(thread) and inside it, clipped to it. Each returns None where the trace
holds no such span, as a program without these annotations gives.
"""

from __future__ import annotations

from benchmark.xtrace import QUERY, _length, _merge, _self_times

PROGRAM = ("query", "compile", "fragment", "exec")


def is_program_span(name: str) -> bool:
    return name in PROGRAM or name.startswith("device.")


def per_query(trace, keep) -> list:
    """[((start, end) of a bench.query, [(name, start, end)] of the events
    on its line whose name passes ``keep``, clipped to it)]."""
    lines: dict = {}
    for name, a, b, line in trace.host:
        if keep(name):
            lines.setdefault(line, []).append((name, a, b))
    out = []
    for name, qa, qb, line in trace.host:
        if name != QUERY:
            continue
        evs = [
            (n, max(a, qa), min(b, qb))
            for n, a, b in lines.get(line, ())
            if b > qa and a < qb
        ]
        out.append(((qa, qb), evs))
    return out


def _spans(trace, keep):
    if trace is None:
        return None
    queries = per_query(trace, keep)
    if not queries or not any(evs for _, evs in queries):
        return None
    return queries


def ms_per_query(trace, names) -> float | None:
    """Milliseconds a query spends in the named spans (which do not nest
    in one another), averaged over the bench.query spans."""
    queries = _spans(trace, lambda n: n in names)
    if queries is None:
        return None
    ns = sum(b - a for _, evs in queries for _, a, b in evs)
    return ns / len(queries) / 1e6


def uncovered_ms(trace, keep) -> float | None:
    """Milliseconds of a query that no span passing ``keep`` covers,
    averaged over the bench.query spans."""
    queries = _spans(trace, keep)
    if queries is None:
        return None
    ns = sum(
        (qb - qa) - _length(_merge((a, b) for _, a, b in evs))
        for (qa, qb), evs in queries
    )
    return ns / len(queries) / 1e6


def self_ms(trace) -> dict | None:
    """{program span name: its self time in ms a query}, and under
    ``bench.query`` the time no program span covers: the values sum to
    the mean bench.query length."""
    queries = _spans(trace, is_program_span)
    if queries is None:
        return None
    out: dict = {QUERY: 0.0}
    for (qa, qb), evs in queries:
        inside = 0.0
        for name, t in _self_times(evs, qa, qb):
            out[name] = out.get(name, 0.0) + t
            inside += t
        out[QUERY] += (qb - qa) - inside
    return {k: v / len(queries) / 1e6 for k, v in out.items()}
