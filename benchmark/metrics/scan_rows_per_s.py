"""Rows aggregated by the queries completed in the window over the
window's seconds (host clock, to the end of the last query)."""


def read(run):
    done = run.done
    if not done or run.window_s <= 0:
        return None
    return sum(r.hi - r.lo for r in done) / run.window_s
