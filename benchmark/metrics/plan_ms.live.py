"""Compile and plan per refresh, in ms: the program's
QueryResult.compile_time_ns, averaged over the window's refreshes."""


def read(run):
    done = run.done
    if not done:
        return None
    return sum(r.compile_ns for r in done) / len(done) / 1e6
