"""Device-to-host finalize per query, in ms: the program's
device.finalize span on the query's thread inside each bench.query span,
averaged over the traced window's queries."""

from benchmark.spans import ms_per_query


def read(run):
    return ms_per_query(run.trace, ("device.finalize",))
