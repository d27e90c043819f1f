"""The host exec graph after the offload per query, in ms: the program's
exec span on the query's thread inside each bench.query span, averaged
over the traced window's queries."""

from benchmark.spans import ms_per_query


def read(run):
    return ms_per_query(run.trace, ("exec",))
