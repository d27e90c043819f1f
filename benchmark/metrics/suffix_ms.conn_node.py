"""The host exec graph after the offload per query, in ms: nslookup,
regex, the second group-by and the join of px/net_flow_graph, read from
the program's exec span inside each bench.query span, averaged over the
traced window's queries."""

from benchmark.spans import ms_per_query


def read(run):
    return ms_per_query(run.trace, ("exec",))
