"""Key planning per refresh, in ms: the program's device.plan_keys and
device.windowize spans (the group keys, px.bin window included, evaluated
over the table's cursor batches) on the query's thread inside each
bench.query span, averaged over the traced window's refreshes."""

from benchmark.spans import ms_per_query


def read(run):
    return ms_per_query(run.trace, ("device.plan_keys", "device.windowize"))
