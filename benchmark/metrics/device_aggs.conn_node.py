"""Aggregations answered on the device per query: the program's
COLD_PROFILE["device_aggs"] (each offload adds the number of
aggregations it answered; px/net_flow_graph's fan-out has two), averaged
over the window's queries. A program without the counter reads nothing."""


def read(run):
    done = run.done
    if not done or not any("device_aggs" in r.profile for r in done):
        return None
    return sum(r.profile.get("device_aggs", 0.0) for r in done) / len(done)
