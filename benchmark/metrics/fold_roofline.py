"""The fold's share of its roofline, in percent: the least time the chip
could take for the queries of the traced window (the rows each covered
times the narrowest lossless bits of the columns it must read, over the
peak HBM bandwidth) over the device time inside those queries' spans.
The byte bound is the roofline's binding side: the operations per row
are a few, against hundreds of bytes per ns of bandwidth."""


def read(run):
    t = run.trace
    if t is None or not t.queries:
        return None
    device_s = t.device_s_in(t.queries)
    if device_s <= 0:
        return None
    done = run.done
    bits = run.cell.dataset.lower_bound_bits(run.cell.config)
    bound_s = sum(r.hi - r.lo for r in done) * bits / 8 / run.peaks["hbm_bytes_per_s"]
    return 100.0 * bound_s / device_s
