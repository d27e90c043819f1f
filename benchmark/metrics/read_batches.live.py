"""Table-cursor batches read per refresh: the program's
COLD_PROFILE["read_batches"] (each pass over the table adds the batches
it walked), averaged over the window's refreshes."""


def read(run):
    done = run.done
    if not done or not any("read_batches" in r.profile for r in done):
        return None
    return sum(r.profile.get("read_batches", 0.0) for r in done) / len(done)
