"""Table segments the cursors examined per refresh: the program's
COLD_PROFILE["read_visits"] (each pass over the table adds the segments
its cursor's reads looped over), averaged over the window's refreshes.
A program without the counter reads nothing."""


def read(run):
    done = run.done
    if not done or not any("read_visits" in r.profile for r in done):
        return None
    return sum(r.profile.get("read_visits", 0.0) for r in done) / len(done)
