"""Host staging per refresh, in ms: the program's COLD_PROFILE read,
pack, put and concat seconds, reset before each refresh, averaged over
the window's refreshes."""

KEYS = ("read_columns", "stage_stream_pack", "stage_stream_put", "stage_concat")


def read(run):
    done = run.done
    if not done or not any(k in r.profile for r in done for k in KEYS):
        return None
    return 1000.0 * sum(r.profile.get(k, 0.0) for r in done for k in KEYS) / len(done)
