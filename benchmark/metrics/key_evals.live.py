"""Group-key evaluations per refresh: the program's
COLD_PROFILE["key_evals"] (key planning adds one per fixed-size chunk of
rows it evaluates), averaged over the window's refreshes. A program
without the counter reads nothing."""


def read(run):
    done = run.done
    if not done or not any("key_evals" in r.profile for r in done):
        return None
    return sum(r.profile.get("key_evals", 0.0) for r in done) / len(done)
