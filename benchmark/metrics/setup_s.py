"""Set-up seconds: from the process's start to the end of warm-up
(generation, loading, the cold query's staging and compilation, warm-up
queries)."""


def read(run):
    return run.setup_s
