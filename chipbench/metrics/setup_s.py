"""Set-up: process start to the window's start (imports, building the
deployment and the query, the warm-up query with any compile)."""


def read(run):
    return run.setup_s
