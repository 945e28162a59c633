"""Sweep cells answered per second: every cell of every whole query in
the window, over the wall seconds from the first query's start to the
last query's end."""


def read(run):
    if not run.queries:
        return None
    return sum(q["cells"] for q in run.queries) / run.window_s
