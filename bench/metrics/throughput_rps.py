"""Requests sent while the window was open, drained to completion, over
the time from the window's opening to the last of their completions."""
from bench.stats import drained_rate


def read(run):
    done = [r.done for r in run.requests if r.done is not None]
    return drained_rate(len(run.requests), run.t_open, done)
