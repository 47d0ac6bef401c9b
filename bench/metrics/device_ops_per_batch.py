"""Device op events in the traced window over the batches in it; reads
the same whatever the walk becomes."""


def read(run):
    if run.trace is None or not run.batches:
        return None
    return run.trace.n_ops / len(run.batches)
