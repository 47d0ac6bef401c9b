"""Mean size of the executed buckets (``StepRecord.buckets``)."""


def read(run):
    sizes = [b for s in run.steps for b in s.buckets]
    return sum(sizes) / len(sizes) if sizes else None
