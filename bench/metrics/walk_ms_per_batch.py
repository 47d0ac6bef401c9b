"""Host-clock time of one homogeneous batch in
``PallasBackend.run_workload`` (the walk), averaged over the window."""


def read(run):
    if not run.batches:
        return None
    return 1e3 * sum(b.end - b.start for b in run.batches) / len(run.batches)
