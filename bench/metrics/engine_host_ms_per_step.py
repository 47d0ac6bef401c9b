"""Host time of ``ServeEngine.run`` outside the backend's
``run_workload``, per engine step (harness clock)."""


def read(run):
    if not run.steps:
        return None
    own = sum(s.end - s.start - s.backend_s for s in run.steps)
    return 1e3 * own / len(run.steps)
