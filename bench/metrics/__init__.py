"""One reader per metric: ``bench/metrics/<name>.py`` defines
``read(run) -> float | None`` over a :class:`bench.records.RunRecord`.
``None`` means the run holds nothing to read, and the metric is left
out of the run's line."""
