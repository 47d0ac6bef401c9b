"""Runner kinds: ``bench/runners/<kind>.py`` runs a configuration whose
``runner`` is ``<kind>`` and returns a :class:`bench.records.RunRecord`."""
