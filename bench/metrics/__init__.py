"""Per-layer metric readers, one module per metric, found by name
(``bench/metrics/<name>.py`` for ``<name>`` and ``<name>.<suffix>``).
Each ``read(record)`` takes a :class:`bench.harness.Record` and returns
the value, or None when the run holds nothing to read."""
