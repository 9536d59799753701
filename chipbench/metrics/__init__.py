"""Metric readers: ``<name>.py`` holds ``read(ctx)`` for the metric of that
name in ``BENCHMARK.json`` (``ctx`` is ``chipbench.harness.Context``), and
returns ``None`` where the run holds nothing to read."""
