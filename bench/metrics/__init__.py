"""Per-layer metric readers, one module per metric named as in
BENCHMARK.json. Each defines ``read(ctx) -> float | None``: ``None`` when
the trace holds nothing for it to read, so the metric is left out."""
