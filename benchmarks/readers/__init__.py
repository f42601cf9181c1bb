"""Per-layer metric readers, one module per kind, found by the
`reader.kind` of a metric's file under layer_metrics/. Each exposes
`read(args, run) -> float | None`; None means nothing was there to read
and the harness leaves the metric out of the line."""
