"""Traffic generators, one module per kind, found by the `generator.kind`
of a cell's file under workloads/. Each exposes `run(ctx)`: see child.py."""
