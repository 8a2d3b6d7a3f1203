"""Per-layer metric reducers, one module per metric name.

Each module has ``reduce(run) -> float | None``: ``run`` is a
``chipbench.tasks.train.RunContext``.  A reducer that finds nothing to
read returns None, and the metric is left out of the result line.
"""
