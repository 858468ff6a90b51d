"""Per-layer metric readers, one file per metric, found by the metric's
name in ``BENCHMARK.json`` (``mfu.prefill`` -> ``mfu_prefill.py``).

Each has ``read(ctx) -> float | None``; ``None`` means there was nothing
to read in this run, and the metric is left out of the result line. The
context (``bench.run.LayerContext``) carries the reduced device
trace, the host records of the traced span and of the whole window, the
configuration file, the cell's mix and the device's peaks.
"""


def module_name(metric: str) -> str:
    return metric.replace(".", "_").replace("-", "_")
