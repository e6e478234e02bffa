"""One reader per metric: ``bench/metrics/<name>.py`` defines
``read(run) -> float | None`` over the run record (``bench.run.Run``). A
reader that finds nothing to read returns None and the metric is left out of
the result line; a share of a roofline or a peak is never reported as 0.
"""
