"""One reader per metric, found by the metric name's text before the
first '.': ``read(run) -> float | None`` over a :class:`chip.record.Run`.
A reader that finds nothing to read returns None; a share of a roofline
or of a peak is never reported as 0 for want of data."""
