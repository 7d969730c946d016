"""Per-layer metric readers. A reader is ``fn(run, **args)``: ``run`` is the
traced run's `harness.RunData` (step stamps, request records, the pool's
counters, the program's spans, the reduced trace, the configuration, the
device). It returns a number, or None where it finds nothing to read - the
harness then leaves the metric out; it never returns 0 for a share of a
roofline or of a peak."""
