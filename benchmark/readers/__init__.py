"""Per-layer metric readers, one module each, found by the ``reader`` key
of a file in ``layer_metrics/``. ``read(ctx, **args)`` returns a number, or
None where it finds nothing to read. ``ctx`` holds what the runner
gathered over the measured window: ``counters`` (deltas of the program's
monitor registry), ``counters_total`` (since process start), ``spans``
(the program's spans that closed inside the window), ``series`` (what the
benchmark sampled itself), ``trace`` (the reduced device trace, or None),
``end_to_end`` (the run's own end-to-end values), ``config``, ``traffic``
and ``peaks``."""
