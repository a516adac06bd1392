"""Per-layer metric readers, one file per metric, found by name:
``bench/metrics/<metric>.py`` defines ``read(ctx)``, which returns the
metric's value or None where the run has nothing to read.  ``ctx`` holds
the trace reduction (``trace``), the device peaks (``peaks``), the
serving registry's histogram deltas over the window (``serve``), the
program's spans of the window (``spans``), the configuration
(``config``), the users whose lists the window computed
(``users_scored``), each request's latency from its due time in an open
loop (``latency_s``, None in a closed one) and the traced window's
seconds (``window_s``)."""
