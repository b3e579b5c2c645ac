"""The on-chip benchmark of ray_tpu: harness, yardstick and data.

Everything a later PR may not change lives here: traffic generation, the
reduction from traces and spans to metrics, the peaks table, the FLOP and
byte arithmetic, each configuration's plain reference and the comparison
that decides ``correct``. From the program it takes only the system under
test and its spans, counters and jitted-program names. ``PERF.md`` says how
a cell, a configuration, a traffic mix, a traffic kind or a per-layer
metric is added as new files with no edit to a file that is here.
"""
