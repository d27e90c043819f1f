"""The chip benchmark of pixie-tpu: see BENCHMARK.json and PERF.md.

Kept apart from the program so that the yardstick does not move when the
program does: traffic, tables, reference, peaks and trace reduction live
here, and the program is used only through its entry points, counters
and trace names.
"""
