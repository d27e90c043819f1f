"""The device's idle share of the traced window, in percent (see
benchmark/xtrace.py: idle_pct)."""

from benchmark.xtrace import idle_pct as read  # noqa: F401
