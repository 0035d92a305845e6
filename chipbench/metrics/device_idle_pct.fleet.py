"""Idle share of the device over the traced window, in percent, read as for
the dense cell: 100 * (1 - busy / window), busy being the union of the
device's operations in the profiler's trace."""

from chipbench.harness import load_reader

read = load_reader("device_idle_pct.dense")
