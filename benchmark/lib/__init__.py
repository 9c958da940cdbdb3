"""The yardstick: inputs, comparison, peaks, bytes and the trace reduction.

Copies of what the program also has (bench.py, bench_scale.py,
chip_smoke.py, tpusim/obs/bench.py), kept here because later PRs may change
the program and may not change what measures it.
"""
