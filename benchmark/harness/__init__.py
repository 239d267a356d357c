"""The benchmark's harness: one cell, one process, one result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a data file of its own (`configs/`,
`traffic/`, `layer_metrics/`), found by the name `BENCHMARK.json` gives
it; the code here is general over them.
"""
