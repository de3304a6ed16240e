"""On-chip benchmark of the UniLRC store: `python bench/run.py --workload ...`.

Everything a cell needs is found by name: `bench/configs/<config>.json`,
`bench/traffic/<traffic>.json` and `bench/metrics/<metric>.py`, as
`BENCHMARK.json` at the checkout's root names them.
"""
