"""On-chip benchmark of the coded-Shuffle graph engine (see BENCHMARK.json).

`python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>`
runs one cell. Everything that belongs to one configuration, graph family,
traffic mix, program or per-layer metric sits in a file of its own, found
by name: `configs/<config>.json`, `graphs/<family>.py`,
`traffic/<mix>.json`, `programs/<program>.py`, `metrics/<metric>.py`.
"""
