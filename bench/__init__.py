"""On-chip serving benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: its configuration in
``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``. See ``PERF.md`` for the metrics and cells.
"""
