"""On-chip benchmark of the serving system: cells named in BENCHMARK.json.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Each configuration (``configs/<name>.json``), its architecture
(``families/<name>.py``), traffic mix (``traffic/<name>.json``), per-layer
metric (``metrics/<name>.py``) and correctness limit
(``limits/<cell>.json``) is a file of its own, found by the name
``BENCHMARK.json`` or the configuration gives it.
"""
