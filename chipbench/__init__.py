"""Chip benchmark of the serving stack: cells of {configuration x traffic}.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell once on the machine it is started on (see
`chipbench.run`). Configurations, traffic mixes and metric readers are data
found by the names in ``BENCHMARK.json`` (`chipbench.registry`).
"""
