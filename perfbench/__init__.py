"""perfbench: the on-chip benchmark of fleetx-tpu (see PERF.md).

One command, ``python3 perfbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``, runs one cell of ``BENCHMARK.json`` once.
Everything that belongs to one configuration, traffic mix, cell, driver or
metric is a file of its own, found by name: a later PR adds files and
entries and edits nothing that is here.
"""
