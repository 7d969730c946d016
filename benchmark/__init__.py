"""The benchmark: harness, traffic generator, metric arithmetic, trace
reduction, operation and byte counts, peaks table and plain reference.

It imports the program (in `system.py` and in a family's `program.py`
alone); nothing in the program imports it.
Entry point: ``python3 benchmark/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.
"""
