"""The chip benchmark: one cell of ``BENCHMARK.json`` per run,
``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``.

``harness`` sets a cell up and runs its window, ``generator`` drives
the traffic a ``traffic/<name>.json`` mix describes, ``families/``
turn a ``configs/<name>.json`` into a served plan and hold its plain
reference, ``metrics/<name>.py`` read the per-layer metrics from
``trace``'s reduction of the profiler trace, ``work`` counts
operations and bytes from shapes and ``peaks`` holds the chips' peaks.
``probe`` reads the check's numbers over many seeds, ``sweep`` offers
open-loop load at fixed rates.
"""
