"""The repo's benchmark: one command runs one cell of ``BENCHMARK.json`` once.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a number rests on lives here, where a PR that claims a gain
cannot change it: traffic generation (``traffic.py``), metric arithmetic
(``arithmetic.py``), the peaks table and FLOP/byte functions
(``peaks.py``), each block type's plain reference and parameter count
(``blocks/<block>.py``, found by the name a configuration's file gives),
the trace reducer (``trace.py``) and the comparison that decides
``correct``. From the program it takes only the system under test and its
spans and counters. See ``README.md`` for how a later PR adds a
configuration, a block type, a cell, a traffic mix or a metric as files.
"""
