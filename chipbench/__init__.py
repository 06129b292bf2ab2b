"""The benchmark of paddle_tpu: one command runs one cell once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a data file found by the name in ``BENCHMARK.json``
(``configs/``, ``traffic/``, ``metrics/``), and everything that belongs to
one model family is the module ``families/<name>.py`` that the
configuration's ``family`` key names. The yardstick (traffic generation,
FLOP and byte counts, peaks, the trace reduction, the plain reference and
the comparison that decides ``correct``) lives here and takes from the
program only the system under test.
"""
