"""On-chip benchmark of the J-DOB co-inference system.

``python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace 0|1``
runs one cell of ``BENCHMARK.json`` once.  Everything a cell needs is
found by name: ``bench/configs/<config>.json``, ``bench/mixes/<traffic>.json``,
``bench/metrics/<metric>.py`` and, for a served model, the module
``bench/reference/<model.reference>.py`` (``transformer`` by default),
which gives its plain forward pass, its weight shapes and its per-layer
FLOP and byte counts.  A model of another layer plan joins as a
configuration file and such a module, with no edit elsewhere.

The yardstick (traffic generation, deployment arithmetic, references,
FLOP and byte counts, peaks, trace reduction) lives in this package and
imports nothing of the program; only ``bench/sut.py`` and
``bench/run.py`` touch the system under test.
"""
