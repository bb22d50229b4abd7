"""On-chip benchmark of the HybridFlow serving stack.

One command runs one cell (a model configuration under a traffic mix) on
the accelerator and prints one JSON line::

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: ``configs/<config>.json`` (the
model as run, with its published reference and its ``family``),
``families/<family>.py`` (the family's weights, program layout and plain
reference), ``traffic/<mix>.json`` (the arrival process and length
distributions, and the entry ``drive_<entry>.py`` that serves them),
``cells/<workload>.json`` (the deployment and the correctness limit) and
``metrics/<metric>.py`` (one reader per per-layer metric).
``BENCHMARK.json`` at the checkout root lists them.
"""
