"""Chip benchmark of the FlexiDiT serving engine.

One command runs one cell of ``BENCHMARK.json`` once on the chip it is
started on::

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own and is found by name:
``configs/<config>.json`` (sizes, with the three modules it names: the
plain reference, the program's builder and condition draw, the needed
work; see :mod:`spec`), ``traffic/<traffic>.json`` (parameters for
:mod:`generator`), ``arrivals/<arrival>.py`` (an arrival process a mix
names, with the loop of :mod:`loops` that serves it),
``metrics/<metric>.py`` (a reader of the run's records or trace) and ``limits/<cell>.json`` (the
correctness limit and the readings it was set from). Adding a cell, a
mix, an arrival process, a metric or a configuration of another model
family adds files and entries only.
"""
