"""The chip benchmark: one cell, one process, one result line.

``bench/run.py`` is the command.  Everything a cell is made of lives in
files of its own, found by the names in ``BENCHMARK.json``:

  configs/<config>.json     sizes and the deployment it stands for
  reference/<name>.py       the plain reference a configuration names
  traffic/<mix>.json        parameters the one generator (traffic.py) reads
  workloads/<cell>.json     config, mix, chips, why and server parameters
  metrics/<metric>.py       a reader: ``read(run) -> float | None``
  runners/<runner>.py       the path a configuration drives

The yardstick (traffic generation, the trace reduction, the peaks table,
the operation and byte counts, the references and the comparison that
decides ``correct``) lives here, apart from the program under test.
"""
