"""On-chip benchmark of the serving stack (see BENCHMARK.json and PERF.md).

Everything a cell needs is data found by name: ``configs/<config>.json``
(the model as it is run), ``traffic/<traffic>.json`` (the request mix),
``cells/<workload>.json`` (the cell's correctness limits) and
``metrics/<metric>.py`` (one reader per metric).
"""
