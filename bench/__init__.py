"""Benchmark of the search service on the chip.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace
<0|1>`` runs one cell of ``BENCHMARK.json``.  Everything that belongs to one
model configuration, traffic mix, cell or per-layer metric is a file of its
own that :mod:`bench.registry` finds by name:

* ``bench/configs/<config>.json`` — the model at its published widths;
* ``bench/archs/<arch>.py`` — the architecture a configuration names under
  ``"arch"`` (``dense`` by default): the program's model configuration, a
  plain reference and its FLOP and byte counts (:mod:`bench.archs`);
* ``bench/traffic/<traffic>.json`` — parameters for the one generator,
  :mod:`bench.traffic`;
* ``bench/workloads/<cell>.json`` — the search spec, evaluator path, cache
  sizes and correctness limits of one cell;
* ``bench/metrics/<metric>.py`` — a reader with ``read(ctx)`` for one
  per-layer metric.
"""
