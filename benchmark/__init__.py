"""The chip benchmark: harness, yardstick and plain references.

Everything a cell needs is found by the names in ``BENCHMARK.json``; see
``benchmark/README.md``.  Nothing here is imported by ``pathway_tpu``.
"""
