"""The benchmark's harness: everything that is the same for every cell.

Nothing in this package names a cell, a configuration or a model; those
live in data files that ``spec`` resolves by the names in
``BENCHMARK.json``.
"""
