"""The chip benchmark's harness: manifest, traffic, load loops, trace reduction.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in files of its own under ``bench/`` and is found by the name
``BENCHMARK.json`` gives it; this package is the general code that reads
them.  It imports nothing of the program at import time.
"""
