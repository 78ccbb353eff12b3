"""On-chip benchmark of the PPR recommendation service.

Driven by ``BENCHMARK.json`` at the checkout root: every configuration,
traffic mix, generator, metric and correctness limit is a file of its own
under this directory, found by the name the manifest gives it.  ``run.py``
runs one cell once; ``harness.py`` holds the run itself.
"""
