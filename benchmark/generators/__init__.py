"""Generators, found by the name a configuration or traffic file gives.

A tree generator has ``build(root, params, rng)`` and writes a tree.  A
traffic generator has ``step(root, params, rng, ctx) -> Path``: it takes
the tree of the generation before (``root``), makes the next generation,
and returns the directory to back up.  ``ctx`` holds ``generation`` (1 for
the first after generation 0), ``work`` (a scratch directory of the run)
and ``seed`` (the run's).  ``rng`` is drawn from the run's seed and the
generation, so the same seed gives the same bytes.
"""
