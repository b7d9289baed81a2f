"""The chip benchmark's harness: one cell of ``BENCHMARK.json``, one run.

Everything that belongs to one configuration, traffic mix or metric lives in
a file of its own beside this package (``configs/``, ``traffic/``,
``metrics/``, ``references/``, ``adapters/``); the code here finds it by the
name the cell gives.
"""
