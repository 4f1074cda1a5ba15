"""The chip benchmark of the graph engine: ``python3 bench/run.py``.

See ``bench/README.md``.  Nothing in ``src/`` imports this package.
"""
