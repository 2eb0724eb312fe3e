"""chipbench: the on-chip benchmark. One harness (``run.py``) driven by
the data files beside it; ``README.md`` says how to add to it."""
