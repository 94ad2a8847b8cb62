"""OLMo-1B at its published widths, 4 of 16 layers (``olmo-1b-l4.json``):
the builder of its weights, its adapter to the program and its plain
reference are OLMo-1B's own, in ``bench/olmo.py``."""
from bench.olmo import *  # noqa: F401,F403
