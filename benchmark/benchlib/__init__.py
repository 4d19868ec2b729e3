"""The yardstick: everything the benchmark measures with and decides by.

Nothing here imports the program (``oktopk_tpu``) except ``harness.py``,
which drives it from outside.
"""
