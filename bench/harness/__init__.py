"""The benchmark's own code: cell loading, traffic generation, weights,
the serving drive, the plain float32 reference and the check that decides
``correct``, the trace reduction and the operation/byte counts.

Nothing here is imported by the program under test, and nothing here
imports the program except ``serve.py``, which drives it.
"""
