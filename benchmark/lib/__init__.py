"""The benchmark's own code: lookup, traffic, trace reduction, references.

Nothing here imports the program except ``traffic.py``, which drives the
system under test through its public entry points.
"""
