"""Chip benchmark of the ``cgra_exec`` execution path.

One cell is one deployment (``configs/<name>.json``) under one traffic
mix (``traffic/<mix>.json``); ``BENCHMARK.json`` at the repository root
names the cells and their metrics, and ``python3 chipbench/run.py``
runs one cell once.  Everything that measures lives here, apart from the
system under test: the traffic generator, the plain references, the
trace reduction, the byte counts and the table of peaks.
"""
