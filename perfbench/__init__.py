"""The FFCz on-chip benchmark (see ``perfbench/harness.py`` and ``PERF.md``)."""
