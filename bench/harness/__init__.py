"""The benchmark harness: one general loop, driven by data files."""
