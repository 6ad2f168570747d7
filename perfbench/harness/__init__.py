"""The benchmark's harness: everything `perfbench/run.py` needs that is not data."""
