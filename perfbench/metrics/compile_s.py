"""Seconds the XLA backend spent compiling (or loading from the cache) before the
window opened: the program's `obs/compile_monitor.py` counter."""


def read(run):
    return run.compile_s
