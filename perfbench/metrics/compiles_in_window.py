"""Compilations between the window's two ends (the same counter). A run that reads
anything but 0 here has already failed."""


def read(run):
    return run.compiles_in_window
