"""Seconds from the process's start to the window's: imports, loading or
building the graph, the trainer's construction and the warm-up (which
compiles, or loads from the compile cache)."""


def read(run):
    return run.setup_s
