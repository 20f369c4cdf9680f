"""Seconds from the start of the process to the start of the window: data,
session, loads and the warm-up with its compiles."""


def read(run):
    return run.setup_s
