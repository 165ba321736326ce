"""Seconds from the start of the process to the start of the window:
imports, the trainer's build and compiles (cache loads after the first run
in a checkout), the weights, and the check steps that warm every shape."""


def read(run):
    return run.setup_s
