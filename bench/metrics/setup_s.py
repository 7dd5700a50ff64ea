"""Seconds from the process's start to the window's opening: imports,
weights from the seed, server build, compiles or cache loads, warm-up and
the lead-in traffic."""


def read(run):
    return run.setup_s
