"""Set-up seconds: process start to the window's start (loading, building
the weights, compiling or loading compiled programs, warm-up, lead-in)."""


def read(run):
    return run.setup_s
