"""Set-up: process start to the window's start (weights, programs loaded
or compiled, warm-up traffic).  Host clock."""


def read(run):
    return run.setup_s
