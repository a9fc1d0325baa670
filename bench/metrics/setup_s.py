"""Set-up: process start to the window's start — weight draw, every
compile or cache load, the warm-up of each reachable shape and the
warm-up traffic."""


def read(run):
    return run.setup_s
