"""setup_s: process start to the window's start (loading, weights,
warm-up, compiles or cache loads), host clock."""


def read(run):
    return run.setup_s
