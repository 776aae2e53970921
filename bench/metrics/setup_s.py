"""Set-up: process start to the window's open (graph, session and view
build, warm-up of every program the window calls, compiles or cache
loads)."""


def read(run):
    return run.setup_s
