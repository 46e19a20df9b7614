"""Process start to the window's start: JAX's start-up, the graph, the
warm-up (and, on a checkout's first run, compilation); the reference's own
time left out."""


def read(run):
    return run.setup_s
