"""Process start to the window's start: JAX start-up, corpus, store build,
compile or cache load, warm-up."""


def read(run):
    return run.setup_s
