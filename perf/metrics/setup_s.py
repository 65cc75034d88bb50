"""Process start to the first measured step or request."""


def read(run):
    return run.setup_s
