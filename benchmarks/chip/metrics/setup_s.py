"""Process start to the start of the window: loading, warming up and,
in a run that compiles, compilation."""


def read(w):
    return w.setup_s
