"""Process start to the window's opening: templates, prewarm compiles,
warm waves."""


def read(run):
    return run.setup_s
