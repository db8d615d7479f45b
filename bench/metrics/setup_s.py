"""Process start to the window's start: build, compile or cache load,
warm-up, and the fill that sends every client's first request through
prefill."""


def read(run):
    return run.setup_s
