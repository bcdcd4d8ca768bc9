"""Set-up time: from process start to the first request of the window
(JAX start-up, store build, compile or cache load, warm-up)."""


def read(run):
    return run.setup_s
