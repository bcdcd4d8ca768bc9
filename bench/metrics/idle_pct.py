"""Share of the traced window in which no operation ran on the device."""

from bench.harness import stats


def read(run):
    if not run.on_device:
        return None
    return 100.0 * (1.0 - stats.length(run.busy) / run.window_s)
