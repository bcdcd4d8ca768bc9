"""Share of the roofline over all kernel calls of the traced window: the
sum of each call's least time on the chip (the larger of its logical
operations over the peak operation rate and its logical bytes over the
peak HBM bandwidth, from ``bench/rooflines/<kernel>.py`` and
``bench/peaks.json``), over the sum of those calls' device time."""


def read(run):
    if not run.on_device:
        return None
    least = spent = 0.0
    for call in run.calls:
        t = run.min_time(call)
        if t is not None and call.device_s > 0:
            least += t
            spent += call.device_s
    return None if spent == 0 else 100.0 * least / spent
