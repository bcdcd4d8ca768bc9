"""Kernels on the device: per request, the union of the profiler trace's
device-op intervals inside the request's kernel calls, mean over the
traced window."""


def read(run):
    ms = run.mean_layer("device")
    return None if ms is None else 1e3 * ms
