"""Kernel dispatch on the host: per request, the union of kernel-call
intervals less the device-busy time inside them (padding, copies,
launch), mean over the traced window."""


def read(run):
    ms = run.mean_layer("dispatch_host")
    return None if ms is None else 1e3 * ms
