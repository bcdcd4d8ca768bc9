"""Host side of a device call: padding of kernel inputs to power-of-two
buckets, and the round trip that runs a jitted program on them.

Every Pallas entry point is jitted on its input shapes, so each new length
compiles a new program. The host wrappers pad every variable length up to
the next power of two, and never below the kernel's tile. That bounds the
compiled variants of a kernel to one per doubling of its inputs, and keeps
each length a multiple of the tile, as the TPU block specs require.

Under a traced request (``telemetry.trace_query``) both record on the
dispatch in flight (DESIGN.md §13): ``pad`` its logical and padded bytes,
``round_trip`` the instants of its launch, wait and copy and the bytes
that cross to the device and back. Each phase also opens a
``jax.profiler.TraceAnnotation`` named ``barq.<kernel>.<phase>``, so a
profiler session holds it on the device trace's clock.
"""

from __future__ import annotations

import functools
import time

import numpy as np

from repro.core import telemetry

# smallest bucket of the build-side partition sort (``ops.hash_build``),
# an XLA sort on the device rather than a Pallas kernel
SORT_TILE = 1024

ANNOTATION_PREFIX = "barq."

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def bucket(n: int, tile: int) -> int:
    """Smallest power of two >= max(n, tile); ``tile`` is a power of two."""
    return max(tile, 1 << max(int(n) - 1, 0).bit_length())


def pad(a, tile: int, fill, dtype=np.int32) -> np.ndarray:
    """Copy of ``a`` as ``dtype`` with its last axis padded with ``fill``
    to ``bucket(len, tile)``."""
    a = np.asarray(a, dtype=dtype)
    n = a.shape[-1]
    out = np.full(a.shape[:-1] + (bucket(n, tile),), fill, dtype=dtype)
    out[..., :n] = a
    d = telemetry.current_dispatch()
    if d is not None:
        d.pad_logical_bytes += a.nbytes
        d.pad_bytes += out.nbytes
    return out


@functools.lru_cache(maxsize=None)
def annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use."""
    import jax

    return jax.profiler.TraceAnnotation


def _on_duration(event: str, duration: float, fun_name: str = "?", **_kw) -> None:
    if event == _COMPILE_EVENT:
        telemetry.record_compile(fun_name, duration)


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT_EVENT:
        telemetry.record_cache_hit()


@functools.lru_cache(maxsize=None)
def _watch_compiles() -> None:
    """Register, once, the listener that charges each compile to the
    dispatch that caused it (``telemetry.record_compile``)."""
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def _launch(program, args, static):
    """Call ``program`` and request the copy of each output to the host,
    so that the copies follow the program on the device without waiting
    for the host to see it finish, and a multi-output program's copies
    overlap (requested one by one from ``np.asarray``, each would cost the
    host one more synchronisation with the device)."""
    out = program(*args, **static)
    for x in out if isinstance(out, tuple) else (out,):
        x.copy_to_host_async()
    return out


def _to_host(out):
    if isinstance(out, tuple):
        return tuple(np.asarray(x) for x in out)
    return np.asarray(out)


def round_trip(program, *args, **static):
    """Run the jitted ``program`` on ``args`` and return its outputs (an
    array or a tuple of arrays) as host numpy arrays. Under a traced
    request it records on the dispatch in flight: ``launch`` (the call,
    with argument transfer, dispatch and any compile, and the request for
    the outputs' copies), ``wait`` (``block_until_ready``: until the
    program has run), ``copy`` (``np.asarray`` of each output: what is
    left of the copies), the bytes of every host numpy argument and of the
    outputs."""
    _watch_compiles()
    d = telemetry.current_dispatch()
    if d is None:
        return _to_host(_launch(program, args, static))
    import jax

    note = annotation()
    name = ANNOTATION_PREFIX + d.kernel
    h2d = sum(a.nbytes for a in args if isinstance(a, np.ndarray))
    d.inputs = args
    t0 = time.perf_counter()
    with note(name + ".launch"):
        out = _launch(program, args, static)
    t1 = time.perf_counter()
    d.inputs = ()
    with note(name + ".wait"):
        jax.block_until_ready(out)
    t2 = time.perf_counter()
    with note(name + ".copy"):
        host = _to_host(out)
    t3 = time.perf_counter()
    d2h = sum(x.nbytes for x in host) if isinstance(host, tuple) else host.nbytes
    d.add_trip(t0, t1, t2, t3, h2d, d2h)
    return host
