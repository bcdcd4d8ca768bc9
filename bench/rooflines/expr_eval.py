"""expr_eval: each instruction of the compiled program once per row."""

from bench.harness.roofline import io_bytes


def cost(result, prog, icols, fcols, backend=None):
    rows = icols.shape[-1] if icols.ndim else 0
    return rows * len(prog.instrs), io_bytes(result, icols, fcols)
