"""Model step: device time per execution of the program that runs a
prefill chunk and a decode step together (XLA module name
``jit__mixed_step``), the sampling program not included.  Device trace.
A program without it, or a window in which no chunk met a decode step,
reports nothing."""

from chipbench.trace_reduce import program_time

MIXED = r"_mixed_step$"


def read(obs):
    found = program_time(obs.get("trace"), MIXED)
    return 1000.0 * found[1] / found[0] if found else None
