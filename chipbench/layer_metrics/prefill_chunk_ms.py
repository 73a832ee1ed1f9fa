"""Model step: device time per execution of the prefill-chunk program
(XLA module name ``jit__prefill_chunk``).  Device trace."""

from chipbench.trace_reduce import program_time

CHUNK = r"_prefill_chunk$"


def read(obs):
    found = program_time(obs.get("trace"), CHUNK)
    return 1000.0 * found[1] / found[0] if found else None
