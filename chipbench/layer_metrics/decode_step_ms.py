"""Model step: device time of the decode program and the sampling
program per decode step (XLA module names ``jit__decode``,
``jit__sample_batch``).  Device trace."""

from chipbench.trace_reduce import program_time

DECODE, SAMPLE = r"_decode$", r"_sample_batch$"


def read(obs):
    decode = program_time(obs.get("trace"), DECODE)
    if not decode:
        return None
    sample = program_time(obs.get("trace"), SAMPLE) or (0, 0.0)
    return 1000.0 * (decode[1] + sample[1]) / decode[0]
