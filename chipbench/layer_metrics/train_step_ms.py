"""Model step: device time per execution of the train-step program
(XLA module name ``jit_train_step``), device 0.  Device trace."""

from chipbench.trace_reduce import program_time

STEP = r"train_step$"


def read(obs):
    found = program_time(obs.get("trace"), STEP)
    return 1000.0 * found[1] / found[0] if found else None
