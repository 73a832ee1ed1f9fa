"""Engine loop: tokens a decode step produced, mean over the traced
window: ``LLMEngine.stats["tokens_generated"]`` delta (read by the
owner at trace start and stop) over the executions of the decode
program in the trace."""

from chipbench.trace_reduce import program_time

DECODE = r"_decode$"


def read(obs):
    traced, found = obs.get("traced"), program_time(obs.get("trace"), DECODE)
    if not traced or not found:
        return None
    tokens = traced["engine"]["tokens_generated"] \
        - traced["engine_before"]["tokens_generated"]
    return tokens / found[0]
