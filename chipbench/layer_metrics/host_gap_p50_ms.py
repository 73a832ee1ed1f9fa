"""Engine loop: device time left idle between the end of one model-step
program (decode or prefill chunk) and the start of the next — the gap
minus what the small programs between them (sampling, key splitting)
ran — over steps whose gap is under 200 ms (a longer one is a wait for
work, not the loop's own cost); median.  Device trace.

The issue put the limit at 50 ms, expecting a gap of a few; the first
trace showed some 40 ms a step at 16 slots, so that limit would have
cut off the very thing measured."""

import re

from chipbench.loadgen import percentile

MODEL_STEP = re.compile(r"_decode$|_prefill_chunk$")
LIMIT_S = 0.200


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace.get("devices"):
        return None
    gaps, end, between = [], None, 0.0
    for name, start, dur in trace["devices"][0]["program_events"]:
        if not MODEL_STEP.search(name):
            between += dur
            continue
        if end is not None and start - end < LIMIT_S:
            gaps.append(max(0.0, start - end - between))
        end, between = start + dur, 0.0
    if len(gaps) < 10:
        return None
    return 1000.0 * percentile(gaps, 50)
