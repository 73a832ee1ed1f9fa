"""``ProbeLLMServer`` for a probe whose positions come in two
populations: the logit-parity probe reports the MEDIAN position's
relative L2 as its one reading (``rel_l2``), with every position's
beside it (``rel_l2_by_position``).

Why (``configs/command-a-plus.json``, ``tolerance.why``): with a share of
a sigmoid router's experts held, an expert pick that flips at a near-tie
of the k-th and the next score under bfloat16 — one of the two held
here, the other not — moves ONE position's logits by 0.05 to 0.18, one
position in twenty-three, while lower precision or a wrong mask moves
EVERY position.  The harness compares the largest entry of ``rel_l2``
with one tolerance; over the worst position the two populations overlap
(a flipped position reads what a float8 reference reads everywhere),
over the median they lie a factor of fourteen apart.  A reading that is
not finite is reported as it is, and fails.

The request path, the probe itself, the trace and the owner's counters
are ``replica.py``'s, untouched.
"""

from __future__ import annotations

import math
import statistics

from chipbench.replica import ProbeLLMServer


class MedianProbeLLMServer(ProbeLLMServer):

    def probe_logits(self, seed: int, prompt_tokens: int,
                     decode_steps: int) -> dict:
        out = super().probe_logits(seed, prompt_tokens, decode_steps)
        by_position = out["rel_l2"]
        reading = (statistics.median(by_position)
                   if all(map(math.isfinite, by_position)) else math.inf)
        return {**out, "rel_l2": [reading],
                "rel_l2_by_position": by_position}
