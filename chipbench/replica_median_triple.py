"""``MedianPairProbeLLMServer`` with a THIRD probe, a short one, for a
model in which few layers attend over positions: all three medians go
into ``rel_l2``, so that the harness, which compares the largest entry
with the tolerance, holds the program to all three.

Why (``configs/granite-4.0-h-small.json``, ``tolerance.why``): with one
softmax layer to nine state-space layers, and random weights, what the
softmax layer adds behind a context of thousands of tokens is an average
over thousands of values — small beside the other layers' output — so a
softmax scale of head_dim^-1/2 in place of the configuration's
``attention_multiplier`` moves the logits of the two long probes by
0.04 to 0.05, twice what bfloat16 does and inside any tolerance with
room over it.  Behind ``serve.probe_short_prompt.tokens`` tokens (one
padded chunk, then the decode steps) the same fault reads 0.15.  It
runs in the same slot after the other two, from a ``start`` of 0.

The request path, the probes themselves, the trace and the owner's
counters are ``replica.py``'s, untouched.
"""

from __future__ import annotations

from chipbench.replica_median import MedianProbeLLMServer
from chipbench.replica_median_pair import MedianPairProbeLLMServer


class MedianTripleProbeLLMServer(MedianPairProbeLLMServer):

    def probe_logits(self, seed: int, prompt_tokens: int,
                     decode_steps: int) -> dict:
        short = self._spec["serve"]["probe_short_prompt"]["tokens"]
        pair = super().probe_logits(seed, prompt_tokens, decode_steps)
        third = MedianProbeLLMServer.probe_logits(self, seed, short,
                                                  decode_steps)
        return {
            **pair,
            "rel_l2": pair["rel_l2"] + third["rel_l2"],
            "rel_l2_by_position": pair["rel_l2_by_position"]
            + [third["rel_l2_by_position"]],
            "prompt_tokens": pair["prompt_tokens"] + [short],
            **{key: pair[key] + third[key]
               for key in ("argmax_equal", "positions", "system_s",
                           "seconds")},
        }
