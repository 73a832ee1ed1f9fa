"""``MedianProbeLLMServer`` for a model whose layers keep a recurrent
STATE: the logit-parity probe runs at TWO geometries and reports both
medians in ``rel_l2``, so that the harness, which compares the largest
entry with the tolerance, holds the program to both.

Why (``configs/solar-open2.json``, ``tolerance.why``): the traffic
file's probe — whole chunks, then decode steps — compares positions
that follow a full chunk -> full chunk -> decode hand-over, the end of
every long prompt; but those positions lie a whole chunk behind the
last boundary, and a delta rule forgets by itself over that many
tokens, so a state that was NOT handed from chunk to chunk reads there
what the sound program reads.  The second geometry ends the prompt
``serve.probe_short_last_chunk.tokens_behind_boundary`` tokens behind
a boundary (one whole chunk fewer; the last chunk padded): there a
dropped state is most of the answer.  It runs in the same slot after
the first, from a ``start`` of 0: what the first probe left is the
slot's last occupant's state.

The request path, the probe itself, the trace and the owner's counters
are ``replica.py``'s, untouched.
"""

from __future__ import annotations

from chipbench.replica_median import MedianProbeLLMServer


class MedianPairProbeLLMServer(MedianProbeLLMServer):

    def probe_logits(self, seed: int, prompt_tokens: int,
                     decode_steps: int) -> dict:
        behind = self._spec["serve"]["probe_short_last_chunk"][
            "tokens_behind_boundary"]
        chunk = self.engine._chunk_tokens
        short = (prompt_tokens - 1) // chunk * chunk + behind
        probes = [super().probe_logits(seed, n, decode_steps)
                  for n in (prompt_tokens, short)]
        whole, last = probes
        return {
            **whole,
            "rel_l2": whole["rel_l2"] + last["rel_l2"],
            "rel_l2_by_position": [p["rel_l2_by_position"] for p in probes],
            "prompt_tokens": [prompt_tokens, short],
            **{key: sum(p[key] for p in probes)
               for key in ("argmax_equal", "positions", "system_s",
                           "seconds")},
        }
