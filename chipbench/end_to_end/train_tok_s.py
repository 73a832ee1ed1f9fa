"""Tokens of the steps that completed (loss fetched) inside the window,
all chips together, over the time from the window's opening (a step
boundary) to the last completion in it.  Dividing by the nominal
window instead would quantise the metric to one step in some forty
(2.5 %), wider than any bound worth setting; see PERF.md, section 2."""


def read(obs):
    m = obs["train"]
    if not m or not m["steps_done_in_window"]:
        return None
    return m["steps_done_in_window"] * m["tokens_per_step"] \
        / m["window_used_s"]
