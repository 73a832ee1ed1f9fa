"""Model step: the share of decode steps whose sampler sorted the
vocabulary, over the traced window:
``LLMEngine.stats["sample_sorted_steps"]`` (a step counts where an
active row samples with top-k or top-p: the rule the one sampler
program applies on the device to the same rows) over ``decode_steps``;
deltas between the owner's readings at trace start and stop.  0 means
no step of the window paid for a sort; a program whose sampler sorts on
every step whatever is asked has no such counter, and the metric is
left out."""

from chipbench.layer_metrics.loop_host_ms_per_step import deltas


def read(obs):
    found = deltas(obs, "sample_sorted_steps", "decode_steps")
    if not found or found[1] <= 0:
        return None
    return 100.0 * found[0] / found[1]
