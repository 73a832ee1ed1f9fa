"""Model step (window and full layers in one cache): what the window
layers' attention walks as a share of what it would walk without the
window, over the traced window's decode steps and chunks:
``LLMEngine.stats["window_span_positions"]`` (positions walked on the
window layers' rings, summed over those layers) over
``full_span_positions`` (positions walked on the full layers' slabs)
times window layers over full layers; deltas between the owner's
readings at trace start and stop.  100 where no context passes the
window (a ring is then walked as far as a slab: the mechanism is idle),
about 30 at contexts of 16k under a window of 4,096.  A program without
the counters, or a model without window layers (they stay at zero),
reports nothing."""

from chipbench import opsbytes_mixed
from chipbench.layer_metrics.loop_host_ms_per_step import deltas


def read(obs):
    found = deltas(obs, "window_span_positions", "full_span_positions")
    if not found or found[1] <= 0:
        return None
    n_window, n_full = opsbytes_mixed.layer_kinds(obs["config"])
    if not n_window or not n_full:
        return None
    return 100.0 * found[0] / (found[1] * n_window / n_full)
