"""Engine loop: the request's first prefill program dispatched -> its
first token handed to the stream (its chunks, the decode steps between
them, the sample that ends the prompt): the ``prefill`` stage of the
``llm:engine`` span, mean over the requests the proxy received inside
the window."""

from chipbench.layer_metrics import engine_queue_mean_ms


def read(obs):
    return engine_queue_mean_ms.read(obs, "prefill")
