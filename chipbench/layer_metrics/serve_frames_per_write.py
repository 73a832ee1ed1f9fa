"""Serve runtime: how many ``data:`` frames one ``resp.write`` of the
proxy carries — ``chunks`` over ``writes`` of the ``http:`` spans, summed
over the traffic's sampled streams that wrote a frame in the window.
The owner pushes a stream's items to the request's handler, which
writes what one wake-up finds on its queue in one call: 1.0 means the
proxy keeps up (the engine makes one item a stream a step), above it
the proxy is behind, tokens reach the client in bunches and the
client's gaps stop being the engine's.

A program whose ``http:`` spans carry no ``writes`` (the parent of the
PR that added it: a pull and a write a frame) gives no metric."""

from chipbench.layer_metrics.engine_itl_p95_ms import in_window, streams


def read(obs):
    chunks = writes = 0
    for stream in streams(obs):
        http = stream["http"]
        attrs = http["attrs"]
        if attrs.get("writes") and any(
                in_window(obs, http["ts"] + 0.001 * frame)
                for frame in attrs["frame_ms"]):
            chunks += attrs["chunks"]
            writes += attrs["writes"]
    if not writes:
        return None
    return chunks / writes
