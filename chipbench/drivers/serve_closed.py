"""``kind: serve_closed`` — callers that each wait for a reply:
``clients`` of them, each sending its next request when the last one
has ended."""

import asyncio

from chipbench import loadgen, serving


def start_traffic(client, cell, seed, vocab, start, end, probes):
    traffic = cell.traffic
    streams = [loadgen.client_stream(traffic, seed, vocab, c)
               for c in range(traffic["clients"])]

    async def make_event():
        return asyncio.Event()

    stop = client.run(make_event()).result(timeout=30)
    # The callers start at once: the ramp fills the slots before the
    # window opens (``start`` is the ramp's length, negated).
    future = client.run(client.closed_loop(
        streams, traffic["think_s"], stop, probes))
    return future, lambda: client._loop.call_soon_threadsafe(stop.set)


def run(cell, args) -> dict:
    return serving.run(cell, args, start_traffic)
