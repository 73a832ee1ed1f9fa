"""The Serve path's ceiling for streamed tokens, read on any host without
a chip: a deployment whose ``stream`` yields the engine's chunk dicts,
ONE "engine" thread in the replica handing every stream a token each
``--step-ms`` (all streams in step, as a decode step's rows are), and
``--streams`` SSE clients through the real controller-hosted HTTP
proxy.  No engine, no jax: what is timed is the replica's generator
threads, the object plane's stream and the proxy.

    python -m benchmarks.serve_stream_ceiling --streams 48 --step-ms 18 \\
        --tokens 600

prints one JSON line:

* ``offered_tok_s``   streams x 1000 / step-ms — what the "engine" makes;
* ``frames_s``        token frames the clients read a second, first
                      arrival to last;
* ``lag_ms_p50/p95``  from the engine thread's hand-over of a token
                      (its wall clock, stamped into the chunk) to the
                      client's read of its frame — replica + proxy +
                      client, one host; ``lag_ms_p95_last_fifth`` beside
                      it: a lag that grows through the run means the
                      offered rate is past the ceiling;
* ``gap_ms_p50/p95``  between a stream's consecutive frames at the
                      client (the client's ``itl``);
* ``frames_per_read`` frames over the reads that brought them — above
                      1 the proxy wrote tokens in bunches;
* ``step_ms_p50/p95`` the engine thread's own period: above
                      ``--step-ms`` the REPLICA's process is the ceiling
                      (its generator threads share the engine's GIL).

These are a CPU rig's numbers, never a device's: they size the Serve
path, and go into ``PERF.md`` marked as such.
"""

import argparse
import asyncio
import json
import queue
import threading
import time

import ant_ray_tpu as art
from ant_ray_tpu import serve
from chipbench.loadgen import percentile


class Ticker:
    """Every ``step_s`` one token to every stream, once ``streams`` of
    them wait — the engine loop's part, without an engine."""

    def __init__(self, streams: int, step_s: float):
        self._streams, self._step_s = streams, step_s
        self._queues: list = []
        self._lock = threading.Lock()
        self._periods: list = []

    def join(self, tokens: int) -> "queue.SimpleQueue":
        mine: queue.SimpleQueue = queue.SimpleQueue()
        with self._lock:
            self._queues.append(mine)
            if len(self._queues) == self._streams:
                threading.Thread(target=self._run, args=(tokens,),
                                 daemon=True).start()
        return mine

    def _run(self, tokens: int) -> None:
        due = last = time.perf_counter()
        for k in range(tokens):
            due += self._step_s
            time.sleep(max(0.0, due - time.perf_counter()))
            now = time.perf_counter()
            self._periods.append(now - last)
            last = now
            at = time.time()
            for mine in self._queues:
                mine.put((k, at))
        for mine in self._queues:
            mine.put(None)

    def stream(self, request: dict):
        mine = self.join(request["tokens"])
        while (item := mine.get()) is not None:
            k, at = item
            yield {"object": "text_completion.chunk",
                   "choices": [{"index": 0, "text": " tok", "token_id": k,
                                "finish_reason": None}],
                   "done": False, "at": at}
        yield {"object": "text_completion.chunk",
               "choices": [{"index": 0, "text": "",
                            "finish_reason": "length"}], "done": True}

    def __call__(self, request=None):
        """The engine thread's periods, seconds."""
        return self._periods


async def client(session, url: str, tokens: int, out: list):
    """One SSE stream: ``(arrival wall, hand-over wall, frames this read
    brought)`` per token frame."""
    async with session.post(url, json={"stream": True,
                                       "tokens": tokens}) as resp:
        assert resp.status == 200, resp.status
        pending = b""
        async for data in resp.content.iter_any():
            now = time.time()
            *lines, pending = (pending + data).split(b"\n\n")
            chunks = [json.loads(line[len(b"data: "):]) for line in lines
                      if line != b"data: [DONE]"]
            out += [(now, chunk["at"], len(chunks)) for chunk in chunks
                    if not chunk["done"]]


async def drive(url: str, streams: int, tokens: int) -> list:
    import aiohttp

    frames: list = [[] for _ in range(streams)]
    timeout = aiohttp.ClientTimeout(total=None)
    connector = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout,
                                     connector=connector) as session:
        await asyncio.gather(*[
            client(session, url, tokens, frames[i])
            for i in range(streams)])
    return frames


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--streams", type=int, default=48)
    parser.add_argument("--step-ms", type=float, default=18.0)
    parser.add_argument("--tokens", type=int, default=600)
    args = parser.parse_args(argv)

    art.init(num_cpus=4, num_tpus=0)
    try:
        deployment = serve.deployment(
            name="ticker", route_prefix="/ticker",
            max_ongoing_requests=args.streams + 1)(Ticker)
        handle = serve.run(deployment.bind(args.streams,
                                           args.step_ms / 1000.0), port=0)
        url = f"http://127.0.0.1:{serve.run.last_http_port}/ticker"
        frames = asyncio.run(drive(url, args.streams, args.tokens))
        periods = handle.call()
    finally:
        serve.shutdown()
        art.shutdown()

    every = sorted(f for stream in frames for f in stream)
    assert len(every) == args.streams * args.tokens, len(every)
    lags = [1000.0 * (now - at) for now, at, _ in every]
    gaps = [1000.0 * (b[0] - a[0])
            for stream in frames for a, b in zip(stream, stream[1:])]
    reads = sum(1.0 / n for _, _, n in every)
    print(json.dumps({
        "streams": args.streams, "step_ms": args.step_ms,
        "tokens": args.tokens,
        "offered_tok_s": args.streams * 1000.0 / args.step_ms,
        "frames_s": len(every) / (every[-1][0] - every[0][0]),
        "lag_ms_p50": percentile(lags, 50),
        "lag_ms_p95": percentile(lags, 95),
        "lag_ms_p95_last_fifth": percentile(lags[-len(lags) // 5:], 95),
        "gap_ms_p50": percentile(gaps, 50),
        "gap_ms_p95": percentile(gaps, 95),
        "frames_per_read": len(every) / reads,
        "step_ms_p50": 1000.0 * percentile(periods, 50),
        "step_ms_p95": 1000.0 * percentile(periods, 95),
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
