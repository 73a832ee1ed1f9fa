"""The one general traffic generator: every mix is a data file of
parameters (``chipbench/traffic/<mix>.json``) that this module reads.

* lengths: per request from a seeded log-normal (median, sigma),
  clipped to [min, max];
* prompts: token ids drawn from the seed (a request is ids, never text);
* sampling: one of the file's ``sampling`` entries by share; an entry
  marked ``seeded`` gets a per-request seed;
* open loop: Poisson arrivals at the rate the file fixes, each request
  timed from when it was DUE;
* the schedule (arrival times, lengths, sampling picks) comes from
  ``SCHEDULE_SEED`` and is the same in every run; ``--seed`` draws the
  token ids, the sampling seeds and the weights;
* closed loop: ``clients`` callers, each sending its next request when
  the last reply has ended (plus ``think_s``).

One asyncio thread sends every request over HTTP (``POST
/v1/completions``, ``"stream": true``) and stamps every streamed token
as it arrives.  The arithmetic on the log (percentiles, due time,
lateness, window edges) is here too, so that no later PR can change it.
Imports no jax.
"""

from __future__ import annotations

import asyncio
import json
import math
import statistics
import threading
import time
from dataclasses import dataclass, field

import numpy as np

LEGAL_FINISH = ("length", "stop")
SCHEDULE_SEED = 20260926     # one schedule for every mix, until a second is a cell


# ------------------------------------------------------------- requests

@dataclass
class Request:
    rid: int
    prompt: list
    max_tokens: int
    sampling: dict
    due: float | None = None          # seconds from the window's opening
    kind: str = "traffic"             # or "probe"
    # filled by the client, same clock as ``due``
    sent: float | None = None
    sent_wall: float | None = None
    status: int | None = None
    token_t: list = field(default_factory=list)
    tokens: list = field(default_factory=list)
    finish: str | None = None
    done: bool = False
    ended: float | None = None
    error: str | None = None

    def fresh(self) -> "Request":
        """The same request, not yet sent."""
        return Request(self.rid, self.prompt, self.max_tokens,
                       self.sampling, kind=self.kind)

    def body(self) -> dict:
        body = {"prompt": self.prompt, "max_tokens": self.max_tokens,
                "stream": True}
        body.update(self.sampling)
        return body


def lognormal_quantile(dist: dict, u: float) -> int:
    """The ``u`` quantile of the file's log-normal (median, sigma),
    clipped to [min, max]."""
    z = statistics.NormalDist().inv_cdf(min(max(u, 1e-9), 1 - 1e-9))
    value = dist["median"] * math.exp(dist["sigma"] * z)
    return int(min(dist["max"], max(dist["min"], round(value))))


def stratified_lengths(rng, dist: dict, n: int) -> list:
    """``n`` lengths, one from each of the ``n`` equal-probability strata
    of the distribution (a seeded point inside each), in seeded order.
    Every seed so offers the same amount of work, to within a stratum:
    the runs of a cell then differ by the system, not by the draw."""
    lengths = [lognormal_quantile(dist, (i + rng.random()) / n)
               for i in range(n)]
    rng.shuffle(lengths)
    return lengths


def schedule_rng(stream: int):
    """Arrival times, lengths and sampling picks come from
    ``SCHEDULE_SEED``, not from ``--seed``: every run of a cell offers
    the same work at the same times, so that runs differ by the system
    and not by the draw (the few tens of requests a window holds would
    otherwise spread any statistic of them by tens of percent between
    seeds).  ``--seed`` draws the token ids, the per-request sampling
    seeds and the weights."""
    return np.random.default_rng([SCHEDULE_SEED, stream])


def draw_requests(rng, ids, traffic: dict, vocab: int, n: int,
                  first_rid: int = 0) -> list:
    """``n`` requests: stratified prompt and output lengths (shuffled
    independently) and sampling entries dealt by share, in the
    schedule's order (``rng``); token ids and sampling seeds from
    ``ids`` (the run's ``--seed``)."""
    prompts = stratified_lengths(rng, traffic["prompt_tokens"], n)
    outputs = stratified_lengths(rng, traffic["output_tokens"], n)
    picks = []
    for i, entry in enumerate(traffic["sampling"]):
        picks += [i] * int(round(entry["share"] * n))
    picks = (picks + [0] * n)[:n]
    rng.shuffle(picks)
    out = []
    for k in range(n):
        pick = traffic["sampling"][picks[k]]
        sampling = {key: v for key, v in pick.items()
                    if key not in ("share", "seeded")}
        if pick.get("seeded"):
            sampling["seed"] = int(ids.integers(0, 2 ** 31 - 1))
        out.append(Request(first_rid + k,
                           ids.integers(0, vocab, prompts[k]).tolist(),
                           outputs[k], sampling))
    return out


def open_schedule(traffic: dict, seed: int, vocab: int, rate: float,
                  start: float, end: float) -> list:
    """Requests due in [start, end), seconds from the window's opening
    (``start`` is negative: the ramp).  Poisson arrivals at ``rate``
    conditioned on their expected number: ``round(rate x length)``
    arrival times, independent and uniform over the span, from
    ``SCHEDULE_SEED`` (given its count, a Poisson process is exactly
    that).  The ramp and
    the window are drawn apart, so that the window's count is fixed."""
    process = traffic["arrivals"]["process"]
    if process != "poisson":
        raise ValueError(f"arrival process {process!r} is not built; "
                         "see PERF.md, Open questions")
    rng, ids = schedule_rng(1), np.random.default_rng([seed, 1])
    out = []
    for lo, hi in ((start, min(0.0, end)), (max(0.0, start), end)):
        n = int(round(rate * (hi - lo))) if hi > lo else 0
        due = np.sort(rng.uniform(lo, hi, n))
        for req, t in zip(draw_requests(rng, ids, traffic, vocab, n,
                                        len(out)), due):
            req.due = float(t)
            out.append(req)
    return out


def client_stream(traffic: dict, seed: int, vocab: int, client: int):
    """The endless request stream of one closed-loop client: its share
    of a stratified pool of ``16 x clients`` requests, cycled."""
    clients = traffic["clients"]
    pool = draw_requests(schedule_rng(2),
                         np.random.default_rng([seed, 2]), traffic, vocab,
                         16 * clients)
    mine, n = pool[client::clients], 0
    while True:
        req = mine[n % len(mine)].fresh()
        req.rid = client * 1_000_000 + n
        yield req
        n += 1


def probe_requests(traffic: dict, vocab: int) -> list:
    """Fixed greedy prompts (the same in every run and every seed):
    answered alone during set-up and again among the traffic."""
    spec = traffic["probe_prompts"]
    rng = np.random.default_rng(20240229)
    return [Request(-1 - i, rng.integers(0, vocab,
                                         spec["prompt_tokens"]).tolist(),
                    spec["output_tokens"], {"temperature": 0.0},
                    kind="probe") for i in range(spec["count"])]


# --------------------------------------------------------------- client

class Client:
    """One thread, one asyncio loop, every request of a run."""

    def __init__(self, port: int):
        self._url = f"http://127.0.0.1:{port}/v1/completions"
        self._loop = asyncio.new_event_loop()
        self._thread = threading.Thread(target=self._loop.run_forever,
                                        name="loadgen", daemon=True)
        self._thread.start()
        self._session = None
        self.t0 = time.perf_counter()     # re-based by ``open_window_at``
        self.log: list = []

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def run(self, coro):
        return asyncio.run_coroutine_threadsafe(coro, self._loop)

    async def _ensure_session(self):
        if self._session is None:
            import aiohttp

            self._session = aiohttp.ClientSession(
                connector=aiohttp.TCPConnector(limit=0),
                timeout=aiohttp.ClientTimeout(total=None))
        return self._session

    async def send(self, req: Request) -> Request:
        session = await self._ensure_session()
        self.log.append(req)
        req.sent, req.sent_wall = self.now(), time.time()
        try:
            async with session.post(self._url, json=req.body()) as resp:
                req.status = resp.status
                if resp.status != 200:
                    req.error = (await resp.text())[:300]
                    return req
                async for raw in resp.content:
                    line = raw.decode().strip()
                    if not line.startswith("data: "):
                        continue
                    payload = line[len("data: "):]
                    if payload == "[DONE]":
                        req.done = True
                        break
                    choice = json.loads(payload)["choices"][0]
                    if choice.get("token_id") is not None:
                        req.token_t.append(self.now())
                        req.tokens.append(choice["token_id"])
                    req.finish = choice.get("finish_reason") or req.finish
        except asyncio.CancelledError:
            req.error = req.error or "cancelled at the end of the run"
            raise
        except Exception as e:  # noqa: BLE001 — judged from the log
            req.error = repr(e)[:300]
        finally:
            req.ended = self.now()
        return req

    async def open_loop(self, schedule: list, extra: list = ()):
        """Send each request when it is due, whatever the server does."""
        tasks = []
        for req in sorted([*schedule, *extra], key=lambda r: r.due):
            delay = req.due - self.now()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.ensure_future(self.send(req)))
        return tasks

    async def closed_loop(self, streams: list, think_s: float,
                          stop: asyncio.Event, extra: list = ()):
        """``len(streams)`` callers; ``extra`` (the probes) are sent
        open-loop at their due times beside them."""
        async def caller(stream):
            while not stop.is_set():
                await self.send(next(stream))
                if think_s:
                    await asyncio.sleep(think_s)

        tasks = [asyncio.ensure_future(caller(s)) for s in streams]
        tasks += await self.open_loop(list(extra))
        return tasks

    async def finish(self, tasks: list, drain_s: float):
        """Wait ``drain_s`` for what is in flight, then cancel the rest."""
        if tasks:
            _, pending = await asyncio.wait(tasks, timeout=drain_s)
            for task in pending:
                task.cancel()
            await asyncio.gather(*pending, return_exceptions=True)

    def close(self):
        async def _close():
            if self._session is not None:
                await self._session.close()

        self.run(_close()).result(timeout=30)
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=30)


# ----------------------------------------------------------- arithmetic

def percentile(values, q: float) -> float:
    """Linear interpolation between order statistics (numpy's default,
    Hyndman-Fan 7).  +inf in the sample is kept: a miss is a miss."""
    data = sorted(values)
    if not data:
        return float("nan")
    pos = (len(data) - 1) * q / 100.0
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if data[hi] == float("inf"):
        return float("inf") if hi != lo or data[lo] == float("inf") \
            else data[lo]
    return data[lo] + (data[hi] - data[lo]) * (pos - lo)


def legal(req: Request) -> str | None:
    """Why a reply is not right, or None: HTTP 200, at most the asked
    tokens, a legal finish reason, the stream ended."""
    if req.status != 200:
        return f"HTTP {req.status}: {req.error}"
    if req.error:
        return req.error
    if len(req.tokens) > req.max_tokens:
        return f"{len(req.tokens)} tokens for max_tokens {req.max_tokens}"
    if req.done and req.finish not in LEGAL_FINISH:
        return f"finish reason {req.finish!r}"
    if req.done and req.finish == "length" and \
            len(req.tokens) != req.max_tokens:
        return (f"finish 'length' after {len(req.tokens)} of "
                f"{req.max_tokens} tokens")
    return None


def summarise(log: list, seconds: float) -> dict:
    """The client log -> what the end-to-end metrics and the load
    generator's own metric are made of.  Times are seconds from the
    window's opening; the window is [0, seconds).

    * attempted: traffic requests due (open loop) or sent (closed loop)
      inside the window; failed: those of them that were not right;
    * ttft: first streamed token minus DUE time (sent time in a closed
      loop), over the attempted; a failed or tokenless request is +inf;
    * gaps: between consecutive streamed tokens of one request, pooled
      over all traffic requests, a gap counted if it ENDS in the window;
    * tokens_in_window: output tokens received inside the window,
      whether or not their request ended in it;
    * late: sent minus due, over the attempted (open loop);
    * slowest: the three attempted requests with the longest ttft, as
      (origin, prompt tokens, ttft, late): printed in every run, so that
      a run whose tail stands out says which requests waited.
    """
    traffic = [r for r in log if r.kind == "traffic"]

    def origin(r):
        return r.due if r.due is not None else r.sent

    attempted = [r for r in traffic
                 if origin(r) is not None and 0 <= origin(r) < seconds]
    failed, ttft, late = [], [], []
    for r in attempted:
        why = legal(r)
        if why and not (r.status == 200 and r.error
                        and "cancelled" in r.error):
            failed.append((r.rid, why))
        if r.token_t and r.status == 200:
            ttft.append(r.token_t[0] - origin(r))
        elif r.done and r.finish == "stop" and not why:
            # End of sequence as the very first token: a success with
            # no token; its first event is the end of the stream.
            ttft.append(r.ended - origin(r))
        else:
            ttft.append(float("inf"))
        if r.due is not None and r.sent is not None:
            late.append(r.sent - r.due)
    gaps, tokens_in = [], 0
    for r in traffic:
        tokens_in += sum(1 for t in r.token_t if 0 <= t < seconds)
        gaps.extend(b - a for a, b in zip(r.token_t, r.token_t[1:])
                    if 0 <= b < seconds)
    slowest = sorted(zip(ttft, attempted), key=lambda p: -p[0])[:3]
    return {"attempted": len(attempted), "failed": len(failed),
            "failures": failed[:5], "ttft_s": ttft, "gaps_s": gaps,
            "slowest": [(origin(r), len(r.prompt), t,
                         r.sent - r.due if r.due is not None
                         and r.sent is not None else 0.0)
                        for t, r in slowest],
            "tokens_in_window": tokens_in, "late_s": late,
            "ended_in_window": sum(
                1 for r in traffic
                if r.done and r.ended is not None and 0 <= r.ended < seconds),
            "sends": [(r.sent_wall, r.max_tokens) for r in traffic
                      if r.sent_wall is not None],
            "requests": [(len(r.prompt), list(r.token_t)) for r in traffic]}
