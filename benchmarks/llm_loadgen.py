"""Multi-client closed-loop load generator for the LLM serving path.

Drives an :class:`~ant_ray_tpu.llm.engine.EngineLoop` (or anything with
its ``submit(prompt, sampling, session_id=...) -> handle`` shape) with a
mix of client populations — short interactive prompts, long-prompt
ingesters, and pausing sessions that go idle between turns (the shape
that exercises KV offload/restore under load).  Collects per-population
TTFT samples and whole-run token throughput.

Used by benchmarks/microbench.py for the guarded
``llm_ttft_short_p50_us`` / ``llm_ttft_short_p99_us`` /
``llm_tokens_per_s`` / ``llm_resident_sessions`` numbers, and by the
`slow` soak test in tests/test_llm_sessions.py.

Prompts are synthetic token-id lists (tiny-config vocab), deterministic
per client index — two runs see identical offered work.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field


@dataclass
class ClientSpec:
    """One client population.

    ``count`` closed-loop clients each submit a ``prompt_tokens``-token
    prompt, wait for the final output, think for ``think_time_s``, and
    repeat.  ``session=True`` gives each client a persistent session id
    and ``pause_s`` of idle time between turns (long enough pauses vs
    the engine's ``kv_idle_evict_s`` force offload→restore cycles).
    """

    name: str
    prompt_tokens: int
    max_tokens: int
    count: int = 1
    think_time_s: float = 0.0
    session: bool = False
    pause_s: float = 0.0
    turns: int | None = None          # None = until duration elapses


@dataclass
class LoadReport:
    duration_s: float = 0.0
    started: int = 0
    finished: int = 0
    shed: int = 0
    failed: int = 0
    tokens: int = 0
    ttft_us: dict = field(default_factory=dict)   # name -> [us, ...]
    errors: list = field(default_factory=list)

    def percentile(self, name: str, q: float) -> float:
        """q in [0, 100] over one population's TTFT samples (µs)."""
        samples = sorted(self.ttft_us.get(name, ()))
        if not samples:
            return float("nan")
        idx = min(len(samples) - 1,
                  max(0, round(q / 100.0 * (len(samples) - 1))))
        return samples[idx]

    def tokens_per_s(self) -> float:
        return self.tokens / self.duration_s if self.duration_s else 0.0


class LoadGen:
    """Closed-loop driver over an EngineLoop-shaped ``submit``."""

    def __init__(self, loop, *, vocab: int = 250, seed: int = 0):
        self._loop = loop
        self._vocab = vocab
        self._seed = seed

    def _prompt(self, spec: ClientSpec, client: int, turn: int) -> list:
        # Deterministic, arm-independent synthetic prompt; avoid token
        # ids near vocab edge (eos of the byte tokenizer is 255).
        base = (self._seed * 7919 + hash(spec.name) % 1000
                + client * 131 + turn * 17)
        return [2 + (base + i * 37) % (self._vocab - 3)
                for i in range(spec.prompt_tokens)]

    def run(self, specs, duration_s: float, *,
            wait_timeout_s: float = 120.0) -> LoadReport:
        from ant_ray_tpu.exceptions import BackPressureError  # noqa: PLC0415
        from ant_ray_tpu.llm import SamplingParams  # noqa: PLC0415

        report = LoadReport()
        lock = threading.Lock()
        stop_at = time.monotonic() + duration_s

        def client_loop(spec: ClientSpec, idx: int):
            sid = (f"{spec.name}-{idx}" if spec.session else None)
            turn = 0
            while time.monotonic() < stop_at and \
                    (spec.turns is None or turn < spec.turns):
                prompt = self._prompt(spec, idx, turn)
                sampling = SamplingParams(temperature=0.0,
                                          max_tokens=spec.max_tokens)
                sent = time.monotonic()
                try:
                    handle = self._loop.submit(prompt, sampling,
                                               session_id=sid)
                except BackPressureError as err:
                    with lock:
                        report.shed += 1
                    time.sleep(min(err.retry_after_s, 0.5))
                    continue
                with lock:
                    report.started += 1
                try:
                    # the caller's clock, to the stream's first event
                    first = handle.events.get(timeout=wait_timeout_s)
                    ttft = (time.monotonic() - sent
                            if first["type"] == "token" else None)
                    out = handle.wait(timeout=wait_timeout_s)
                except BaseException as exc:  # noqa: BLE001 — tallied
                    with lock:
                        report.failed += 1
                        report.errors.append(repr(exc))
                    continue
                with lock:
                    report.finished += 1
                    report.tokens += len(out.token_ids)
                    if ttft is not None:
                        report.ttft_us.setdefault(
                            spec.name, []).append(ttft * 1e6)
                turn += 1
                if spec.session and spec.pause_s:
                    time.sleep(spec.pause_s)
                elif spec.think_time_s:
                    time.sleep(spec.think_time_s)

        threads = [threading.Thread(target=client_loop,
                                    args=(spec, idx), daemon=True,
                                    name=f"loadgen-{spec.name}-{idx}")
                   for spec in specs for idx in range(spec.count)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=duration_s + 2 * wait_timeout_s)
        report.duration_s = time.monotonic() - t0
        return report
