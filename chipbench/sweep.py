"""``--sweep``: find an open-loop mix's knee once, by hand, on the chip.

One set-up, then the mix at each given rate for ``--seconds`` (after its
ramp), drained between rates.  The knee is the highest rate at which at
least ``attainment`` of the requests due got a first token within
``ttft_s`` and a mean gap within ``mean_gap_s`` (``knee_limits`` of the
traffic file) and the backlog did not grow.  The rate a cell runs at is
then written into the traffic file as a number: a run never searches.
"""

from __future__ import annotations

import time

from chipbench import loadgen, serving
from chipbench.drivers import serve_open
from chipbench.session import Session, log


def row(log_: list, seconds: float, limits: dict) -> dict:
    summary = loadgen.summarise(log_, seconds)
    due = [r for r in log_ if r.kind == "traffic" and r.due is not None
           and 0 <= r.due < seconds]
    met = 0
    for r in due:
        gaps = [b - a for a, b in zip(r.token_t, r.token_t[1:])]
        mean_gap = sum(gaps) / len(gaps) if gaps else 0.0
        if r.token_t and r.token_t[0] - r.due <= limits["ttft_s"] and \
                mean_gap <= limits["mean_gap_s"] and not loadgen.legal(r):
            met += 1

    def in_flight(t: float) -> int:
        return sum(1 for r in log_ if r.sent is not None and r.sent <= t
                   and (r.ended is None or r.ended > t))

    return {"due": len(due), "met": met,
            "attainment": met / len(due) if due else 0.0,
            "ttft_mean_ms": 1000 * sum(summary["ttft_s"])
            / max(1, len(summary["ttft_s"])),
            "ttft_p50_ms": 1000 * loadgen.percentile(summary["ttft_s"], 50),
            "ttft_p90_ms": 1000 * loadgen.percentile(summary["ttft_s"], 90),
            "itl_p50_ms": 1000 * loadgen.percentile(summary["gaps_s"], 50),
            "itl_p95_ms": 1000 * loadgen.percentile(summary["gaps_s"], 95),
            "in_flight_max": max((in_flight(r.sent) for r in due
                                  if r.sent is not None), default=0),
            "in_flight_mid": in_flight(seconds / 2),
            "in_flight_end": in_flight(seconds),
            "out_tok_s": summary["tokens_in_window"] / seconds,
            "late_p95_ms": 1000 * loadgen.percentile(summary["late_s"], 95)
            if summary["late_s"] else 0.0,
            "failed": summary["failed"]}


def run(cell, args, rates: list) -> int:
    traffic = cell.traffic
    if traffic["kind"] != "serve_open":
        raise SystemExit("--sweep is for open-loop serving cells")
    with Session(cell, False) as session:
        handle, port = serving.deploy(cell, args.seed)
        device = serving.call(handle, "device_info")
        session.check_device(device)
        vocab = cell.config["vocab_size"]
        client = loadgen.Client(port)
        try:
            serving.warm_up(client, traffic,
                            loadgen.probe_requests(traffic, vocab))
            log(f"[sweep] {cell.name} on {device['kind']}: limits "
                f"{traffic['knee_limits']}, {args.seconds} s a rate")
            for rate in rates:
                client.log = []
                client.t0 = time.perf_counter() + traffic["ramp_s"]
                future, _ = serve_open.start_traffic(
                    client, cell, args.seed, vocab, -traffic["ramp_s"],
                    args.seconds, [], rate=rate)
                tasks = future.result(timeout=args.seconds + 600)
                client.run(client.finish(tasks, 60.0)).result(timeout=120)
                r = row(client.log, args.seconds, traffic["knee_limits"])
                r["engine"] = {k: v for k, v in serving.call(
                    handle, "owner_stats")["engine"].items()
                    if k in ("tokens_generated", "chunks")}
                log(f"[sweep] rate {rate:g}/s: " + ", ".join(
                    f"{k} {v:.3g}" if isinstance(v, float) else f"{k} {v}"
                    for k, v in r.items()))
        finally:
            client.close()
    return 0
