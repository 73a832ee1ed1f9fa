"""What every serving cell does, whatever its traffic: deploy through
``serve.run(build_llm_deployment(...))``, prove the replica right, warm
up, open the window among running traffic, observe, tear down.  The
traffic itself comes from the driver of the mix's ``kind``.
"""

from __future__ import annotations

import dataclasses
import os
import shutil
import time

from chipbench import loadgen
from chipbench.session import Session, log
from chipbench.spec import CHECKOUT, resolve


def deploy(cell, seed: int):
    from ant_ray_tpu import serve

    spec, traffic = cell.config, cell.traffic
    build = resolve(spec["serve"]["deployment"])
    app = build(spec, slots=traffic["slots"], max_seq=traffic["max_seq"],
                **spec["serve"]["kwargs"])
    # Exactly that application, with the replica class swapped for its
    # probing subclass and the weights' seed set from --seed.
    app = dataclasses.replace(
        app, deployment=dataclasses.replace(
            app.deployment, cls_or_fn=resolve(spec["serve"]["replica"])),
        kwargs={**app.kwargs, "seed": seed})
    handle = serve.run(app, port=0)
    return handle, serve.run.last_http_port


def call(handle, method: str, *args, timeout: float = 900.0):
    import ant_ray_tpu as art

    return art.get(handle.options(method_name=method).remote(*args),
                   timeout=timeout)


def sleep_until(client, t: float) -> None:
    delay = t - client.now()
    if delay > 0:
        time.sleep(delay)


def warm_up(client, traffic: dict, probes: list) -> list:
    """Warm-up of this cell's shapes only: the probes answered alone
    (greedy), and one short request for every other sampling entry of
    the mix.  Returns the probes' replies."""
    alone = [client.run(client.send(p.fresh())).result(timeout=900)
             for p in probes]
    for i, pick in enumerate(traffic["sampling"]):
        if pick.get("temperature", 0.0) > 0:
            body = {k: v for k, v in pick.items()
                    if k not in ("share", "seeded")}
            if pick.get("seeded"):
                body["seed"] = 1 + i
            client.run(client.send(loadgen.Request(
                -100 - i, probes[0].prompt[:32], 8, body,
                kind="warmup"))).result(timeout=900)
    return alone


def run(cell, args, start_traffic) -> dict:
    """``start_traffic(client, cell, seed, vocab, start, end, probes)``
    returns the coroutine that runs the mix and yields its tasks, and a
    callable that tells it to stop issuing."""
    traffic, seconds = cell.traffic, args.seconds
    split = {}
    mark = time.time()

    def lap(name: str):
        nonlocal mark
        now = time.time()
        split[name] = now - mark
        mark = now

    split["process_start_to_main"] = mark - args.t0
    with Session(cell, bool(args.trace)) as session:
        from ant_ray_tpu.util.timeline import fetch_span_events

        lap("session")
        handle, port = deploy(cell, args.seed)
        device = call(handle, "device_info")
        session.check_device(device)
        lap("replica_ready")
        vocab = cell.config["vocab_size"]
        parity = call(handle, "probe_logits", args.seed,
                      traffic["parity"]["prompt_tokens"],
                      traffic["parity"]["decode_steps"])
        lap("logit_parity")
        client = loadgen.Client(port)
        try:
            probes = loadgen.probe_requests(traffic, vocab)
            alone = warm_up(client, traffic, probes)
            lap("warmup_and_probes_alone")

            ramp = traffic["ramp_s"]
            client.t0 = time.perf_counter() + ramp   # now() < 0: the ramp
            for i, p in enumerate(probes):
                p.due = seconds * (i + 1) / (len(probes) + 1)
            tasks_future, stop = start_traffic(
                client, cell, args.seed, vocab, -ramp, seconds, probes)
            sleep_until(client, 0.0)
            window_wall = time.time()
            setup_s = window_wall - args.t0
            split["ramp"] = window_wall - mark
            before = call(handle, "owner_stats")
            traced = None
            if args.trace:
                sleep_until(client, traffic["trace"]["after_s"])
                trace_dir = os.path.join(CHECKOUT, ".chipbench_trace",
                                         cell.name)
                shutil.rmtree(trace_dir, ignore_errors=True)
                call(handle, "trace_start", trace_dir)
                time.sleep(traffic["trace"]["seconds"])
                traced = call(handle, "trace_stop")
            sleep_until(client, seconds)
            after = call(handle, "owner_stats")
            stop()
            tasks = tasks_future.result(timeout=seconds + 600)
            client.run(client.finish(tasks, traffic["drain_s"])).result(
                timeout=traffic["drain_s"] + 60)
        finally:
            client.close()
        final = call(handle, "owner_stats")
        one_owner = session.watch.verdict(device["pid"], cell.rehearsal)
        trace = call(handle, "trace_reduce") if args.trace else None
        spans = fetch_span_events() if args.trace else None
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)

    summary = loadgen.summarise(client.log, seconds)
    replay = {r.rid: r for r in client.log if r.kind == "probe" and
              r.due is not None}
    probe_equal = [a.tokens == replay[a.rid].tokens and bool(a.tokens)
                   and loadgen.legal(a) is None
                   for a in alone if a.rid in replay]
    tol = cell.config["tolerance"]["serve_logit_rel_l2"]
    compiles = after["compiles"] - before["compiles"]
    checks = {
        "replies_right": summary["failed"] == 0 and summary["attempted"] > 0,
        "logit_parity": max(parity["rel_l2"]) <= tol,
        "probes_equal_among_traffic": len(probe_equal) == len(probes)
        and all(probe_equal),
        "no_compile_in_window": compiles == 0,
        "one_owner_per_chip": one_owner,
    }
    log(f"[setup] {setup_s:.1f} s = " + " | ".join(
        f"{k} {v:.1f}" for k, v in split.items()) + f" | replica's own "
        f"init {final['init_s']:.1f} s inside replica_ready")
    log(f"[parity] logits vs reference over {parity['positions']} positions:"
        f" worst relative L2 {max(parity['rel_l2']):.3e} (tolerance "
        f"{tol:.1e}), argmax equal at {parity['argmax_equal']}, reference "
        f"logit RMS {parity['logit_rms']:.3f}, {parity['seconds']:.1f} s")
    log(f"[window] {seconds} s: attempted {summary['attempted']}, failed "
        f"{summary['failed']} {summary['failures']}, ended in window "
        f"{summary['ended_in_window']}, first tokens "
        f"{sum(1 for t in summary['ttft_s'] if t != float('inf'))}, gaps "
        f"{len(summary['gaps_s'])}, tokens in window "
        f"{summary['tokens_in_window']}, compilations in window {compiles}, "
        f"engine tokens "
        f"{after['engine']['tokens_generated'] - before['engine']['tokens_generated']}"
        f", chunks {after['engine']['chunks'] - before['engine']['chunks']}")
    got = [t for t in summary["ttft_s"] if t != float("inf")]
    if got:
        log(f"[ttft] {len(got)} first tokens: mean "
            f"{1000 * sum(got) / len(got):.0f} ms, p50 "
            f"{1000 * loadgen.percentile(got, 50):.0f}, p90 "
            f"{1000 * loadgen.percentile(got, 90):.0f}, max "
            f"{1000 * max(got):.0f}; slowest (at s, prompt tokens, ttft "
            f"ms, sent late ms): " + ", ".join(
                f"({at:.1f}, {n}, {1000 * t:.0f}, {1000 * late:.1f})"
                for at, n, t, late in summary["slowest"]))
    log(f"[checks] {checks}")
    return {
        "device": device, "checks": checks, "setup_s": setup_s,
        "setup_split": split, "client": summary, "train": None,
        "attempted": summary["attempted"], "failed": summary["failed"],
        "trace": trace, "spans": spans, "traced": traced,
        "memory_peak_bytes": final["memory_peak_bytes"], "parity": parity,
    }
