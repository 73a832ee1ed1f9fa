"""The benchmark's one command.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Driven by data: the cell names its configuration, its traffic mix and
its metrics; files named after them hold everything else (see
``chipbench/README.md``).  This process never initialises a jax
backend: it starts a session, drives the normal entry points
(``serve.run``, ``JaxTrainer.fit``), reads what the worker that owns
the chip reports, shuts the session down and waits until no process
holds the chip.  No chip, or an owner that reports anything but a TPU,
means a non-zero exit and no result line.

``--sweep r1,r2,...`` (open-loop serving cells): one set-up, then the
mix at each rate for ``--seconds``; prints one row per rate.  This is
how a mix's knee was found once; a run never searches for a rate.
"""

from __future__ import annotations

import time

T0 = time.time()            # as near to process start as Python gets

import argparse             # noqa: E402
import json                 # noqa: E402
import math                 # noqa: E402
import os                   # noqa: E402
import sys                  # noqa: E402
import threading            # noqa: E402

if __package__ in (None, ""):             # `python3 chipbench/run.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chipbench import spec  # noqa: E402
from chipbench.session import log  # noqa: E402

BIG = 1.0e9                 # a latency metric all of whose sample missed


def _watchdog(limit_s: float) -> None:
    """A hang ends inside the run's time limit, with the logs."""
    def fire():
        from chipbench.session import log_tails

        log(f"[run] WATCHDOG: no result after {limit_s:.0f} s")
        try:
            log_tails()
        finally:
            sys.stdout.flush()
            os._exit(3)

    timer = threading.Timer(limit_s, fire)
    timer.daemon = True
    timer.start()


def metrics_of(cell, obs: dict, traced: bool) -> dict:
    """``--trace 0``: the cell's end-to-end metrics; ``--trace 1``: its
    per-layer metrics.  A reader that finds nothing returns None and
    its metric is left out."""
    wanted = cell.per_layer if traced else cell.end_to_end
    package = "chipbench.layer_metrics." if traced else \
        "chipbench.end_to_end."
    out = {}
    for metric in wanted:
        read = spec.resolve(package + metric["name"] + ":read")
        value = read(obs)
        if value is None or (isinstance(value, float) and math.isnan(value)):
            continue
        if value == float("inf"):
            value = BIG
        out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def observe(cell, args) -> dict:
    obs = cell.driver().run(cell, args)
    obs.update(cell=cell.entry, config=cell.config, traffic=cell.traffic,
               chips=cell.chips, seconds=args.seconds,
               window_wall=T0 + obs["setup_s"],
               peaks=None if cell.rehearsal
               else spec.peaks(obs["device"]["kind"]))
    return obs


def result_line(cell, obs: dict, traced: bool) -> dict:
    device = {k: obs["device"][k] for k in ("platform", "kind", "count")}
    device["memory_peak_bytes"] = obs["memory_peak_bytes"]
    line = {"correct": all(obs["checks"].values()),
            "attempted": obs["attempted"], "failed": obs["failed"],
            "metrics": metrics_of(cell, obs, traced), "device": device}
    trace = obs.get("trace")
    if traced and trace and trace.get("devices"):
        from chipbench.trace_reduce import breakdown

        used = trace["devices"][:cell.chips]
        device["busy_s"] = sum(d["busy_s"] for d in used) / len(used)
        device["window_s"] = trace["window_s"]
        line["breakdown"] = breakdown(trace)
    return line


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=int, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--sweep", default=None,
                        help="rates (requests/s), comma-separated")
    parser.add_argument("--dump", default=None,
                        help="write everything observed to this JSON file")
    args = parser.parse_args(argv)
    args.t0 = T0
    cell = spec.Cell(args.workload)
    if args.seconds is None:
        args.seconds = cell.run_seconds
    _watchdog(1100.0)
    log(f"[run] {cell.name}: config {cell.entry['config']}, traffic "
        f"{cell.entry['traffic']} ({cell.traffic['kind']}), {cell.chips} "
        f"chip(s), seed {args.seed}, {args.seconds} s, trace {args.trace}"
        f"{' — REHEARSAL on the CPU' if cell.rehearsal else ''}")
    if args.sweep:
        from chipbench import sweep

        return sweep.run(cell, args,
                         [float(r) for r in args.sweep.split(",")])
    obs = observe(cell, args)
    line = result_line(cell, obs, bool(args.trace))
    for name, entry in line["metrics"].items():
        log(f"[metric] {name} = {entry['value']:.6g} {entry['unit']}")
    if obs.get("train") and not args.trace:
        from chipbench import opsbytes

        rate = line["metrics"].get("train_tok_s", {}).get("value")
        if rate and obs["peaks"]:
            flops = opsbytes.train_flops_per_token(
                cell.config, cell.traffic["sequence_tokens"])
            log(f"[metric] required-operations MFU on wall time "
                f"{100 * rate * flops / (cell.chips * obs['peaks']['bf16_flops_per_s']):.2f} %")
    if args.dump:
        os.makedirs(os.path.dirname(os.path.abspath(args.dump)),
                    exist_ok=True)
        with open(args.dump, "w") as f:
            json.dump({k: v for k, v in obs.items() if k != "spans"}
                      | {"spans": (obs.get("spans") or [])[:2000],
                         "line": line}, f, default=str)
    if not cell.rehearsal and line["device"]["platform"] != "tpu":
        log(f"[run] the owner reported {line['device']}: no result")
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
