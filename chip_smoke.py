#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full width of Llama-3.2-1B (``llama.CONFIGS["llama3-1b"]``, random
weights from ``--seed``), in WORKER processes: this script's own process
never touches a jax backend, because a chip belongs to the one process
that leased it (``ant_ray_tpu/_private/jax_utils.py``).

    python chip_smoke.py            # one chip: phase train, then phase serve
    python chip_smoke.py --chips 4  # four chips: ONLY the fsdp=4 train step
                                    # against the same steps on one device

* phase ``train`` — ``art.init()``; ``JaxTrainer(loop, ScalingConfig(
  num_workers=1, use_tpu=True)).fit()``: loss + grad + adamw at batch
  2 x 2048, ``remat="full"``, 3 warm-up + 5 timed steps, a report each
  step and one reported checkpoint, restored by a CPU-pinned actor.
* phase ``serve`` — a fresh ``art.init()``; ``serve.run(
  build_llm_deployment("llama3-1b", slots=8, max_seq=2048))``; four
  requests over HTTP to ``/v1/completions``.
* ``--chips 4`` — one Train worker that leased four chips runs the
  repo's fsdp rule table on an ``fsdp=4`` mesh, and in the same worker
  the same steps on a one-device mesh; losses must agree.

A phase that fails prints the exception and the tail of that session's
daemon and worker logs, and the script exits non-zero at once.  It
fails wherever jax finds no TPU — also under ``JAX_PLATFORMS=cpu``,
where its worker reports ``cpu``.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
with the device as the owning worker reported it.  A smoke, not a
benchmark: the times it prints are there to be looked at, not compared.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import threading
import time
import traceback
import urllib.request
from concurrent.futures import ThreadPoolExecutor

MODEL = "llama3-1b"
BF16_LOSS_RTOL = 4 * 2.0 ** -8      # four bf16 ulps (8 mantissa bits)
_TREE_MARK = "ant_ray_tpu._private"
_CHIP_NODE = re.compile(r"^/dev/(accel\d+|vfio/\d+)$")


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------- processes

def _pids() -> list:
    return [int(n) for n in os.listdir("/proc") if n.isdigit()]


def tree_pids() -> set:
    """Processes of an ant_ray_tpu session (GCS, daemon, dashboard,
    agent, workers): every one is ``python -m ant_ray_tpu._private.*``."""
    out = set()
    for pid in _pids():
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                if _TREE_MARK.encode() in f.read():
                    out.add(pid)
        except OSError:
            continue
    return out


def chip_holders() -> dict:
    """pid -> chip device nodes it holds open: the processes that have a
    TPU backend open right now, as the kernel sees them."""
    out: dict = {}
    for pid in _pids():
        try:
            fds = os.listdir(f"/proc/{pid}/fd")
        except OSError:
            continue
        for fd in fds:
            try:
                link = os.readlink(f"/proc/{pid}/fd/{fd}")
            except OSError:
                continue
            if _CHIP_NODE.match(link):
                out.setdefault(pid, set()).add(link)
    return out


def assert_own_process_clean() -> None:
    if "jax" in sys.modules:
        raise AssertionError(
            "chip_smoke's own process imported jax — it must never "
            "initialise a backend")
    if os.getpid() in chip_holders():
        raise AssertionError("chip_smoke's own process holds the chip")


class OwnershipWatch(threading.Thread):
    """Samples, while a phase runs: which processes hold the chip open
    (kernel view), which workers the daemon's ledger says own chips, and
    the cluster's resource view of ``TPU``."""

    def __init__(self):
        super().__init__(daemon=True, name="ownership-watch")
        self._stop_event = threading.Event()
        self.holder_sets: list = []     # one frozenset of pids per sample
        self.ledger_sets: list = []
        self.tpu_views: list = []       # (total, available)
        self.errors: list = []

    def run(self):
        from ant_ray_tpu.util import state

        while not self._stop_event.wait(1.0):
            try:
                holders = chip_holders()
                node = next(n for n in state.list_nodes() if n.alive)
                ledger = state._client_pool().get(node.address).call(
                    "DebugResources", timeout=10)
                owners = frozenset(
                    w["pid"] for w in ledger["workers"] if w["tpu_chips"])
                self.holder_sets.append(frozenset(holders))
                self.ledger_sets.append(owners)
                self.tpu_views.append(
                    (node.total_resources.get("TPU", 0.0),
                     node.available_resources.get("TPU", 0.0)))
            except Exception as e:  # noqa: BLE001 — judged in verdict()
                self.errors.append(repr(e))

    def stop(self):
        self._stop_event.set()
        self.join(timeout=30)

    def verdict(self, owner_pid: int, chips: int, expect_open: bool):
        """Exactly one process had the chip open, it is the worker that
        holds ``TPU`` in the daemon's ledger, and the cluster's view
        shows the chips leased."""
        if len(self.errors) > len(self.holder_sets):
            raise AssertionError(f"ownership watch failed: {self.errors[-3:]}")
        owned = [s for s in self.ledger_sets if s]
        if not owned or any(s != {owner_pid} for s in owned):
            raise AssertionError(
                f"daemon ledger: chips owned by {set().union(*owned)}, "
                f"the device reported from pid {owner_pid}")
        if (float(chips), 0.0) not in self.tpu_views:
            raise AssertionError(
                f"cluster resource view never showed TPU {chips} leased "
                f"in full: {sorted(set(self.tpu_views))}")
        opened = set().union(*self.holder_sets) if self.holder_sets else set()
        if expect_open and opened != {owner_pid}:
            raise AssertionError(
                f"processes with a chip device open: {sorted(opened)}; "
                f"expected exactly the owning worker {owner_pid}")
        if os.getpid() in opened:
            raise AssertionError("chip_smoke's own process held the chip")
        return (f"chip open in pids {sorted(opened)} over "
                f"{len(self.holder_sets)} samples; ledger owner pid "
                f"{owner_pid}; TPU view (total, available) "
                f"{sorted(set(self.tpu_views))}")


def wait_tree_gone(before: set, timeout: float = 60.0) -> None:
    """After shutdown no process of the session is left, so the chip is
    free for the next owner."""
    deadline = time.monotonic() + timeout
    while True:
        left = tree_pids() - before
        holders = chip_holders()
        if not left and not holders:
            return
        if time.monotonic() > deadline:
            raise AssertionError(
                f"after shutdown: session processes left {sorted(left)}, "
                f"chip holders {holders}")
        time.sleep(0.25)


def log_tails(session_dir: str, n: int = 40) -> None:
    logs = sorted(glob.glob(os.path.join(session_dir, "logs", "*")),
                  key=os.path.getmtime)
    for path in logs:
        try:
            with open(path, errors="replace") as f:
                lines = f.read().splitlines()
        except OSError:
            continue
        if not lines:
            continue
        log(f"---- {path} (last {min(n, len(lines))} of {len(lines)} lines)")
        for line in lines[-n:]:
            log("    " + line[:400])


# ------------------------------------------------------------------- train

def train_loop(cfg: dict):
    """Runs in the Train worker that leased the chip."""
    import dataclasses
    import os
    import time
    import zlib

    import numpy as np

    from ant_ray_tpu import train
    from ant_ray_tpu._private.jax_utils import import_jax

    jax = import_jax()
    device = jax.devices()[0]
    info = {"platform": device.platform, "kind": device.device_kind,
            "count": len(jax.devices()), "pid": os.getpid()}
    if device.platform != cfg["platform"]:
        raise RuntimeError(
            f"the Train worker runs on {device.platform!r} "
            f"({device.device_kind}), not {cfg['platform']!r}")

    import jax.numpy as jnp
    import optax

    from ant_ray_tpu.models import llama

    config = llama.CONFIGS[cfg["model"]]
    if cfg.get("max_seq"):
        config = dataclasses.replace(config, max_seq=cfg["max_seq"])
    batch, seq = cfg["batch"], cfg["seq"]
    params = llama.init_params(config, jax.random.PRNGKey(cfg["seed"]))
    opt = optax.adamw(3e-4, weight_decay=0.01)
    opt_state = opt.init(params)
    tokens = jnp.asarray(
        np.random.RandomState(cfg["seed"]).randint(
            0, config.vocab_size, (batch, seq + 1)), jnp.int32)

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(llama.loss_fn)(
            params, {"tokens": tokens}, config, remat="full")
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    t0 = time.perf_counter()
    step = jax.jit(train_step, donate_argnums=(0, 1)).lower(
        params, opt_state, tokens).compile()
    compile_s = time.perf_counter() - t0
    has_kernel = "tpu_custom_call" in step.as_text()

    losses, step_s, report_s = [], [], []
    n = cfg["warmup"] + cfg["timed"]
    for i in range(n):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, tokens)
        losses.append(float(loss))                  # value fetch
        t1 = time.perf_counter()
        # The last step's report carries the checkpoint: jax.Arrays
        # cross the wire to the controller, which saves them.
        train.report({"step": i, "loss": losses[-1]},
                     checkpoint=params if i == n - 1 else None)
        step_s.append(t1 - t0)
        report_s.append(time.perf_counter() - t1)
    ms = 1000 * sum(step_s[cfg["warmup"]:]) / cfg["timed"]
    stats = device.memory_stats() or {}
    train.report({
        "device": info, "compile_s": compile_s, "has_kernel": has_kernel,
        "losses": losses, "ms_per_step": ms,
        "tokens_per_s": batch * seq / (ms / 1000),
        "ms_per_report": 1000 * sum(report_s[:-1]) / (n - 1),
        "checkpoint_report_s": report_s[-1],
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "param_digest": {
            jax.tree_util.keystr(path): zlib.crc32(
                np.ascontiguousarray(jax.device_get(leaf))
                .reshape(-1).view(np.uint8))
            for path, leaf in
            jax.tree_util.tree_flatten_with_path(params)[0]}})


class CheckpointReader:
    """A non-owner (it leases no TPU, so it is pinned to the CPU
    backend) restores the reported checkpoint."""

    def digest(self, checkpoint) -> dict:
        import zlib

        import numpy as np

        from ant_ray_tpu._private.jax_utils import import_jax

        jax = import_jax()
        tree = checkpoint.to_pytree()
        return {
            "platform": jax.devices()[0].platform,
            "crc": {jax.tree_util.keystr(path): zlib.crc32(
                np.ascontiguousarray(np.asarray(leaf))
                .reshape(-1).view(np.uint8))
                for path, leaf in
                jax.tree_util.tree_flatten_with_path(tree)[0]}}


def phase_train(seed: int, *, model: str = MODEL, batch: int = 2,
                seq: int = 2048, max_seq: int | None = None,
                platform: str = "tpu", need_kernel: bool = True,
                storage: str | None = None) -> dict:
    import ant_ray_tpu as art
    from ant_ray_tpu import train

    art.init()
    watch = OwnershipWatch()
    watch.start()
    t_fit = time.perf_counter()
    try:
        result = train.JaxTrainer(
            train_loop,
            train_loop_config={
                "model": model, "batch": batch, "seq": seq,
                "max_seq": max_seq, "seed": seed, "platform": platform,
                "warmup": 3, "timed": 5},
            scaling_config=train.ScalingConfig(num_workers=1, use_tpu=True),
            run_config=train.RunConfig(
                name=f"chip-smoke-{os.getpid()}",
                storage_path=storage or ""),
        ).fit()
    finally:
        watch.stop()
    fit_s = time.perf_counter() - t_fit
    m = result.metrics
    device = m["device"]
    log(f"[train] {watch.verdict(device['pid'], 1, platform == 'tpu')}")
    if device["platform"] != platform:
        raise AssertionError(f"worker reported {device}")
    if need_kernel and not m["has_kernel"]:
        raise AssertionError(
            "the compiled train step holds no tpu_custom_call: the "
            "flash kernel is not in the program")
    losses = m["losses"]
    if not all(x == x and abs(x) != float("inf") for x in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(
            f"loss did not fall on the repeated batch: {losses}")
    if result.checkpoint is None:
        raise AssertionError("fit() returned no checkpoint")
    t_restore = time.perf_counter()
    reader = art.remote(CheckpointReader).remote()
    restored = art.get(reader.digest.remote(result.checkpoint),
                       timeout=600)
    art.kill(reader)
    restore_s = time.perf_counter() - t_restore
    if restored["platform"] != "cpu":
        raise AssertionError(
            f"a worker that leased no TPU runs on {restored['platform']}")
    if restored["crc"] != m["param_digest"]:
        raise AssertionError(
            "the restored checkpoint differs from the reported params: "
            f"{restored['crc']} != {m['param_digest']}")
    peak = m["peak_bytes_in_use"]
    log(f"[train] device {device['kind']} x{device['count']} | compile "
        f"{m['compile_s']:.1f} s | {m['ms_per_step']:.1f} ms/step | "
        f"{m['tokens_per_s']:.0f} tokens/s | report "
        f"{m['ms_per_report']:.1f} ms | peak_bytes_in_use "
        f"{peak if peak is None else f'{peak / 2**30:.2f} GiB'} | loss "
        f"{losses[0]:.4f} -> {losses[-1]:.4f} | fit() {fit_s:.1f} s, of "
        f"which the checkpoint report {m['checkpoint_report_s']:.1f} s | "
        f"checkpoint restored on a CPU-pinned actor in {restore_s:.1f} s "
        f"({len(restored['crc'])} leaves, crc equal)")
    return device


# ------------------------------------------------------------------- serve

def _post(port: int, body: dict, timeout: float = 900.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=timeout)


def complete(port: int, prompt: list, max_tokens: int) -> dict:
    t0 = time.perf_counter()
    with _post(port, {"prompt": prompt, "max_tokens": max_tokens}) as resp:
        status = resp.status
        choice = json.loads(resp.read())["result"]["choices"][0]
    return {"status": status, "tokens": choice["token_ids"],
            "finish": choice["finish_reason"], "ttft_s": None,
            "wall_s": time.perf_counter() - t0}


def complete_streamed(port: int, prompt: list, max_tokens: int) -> dict:
    t0 = time.perf_counter()
    tokens, ttft, finish = [], None, None
    with _post(port, {"prompt": prompt, "max_tokens": max_tokens,
                      "stream": True}) as resp:
        status = resp.status
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            payload = line[len("data: "):]
            if payload == "[DONE]":
                break
            choice = json.loads(payload)["choices"][0]
            if choice.get("token_id") is not None:
                if ttft is None:
                    ttft = time.perf_counter() - t0
                tokens.append(choice["token_id"])
            finish = choice.get("finish_reason") or finish
    return {"status": status, "tokens": tokens, "finish": finish,
            "ttft_s": ttft, "wall_s": time.perf_counter() - t0}


def phase_serve(seed: int, *, model: str = MODEL, slots: int = 8,
                max_seq: int = 2048, prompt_lens=(32, 128, 512),
                max_tokens: int = 32, vocab: int = 128256,
                platform: str = "tpu") -> dict:
    """Four requests: the first two alone, then the last prompt twice at
    once — plain and streamed — whose greedy outputs must be equal."""
    import random

    import ant_ray_tpu as art
    from ant_ray_tpu import serve
    from ant_ray_tpu.llm.serve_llm import build_llm_deployment

    art.init()
    watch = OwnershipWatch()
    watch.start()
    try:
        t0 = time.perf_counter()
        handle = serve.run(
            build_llm_deployment(model, slots=slots, max_seq=max_seq),
            port=0)
        port = serve.run.last_http_port
        device = art.get(
            handle.options(method_name="device_info").remote(),
            timeout=600)
        ready_s = time.perf_counter() - t0
        if device["platform"] != platform:
            raise AssertionError(f"the replica reported {device}")

        rng = random.Random(seed)
        short, mid, long_ = (
            [rng.randrange(1, vocab) for _ in range(n)]
            for n in prompt_lens)
        first = complete_streamed(port, short, max_tokens)   # compiles
        second = complete(port, mid, max_tokens)
        t_pair = time.perf_counter()
        with ThreadPoolExecutor(max_workers=2) as pool:
            pair = [f.result(timeout=900) for f in (
                pool.submit(complete, port, long_, max_tokens),
                pool.submit(complete_streamed, port, long_, max_tokens))]
        pair_wall = time.perf_counter() - t_pair
        replies = [first, second, *pair]
    finally:
        watch.stop()
    log(f"[serve] {watch.verdict(device['pid'], 1, platform == 'tpu')}")
    for i, r in enumerate(replies):
        if r["status"] != 200 or len(r["tokens"]) != max_tokens:
            raise AssertionError(
                f"request {i}: HTTP {r['status']}, {len(r['tokens'])} "
                f"tokens ({r['finish']}), expected {max_tokens}")
    if pair[0]["tokens"] != pair[1]["tokens"]:
        raise AssertionError(
            "greedy output of the two equal prompts differs: "
            f"{pair[0]['tokens']} != {pair[1]['tokens']}")
    steady = second["wall_s"] + pair_wall
    log(f"[serve] device {device['kind']} x{device['count']} | replica "
        f"ready {ready_s:.1f} s | ttft first (compiling) "
        f"{first['ttft_s']:.2f} s | ttft last ({prompt_lens[2]} tokens, "
        f"concurrent) "
        f"{pair[1]['ttft_s']:.2f} s | {3 * max_tokens / steady:.1f} "
        f"tokens/s over requests 2-4 ({steady:.2f} s) | 4 x HTTP 200, "
        f"{max_tokens} tokens each, equal prompts -> equal tokens")
    serve.shutdown()
    return device


# ----------------------------------------------------------------- 4 chips

def sharded_loop(cfg: dict):
    """Runs in ONE Train worker that leased all four chips: the fsdp
    rule table on an fsdp=4 mesh, then the same steps on one device."""
    import dataclasses
    import os
    import time

    import numpy as np

    from ant_ray_tpu import train
    from ant_ray_tpu._private.jax_utils import import_jax

    jax = import_jax()
    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "pid": os.getpid()}
    if info["platform"] != cfg["platform"] or len(devices) < cfg["chips"]:
        raise RuntimeError(f"the Train worker sees {info}")

    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from ant_ray_tpu.models import llama
    from ant_ray_tpu.parallel.mesh import build_mesh
    from ant_ray_tpu.parallel.sharding import logical_to_spec

    batch, seq, steps = cfg["batch"], cfg["seq"], cfg["steps"]
    opt = optax.adamw(3e-4, weight_decay=0.01)
    tokens_host = np.random.RandomState(cfg["seed"]).randint(
        0, llama.CONFIGS[cfg["model"]].vocab_size,
        (batch, seq + 1)).astype(np.int32)

    def build(config, mesh_devices):
        """(compiled step, its placed arguments) on an fsdp mesh over
        ``mesh_devices``; every run starts from the same host values."""
        mesh = build_mesh(devices=mesh_devices, fsdp=len(mesh_devices))
        shardings = llama.param_shardings(config, mesh)
        params = jax.jit(
            lambda: llama.init_params(
                config, jax.random.PRNGKey(cfg["seed"])),
            out_shardings=shardings)()
        # The moments take the parameters' shardings (zeros_like keeps
        # them); the step count lands on device 0 and is replicated.
        on_mesh = set(mesh.devices.flat)
        opt_state = jax.tree.map(
            lambda x: x if set(x.sharding.device_set) == on_mesh
            else jax.device_put(x, NamedSharding(mesh, PartitionSpec())),
            opt.init(params))
        tokens = jax.device_put(tokens_host, NamedSharding(
            mesh, logical_to_spec(("batch", None))))

        def train_step(params, opt_state, tokens):
            loss, grads = jax.value_and_grad(llama.loss_fn)(
                params, {"tokens": tokens}, config, mesh=mesh,
                remat="full")
            updates, opt_state = opt.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), opt_state,
                    loss)

        state_shardings = jax.tree.map(lambda x: x.sharding, opt_state)
        step = jax.jit(
            train_step, donate_argnums=(0, 1),
            out_shardings=(shardings, state_shardings, None),
        ).lower(params, opt_state, tokens).compile()
        return step, params, opt_state, tokens

    def shares(tree) -> list:
        """Fraction of the tree's bytes each device holds."""
        held = {d.id: 0 for d in devices}
        total = 0
        for leaf in jax.tree.leaves(tree):
            total += leaf.nbytes
            for shard in leaf.addressable_shards:
                held[shard.device.id] += shard.data.nbytes
        return [held[d.id] / total for d in devices]

    def run(step, params, opt_state, tokens):
        losses, t0 = [], time.perf_counter()
        for i in range(steps):
            if i == 1:
                t0 = time.perf_counter()         # step 0 warms up
            params, opt_state, loss = step(params, opt_state, tokens)
            losses.append(float(loss))
        ms = 1000 * (time.perf_counter() - t0) / max(1, steps - 1)
        return losses, ms

    # Depth: what the ONE-device run fits — its compiled step's own
    # count against the chip's limit, with room for what else the
    # process keeps there.  The cut, if any, holds for both runs.
    config = llama.CONFIGS[cfg["model"]]
    if cfg.get("max_seq"):
        config = dataclasses.replace(config, max_seq=cfg["max_seq"])
    full_depth = config.n_layers
    limit = (devices[0].memory_stats() or {}).get("bytes_limit")
    needs = []                      # (depth tried, bytes its step needs)
    while True:
        one = build(config, devices[:1])
        mem = one[0].memory_analysis()
        need = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
        needs.append((config.n_layers, need))
        if limit is None or need + cfg["reserve_bytes"] <= limit \
                or config.n_layers == 1:
            break
        del one
        config = dataclasses.replace(
            config, n_layers=config.n_layers // 2)
    one_losses, one_ms = run(*one)
    del one

    step, params, opt_state, tokens = build(config, devices[:cfg["chips"]])
    text = step.as_text()
    param_shares = shares(params)
    state_shares = shares(opt_state)
    sharded_losses, sharded_ms = run(step, params, opt_state, tokens)
    stats = devices[0].memory_stats() or {}
    train.report({
        "device": info, "depth": config.n_layers, "full_depth": full_depth,
        "one_device_needs": needs, "bytes_limit": limit,
        "one_losses": one_losses, "sharded_losses": sharded_losses,
        "one_ms": one_ms, "sharded_ms": sharded_ms,
        "param_shares": param_shares, "state_shares": state_shares,
        "collectives": {op: text.count(op) for op in (
            "all-gather", "reduce-scatter", "all-reduce")},
        "has_kernel": "tpu_custom_call" in text,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    })


def phase_sharded(seed: int, *, chips: int = 4, model: str = MODEL,
                  batch: int = 4, seq: int = 2048,
                  max_seq: int | None = None, platform: str = "tpu",
                  need_kernel: bool = True) -> dict:
    import ant_ray_tpu as art
    from ant_ray_tpu import train

    art.init()
    watch = OwnershipWatch()
    watch.start()
    try:
        result = train.JaxTrainer(
            sharded_loop,
            train_loop_config={
                "model": model, "batch": batch, "seq": seq,
                "max_seq": max_seq, "seed": seed, "platform": platform,
                "chips": chips, "steps": 6, "reserve_bytes": 1 << 30},
            scaling_config=train.ScalingConfig(num_workers=1, use_tpu=True),
            run_config=train.RunConfig(name=f"chip-smoke-{os.getpid()}"),
        ).fit()
    finally:
        watch.stop()
    m = result.metrics
    device = m["device"]
    log(f"[sharded] {watch.verdict(device['pid'], chips, platform == 'tpu')}")
    if device["platform"] != platform or device["count"] != chips:
        raise AssertionError(f"worker reported {device}, wanted {chips} "
                             f"{platform} devices")
    if m["depth"] != m["full_depth"]:
        needs = ", ".join(f"{need / 2**30:.2f} GiB at depth {depth}"
                          for depth, need in m["one_device_needs"])
        log(f"[sharded] DEPTH CUT {m['full_depth']} -> {m['depth']} layers "
            f"for BOTH runs (widths kept): the one-device step at batch "
            f"{batch} x {seq} needs {needs} by the compiler's count, and "
            f"must leave 1 GiB of the chip's "
            f"{m['bytes_limit'] / 2**30:.2f} GiB free")
    one, sharded = m["one_losses"], m["sharded_losses"]
    worst = max(abs(a - b) / abs(a) for a, b in zip(one, sharded))
    if not all(x == x for x in one + sharded) or worst > BF16_LOSS_RTOL:
        raise AssertionError(
            f"sharded and one-device losses disagree (worst relative "
            f"difference {worst:.2e} > {BF16_LOSS_RTOL:.2e}): "
            f"{sharded} vs {one}")
    if not sharded[-1] < sharded[0]:
        raise AssertionError(f"loss did not fall: {sharded}")
    shares = m["param_shares"]
    if max(shares) > 0.30 or min(shares) < 0.20:
        raise AssertionError(
            f"parameter bytes per device are not about a quarter each: "
            f"{shares}")
    if max(m["state_shares"]) > 0.30:
        raise AssertionError(
            f"optimizer state is not sharded: {m['state_shares']}")
    coll = m["collectives"]
    if not coll["all-gather"] or not (
            coll["reduce-scatter"] or coll["all-reduce"]):
        raise AssertionError(f"no collectives in the compiled step: {coll}")
    if need_kernel and not m["has_kernel"]:
        raise AssertionError("the sharded step holds no tpu_custom_call")
    peak = m["peak_bytes_in_use"]
    log(f"[sharded] device {device['kind']} x{device['count']} | depth "
        f"{m['depth']} of {m['full_depth']} | batch {batch} x {seq} | "
        f"fsdp={chips}: {m['sharded_ms']:.1f} ms/step, one device: "
        f"{m['one_ms']:.1f} ms/step | losses agree to {worst:.2e} "
        f"(tolerance {BF16_LOSS_RTOL:.2e}): {sharded[0]:.4f} -> "
        f"{sharded[-1]:.4f} | parameter share per device "
        f"{[round(s, 3) for s in shares]} | collectives {coll} | "
        f"peak_bytes_in_use device 0, one-device run included, "
        f"{peak if peak is None else f'{peak / 2**30:.2f} GiB'}")
    return device


# -------------------------------------------------------------------- main

def run_phase(name: str, fn, *args, **kwargs) -> dict:
    """One phase in its own session.  Failure prints the exception and
    the session's log tails and ends the script: no phase is skipped
    past."""
    import ant_ray_tpu as art

    before = tree_pids()
    t0 = time.perf_counter()
    log(f"[{name}] start")
    try:
        device = fn(*args, **kwargs)
        assert_own_process_clean()
        art.shutdown()
        wait_tree_gone(before)
    except BaseException:
        log(f"[{name}] FAILED after {time.perf_counter() - t0:.1f} s")
        traceback.print_exc(file=sys.stdout)
        from ant_ray_tpu._private.worker import global_worker

        session_dir = getattr(global_worker.runtime, "session_dir", "")
        if session_dir:
            log_tails(session_dir)
        sys.stdout.flush()
        try:
            art.shutdown()
        finally:
            sys.exit(1)
    log(f"[{name}] ok in {time.perf_counter() - t0:.1f} s; no process of "
        f"the session left, chip free")
    return device


def _start_watchdog(limit_s: float) -> None:
    """A hang ends inside the caller's time limit, with the logs: the
    session's processes watch this pid and go with it."""
    def fire():
        from ant_ray_tpu._private.worker import global_worker

        log(f"[host] WATCHDOG: no result after {limit_s:.0f} s")
        session_dir = getattr(global_worker.runtime, "session_dir", "")
        if session_dir:
            log_tails(session_dir)
        sys.stdout.flush()
        os._exit(1)

    timer = threading.Timer(limit_s, fire)
    timer.daemon = True
    timer.start()


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs ONLY the sharded train step and its "
                             "one-device comparison")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()

    t0 = time.perf_counter()
    _start_watchdog(1080.0)
    log(f"[host] chips in /dev: "
        f"{sorted(glob.glob('/dev/accel*') + glob.glob('/dev/vfio/[0-9]*'))}"
        f" | JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r} | "
        f"JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')!r} | cpus "
        f"{os.cpu_count()}")
    assert_own_process_clean()
    holders = chip_holders()
    if holders:
        log(f"[host] the chip is already held by {holders}")
        return 1
    if args.chips == 4:
        devices = [run_phase("sharded", phase_sharded, args.seed)]
    else:
        devices = [run_phase("train", phase_train, args.seed),
                   run_phase("serve", phase_serve, args.seed)]
    device = {k: devices[0][k] for k in ("platform", "kind", "count")}
    if any({k: d[k] for k in device} != device for d in devices) or \
            device["platform"] != "tpu" or device["count"] != args.chips:
        log(f"[host] devices reported by the owners: {devices}")
        return 1
    assert_own_process_clean()
    log(f"[host] all phases ok in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
