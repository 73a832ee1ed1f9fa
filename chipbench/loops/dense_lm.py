"""The training cell's loop: a user's train loop in this framework, kept
by the benchmark and frozen with it.

What is under test is everything the loop calls: ``JaxTrainer`` / the
controller / ``train.report``, ``iter_device_batches``,
``llama.loss_fn`` and all below it, ``parallel/`` and ``ops/``.  A later
PR moves ``train_tok_s`` through those, not through this file.

The loop: the repo's fsdp rule table on an ``fsdp=<chips>`` mesh;
float32 master weights and float32 AdamW moments; ``llama.loss_fn`` on a
bfloat16 cast of the masters; one jitted, donated step; a report every
step; no checkpoint.  It runs in the worker that leased the chips, so
it also takes the device trace and reads the memory statistics.
"""

from __future__ import annotations

import time


def make_step(jax, llama, optax, config16, mesh, opt, remat: str):
    """The step function, not yet jitted: shared with the rehearsal
    compile (``chipbench/rehearsal/compile_v5e.py``)."""
    import jax.numpy as jnp

    def loss_of(params, tokens):
        half = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
        return llama.loss_fn(half, {"tokens": tokens}, config16,
                             mesh=mesh, remat=remat)

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_of)(params, tokens)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return loss_of, train_step


def state_shardings(jax, optax, opt, param_shapes, shardings, mesh):
    """Moments take their parameter's sharding, the count is
    replicated."""
    from jax.sharding import NamedSharding, PartitionSpec

    replicated = NamedSharding(mesh, PartitionSpec())
    shapes = jax.eval_shape(opt.init, param_shapes)
    return optax.tree_map_params(
        opt, lambda _, s: s, shapes, shardings,
        transform_non_params=lambda _: replicated)


def _reference_grads(jax, ref, dims, ref_params, tokens, keep: list):
    """The plain reference's loss and gradients on ``tokens`` (b, s+1),
    by chaining ``jax.vjp`` of the reference's OWN pieces
    (``embed_tokens``, ``block``, ``head_loss``) one layer at a time.
    Differentiating ``ref.loss`` whole is the same mathematics, but its
    unrolled program takes 339 s to compile for a described v5e:2x2
    (rehearsal), against seconds for one layer.  Layer gradients are
    kept for the layers in ``keep`` only."""
    import jax.numpy as jnp

    embed, layer, n, norm_f, head = ref_params
    positions = jnp.arange(tokens.shape[1] - 1)
    eps = dims["norm_eps"]

    def block(lyr, x):
        return ref.block(lyr, x, positions, **dims)

    forward = jax.jit(block)
    backward = jax.jit(lambda lyr, x, ct: jax.vjp(block, lyr, x)[1](ct))
    tail = jax.jit(jax.value_and_grad(
        lambda nf, hd, x, targets: ref.head_loss(nf, hd, x, targets, eps),
        argnums=(0, 1, 2)))
    total, grads = 0.0, None
    for row in tokens:
        inputs, targets = row[:-1], row[1:]
        x, embed_vjp = jax.vjp(lambda e: ref.embed_tokens(e, inputs), embed)
        xs = [x]
        for i in range(n):
            xs.append(forward(layer(i), xs[-1]))
        value, (d_norm, d_head, ct) = tail(norm_f, head, xs[-1], targets)
        d_layers = {}
        for i in reversed(range(n)):
            d_layer, ct = backward(layer(i), xs[i], ct)
            if i in keep:
                d_layers[i] = d_layer
        mine = {"embed": embed_vjp(ct)[0], "norm_f": d_norm, "head": d_head,
                "layers": d_layers}
        total = total + float(value)
        grads = mine if grads is None else jax.tree.map(
            lambda a, b: a + b, grads, mine)
    b = tokens.shape[0]
    return total / b, jax.tree.map(lambda g: g / b, grads)


def _parity(jax, spec, params, shardings, probe_tokens, loss_of):
    """First-step loss and a sample of gradient leaves against the
    plain reference, at the published widths, on a short sequence:
    embedding, final norm, output head, and every leaf of the first,
    the middle and the last layer."""
    import importlib

    import jax.numpy as jnp

    from chipbench.spec import resolve

    ref = importlib.import_module(spec["reference"]["module"])
    to_ref = resolve(spec["reference"]["params"])
    t0 = time.perf_counter()
    # Gradients sharded like their parameters: left to the compiler
    # they come out replicated, 7 GB on every chip.
    sys_loss, sys_grads = jax.jit(
        jax.value_and_grad(loss_of), out_shardings=(None, shardings))(
        params, probe_tokens)
    g_embed, g_layer, n, g_norm, g_head = to_ref(sys_grads)
    keep = sorted({0, n // 2, n - 1})
    ref_loss, want = _reference_grads(jax, ref, ref.dims_of(spec),
                                      to_ref(params), probe_tokens, keep)
    got = {"embed": g_embed, "norm_f": g_norm, "head": g_head,
           "layers": {i: g_layer(i) for i in keep}}
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    leaves = {}
    for (path, a), b in zip(flat_got, jax.tree.leaves(want)):
        num = jnp.sqrt(jnp.sum((a.astype(jnp.float32) - b) ** 2))
        leaves[jax.tree_util.keystr(path)] = float(
            num / jnp.sqrt(jnp.sum(b * b)))
    out = {"loss_system": float(sys_loss), "loss_reference": ref_loss,
           "grad_rel_l2": leaves, "seconds": time.perf_counter() - t0}
    out["loss_rel_err"] = abs(out["loss_system"] - ref_loss) / abs(ref_loss)
    return out


def train_loop(cfg: dict):
    """Runs in the ONE Train worker that leased the cell's chips.
    ``cfg``: ``spec`` (configuration file), ``job`` (traffic file),
    ``seed``, ``seconds``, ``trace``, ``platform``, ``chips``,
    ``trace_dir``."""
    import numpy as np

    from ant_ray_tpu import train
    from ant_ray_tpu._private.jax_utils import import_jax

    jax = import_jax()
    t_enter = time.time()
    from chipbench.owner import (CompileCounter, device_info,
                                 memory_peak_bytes)

    devices = jax.devices()
    device = device_info(jax)
    chips = cfg["chips"]
    if device["platform"] != cfg["platform"] or len(devices) < chips:
        raise RuntimeError(f"the Train worker sees {device}, the cell "
                           f"needs {chips} {cfg['platform']} device(s)")

    import optax
    from jax.sharding import NamedSharding

    from ant_ray_tpu.models import llama
    from ant_ray_tpu.parallel.mesh import build_mesh
    from ant_ray_tpu.parallel.sharding import logical_to_spec
    from chipbench.spec import resolve

    compiles = CompileCounter(jax)
    spec, job, seed = cfg["spec"], cfg["job"], cfg["seed"]
    build = resolve(spec["model"]["factory"])
    config32 = build(spec, dtype="float32")
    config16 = build(spec, dtype="bfloat16")
    mesh = build_mesh(devices=devices[:chips], fsdp=chips)
    shardings = llama.param_shardings(config32, mesh)
    kw = spec["train"]["kwargs"]
    opt = optax.adamw(kw["learning_rate"], weight_decay=kw["weight_decay"])
    loss_of, train_step = make_step(jax, llama, optax, config16, mesh, opt,
                                    job["remat"])
    batch_sharding = NamedSharding(mesh, logical_to_spec(("batch", None)))
    per_chip, seq = job["sequences_per_chip"], job["sequence_tokens"]
    global_batch = per_chip * chips

    # Weights on the device in one jitted call from the seed, in the
    # type they are kept in (float32 masters), already sharded.
    t0 = time.perf_counter()
    # (the key is an ARGUMENT: traced in as a constant, every new seed
    # would be a new program and 23 s of compilation)
    params = jax.jit(
        lambda key: llama.init_params(config32, key),
        out_shardings=shardings)(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0

    probe = np.random.default_rng([seed, 7]).integers(
        0, config32.vocab_size, (chips, job["parity_tokens"] + 1),
        dtype=np.int32)
    parity = _parity(jax, spec, params, shardings,
                     jax.device_put(probe, batch_sharding), loss_of)

    t0 = time.perf_counter()
    st_shardings = state_shardings(jax, optax, opt, params, shardings, mesh)
    opt_state = jax.jit(opt.init, out_shardings=st_shardings)(params)
    tokens_shape = jax.ShapeDtypeStruct((global_batch, seq + 1), np.int32,
                                        sharding=batch_sharding)
    step = jax.jit(
        train_step, donate_argnums=(0, 1),
        out_shardings=(shardings, st_shardings, None),
    ).lower(params, opt_state, tokens_shape).compile()
    compile_s = time.perf_counter() - t0
    text = step.as_text()
    collectives = {op: text.count(f" {op}(") + text.count(f" {op}-start(")
                   for op in ("all-gather", "reduce-scatter", "all-reduce")}
    mem = step.memory_analysis()

    shard = train.get_dataset_shard("train")

    def batches():
        while True:                       # one pass is one epoch
            yield from shard.iter_device_batches(
                batch_size=global_batch,
                prefetch_batches=job["prefetch_batches"],
                sharding=batch_sharding, drop_last=True,
                collate_fn=lambda b: {"tokens": np.ascontiguousarray(
                    np.asarray(b["value"] if isinstance(b, dict) else b,
                               np.int32))})

    feed = batches()

    def starve_s() -> float:
        return shard.stats().get("device_feed", {}).get(
            "consumer_starve_s", 0.0)

    losses, records = [], []              # records: (t_begin, t_done, t_rep)
    trace = {"started": None, "stopped": None, "dir": None}
    seconds, warmup = cfg["seconds"], job["warmup_steps"]
    trace_after, trace_steps = job["trace_after_steps"], job["trace_steps"]
    t_window = None
    starve_epoch = 0.0                    # starve of finished epochs
    last_starve = 0.0
    i = 0
    while True:
        if i == warmup:
            # The window opens on a step boundary, after the warm-up
            # steps have run: feed primed, every program compiled.
            t_window = time.perf_counter()
            window_wall = time.time()
            compiles_before = compiles.count
            starve_at_open = starve_epoch + starve_s()
        if t_window is not None and \
                time.perf_counter() - t_window >= seconds:
            break
        in_window = i - warmup
        if cfg["trace"] and in_window == trace_after:
            trace["dir"] = cfg["trace_dir"]
            from chipbench.trace_reduce import start_trace

            start_trace(jax, trace["dir"])
            trace["started"] = time.perf_counter()
        t_begin = time.perf_counter()
        batch = next(feed)
        now_starve = starve_s()
        if now_starve < last_starve:      # a new epoch's feed started
            starve_epoch += last_starve
        last_starve = now_starve
        params, opt_state, loss = step(params, opt_state, batch["tokens"])
        losses.append(float(loss))        # the fetch ends the step
        t_done = time.perf_counter()
        train.report({"step": i, "loss": losses[-1]})
        records.append((t_begin, t_done, time.perf_counter()))
        if trace["started"] and not trace["stopped"] and \
                in_window + 1 >= trace_after + trace_steps:
            jax.profiler.stop_trace()
            trace["stopped"] = time.perf_counter()
        i += 1
    if trace["started"] and not trace["stopped"]:
        jax.profiler.stop_trace()
        trace["stopped"] = time.perf_counter()
    compiles_in_window = compiles.count - compiles_before
    starve_in_window = starve_epoch + starve_s() - starve_at_open

    done_in = [r for r in records[warmup:] if r[1] - t_window <= seconds]
    reduced = None
    if trace["dir"]:
        from chipbench.trace_reduce import reduce_dir

        reduced = reduce_dir(trace["dir"])
        reduced["host_window_s"] = trace["stopped"] - trace["started"]
    train.report({
        "final": True, "device": device, "losses": losses,
        "warmup_steps": warmup,
        "steps_begun_in_window": len(records) - warmup,
        "steps_done_in_window": len(done_in),
        "window_used_s": (done_in[-1][1] - t_window) if done_in else 0.0,
        "window_wall": window_wall, "worker_entered_wall": t_enter,
        "step_s": [r[1] - r[0] for r in records[warmup:]],
        "report_s": [r[2] - r[1] for r in records[warmup:]],
        "tokens_per_step": global_batch * seq,
        "starve_in_window_s": starve_in_window,
        "compiles_in_window": compiles_in_window,
        "compiles_total": compiles.count,
        "init_s": init_s, "compile_s": compile_s, "parity": parity,
        "collectives_in_program": collectives,
        "has_kernel": "tpu_custom_call" in text,
        "program_bytes": {
            "arguments": mem.argument_size_in_bytes,
            "temporaries": mem.temp_size_in_bytes,
            "outputs": mem.output_size_in_bytes,
            "aliased": mem.alias_size_in_bytes} if mem else None,
        "memory_peak_bytes": memory_peak_bytes(jax, chips),
        "trace": reduced,
    })
