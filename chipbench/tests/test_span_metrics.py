"""The seven per-layer metrics read from inside the program (request
spans, engine counters, phase labels of the device trace), each on a
hand-made ``obs`` — and None, never an exception, where the program
records no such span or counter (the parent of the PR that added
them)."""

import importlib

import pytest

WINDOW = 1000.0
NAMES = ("serve_ingress_p50_ms", "serve_egress_p50_ms",
         "engine_queue_mean_ms", "engine_prefill_mean_ms",
         "loop_host_ms_per_step", "d2h_syncs_per_step",
         "idle_unattributed_pct")


def read(name, obs):
    return importlib.import_module(
        "chipbench.layer_metrics." + name).read(obs)


def request(i, at, *, ingress=0.004, queue=0.010, prefill=0.200,
            egress=0.003, stream_ok=True, prompt_tokens=192):
    """The spans one streamed request leaves, received at ``at``."""
    trace = f"{i:032x}"
    submit = at + ingress - 0.0005
    first_token = submit + queue + prefill
    http = {"trace_id": trace, "name": "http:/v1/completions", "ts": at,
            "dur_s": 1.0, "attrs": {"path": "/v1/completions",
                                    "stream": True, "status": 200,
                                    "chunks": 9}}
    if stream_ok:
        http["attrs"]["first_chunk_s"] = first_token + egress - at
    return [
        http,
        {"trace_id": trace, "name": "replica:llm", "ts": at + 0.001,
         "dur_s": 0.9},
        {"trace_id": trace, "name": "llm:stream", "ts": at + 0.002,
         "dur_s": 0.9, "attrs": {"max_tokens": 8}},
        {"trace_id": trace, "name": "llm:admission", "ts": at + 0.003,
         "dur_s": ingress - 0.003},
        {"trace_id": trace, "name": "llm:engine", "ts": submit,
         "dur_s": queue + prefill + 0.5,
         "stages": {"queue": queue, "prefill": prefill, "decode": 0.5},
         "attrs": {"chunks": 3, "prompt_tokens": prompt_tokens}},
    ]


def spans_obs(spans):
    return {"spans": spans, "window_wall": WINDOW, "seconds": 50}


def twelve():
    spans = []
    for i in range(12):
        spans += request(i, WINDOW + 1 + i, queue=0.010 * (i + 1))
    return spans


def test_span_metrics_over_the_requests_received_in_the_window():
    spans = twelve()
    # before the window, after it, and one whose stream never started
    spans += request(100, WINDOW - 1.0, ingress=9.0)
    spans += request(101, WINDOW + 50.5, ingress=9.0)
    spans += request(102, WINDOW + 20, stream_ok=False)
    obs = spans_obs(spans)
    assert read("serve_ingress_p50_ms", obs) == pytest.approx(4.0)
    # a correctness probe among the traffic: told by its prompt length
    # once the client's log of the traffic is there
    spans += request(103, WINDOW + 30, queue=9.0, prompt_tokens=96)
    assert read("engine_queue_mean_ms", obs) > 700
    obs["client"] = {"requests": [(192, [0.1, 0.2])]}
    assert read("serve_ingress_p50_ms", obs) == pytest.approx(4.0)
    assert read("serve_egress_p50_ms", obs) == pytest.approx(3.0, abs=1e-3)
    assert read("engine_queue_mean_ms", obs) == pytest.approx(65.0)
    assert read("engine_prefill_mean_ms", obs) == pytest.approx(200.0)


def test_too_few_requests_or_a_failed_one_give_nothing():
    few = spans_obs([s for i in range(9) for s in request(i, WINDOW + i)])
    assert read("serve_ingress_p50_ms", few) is None
    failed = twelve()[:-5 * 2]           # ten whole requests ...
    assert read("engine_queue_mean_ms", spans_obs(failed)) is not None
    failed[4]["error"] = True            # ... one of them failed
    assert read("engine_queue_mean_ms", spans_obs(failed)) is None


def counters(steps, decode_steps, syncs, phase_s, block_s, idle_s):
    stats = {"tokens_generated": 0, "steps": steps,
             "decode_steps": decode_steps, "d2h_syncs": syncs,
             "block_s": block_s, "block_fetch_s": block_s,
             "phase_idle_wait_s": idle_s}
    for phase in ("drain", "decode", "sample", "fetch"):
        stats[f"phase_{phase}_s"] = phase_s / 4
    return stats


def test_counter_metrics_are_deltas_over_the_traced_window():
    obs = {"traced": {
        "engine_before": counters(100, 90, 1500, 8.0, 3.0, 20.0),
        "engine": counters(150, 130, 2180, 12.0, 5.0, 21.0)}}
    # phases (4.0 + 1.0 of idle_wait) - blocked 2.0 - waited 1.0, 50 steps
    assert read("loop_host_ms_per_step", obs) == pytest.approx(40.0)
    assert read("d2h_syncs_per_step", obs) == pytest.approx(17.0)
    still = {"traced": {"engine_before": obs["traced"]["engine"],
                        "engine": obs["traced"]["engine"]}}
    assert read("loop_host_ms_per_step", still) is None
    assert read("d2h_syncs_per_step", still) is None


def test_idle_share_that_no_phase_owns():
    device = {"index": 0, "idle_by_host": [
        ["python3: engine:sample", 0.6, 700],
        ["python3: np.asarray(jax.Array)", 0.2, 300],
        ["unattributed", 0.1, 90],
        ["python3: engine", 0.1, 5]]}
    other = {"index": 1, "idle_by_host": [["unattributed", 9.0, 1]]}
    obs = {"trace": {"devices": [other, device], "window_s": 4.0}}
    assert read("idle_unattributed_pct", obs) == pytest.approx(20.0)
    device["idle_by_host"] = []
    assert read("idle_unattributed_pct", obs) is None


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("obs", [
    {},
    {"spans": None, "traced": None, "trace": None},
    # the parent: spans of streams without http: and llm:engine, stats
    # without the new counters, a trace that reduced to no device
    {"spans": [s for s in twelve()
               if s["name"] in ("replica:llm", "llm:stream",
                                "llm:admission")],
     "window_wall": WINDOW, "seconds": 50,
     "traced": {"engine": {"tokens_generated": 9, "chunks": 2},
                "engine_before": {"tokens_generated": 1, "chunks": 0}},
     "trace": {"devices": [], "window_s": 0.0}},
], ids=["empty", "untraced", "parent"])
def test_absent_source_reads_as_none(name, obs):
    assert read(name, obs) is None


def test_the_parent_still_reads_its_unattributed_share():
    """The one new metric whose source (the device trace) the parent
    has: there it says how much XLA's own host events leave dark."""
    obs = {"trace": {"devices": [{"index": 0, "idle_by_host": [
        ["unattributed", 0.96, 936],
        ["python3: np.asarray(jax.Array)", 0.89, 785]]}]}}
    assert read("idle_unattributed_pct", obs) == pytest.approx(
        100 * 0.96 / 1.85)
