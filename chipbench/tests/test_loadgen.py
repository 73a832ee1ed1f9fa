"""The generator's arithmetic on synthetic logs: percentile, due time,
lateness, window edges; and that a seed fixes the traffic.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""

import json
import os

import pytest

from chipbench import loadgen
from chipbench.loadgen import Request, percentile, summarise

HERE = os.path.dirname(os.path.abspath(__file__))


def traffic(name):
    with open(os.path.join(HERE, "..", "traffic", name + ".json")) as f:
        return json.load(f)


def test_percentile_interpolates_between_order_statistics():
    data = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert percentile(data, 0) == 10.0
    assert percentile(data, 50) == 30.0
    assert percentile(data, 100) == 50.0
    assert percentile(data, 90) == pytest.approx(46.0)   # 40 + 0.6 * 10
    assert percentile([7.0], 95) == 7.0
    assert percentile(list(range(101)), 95) == 95.0


def test_percentile_keeps_a_miss_a_miss():
    inf = float("inf")
    assert percentile([1.0, 2.0, 3.0, inf], 50) == 2.5
    assert percentile([1.0, 2.0, 3.0, inf], 90) == inf
    assert percentile([1.0] * 19 + [inf], 90) == 1.0
    assert percentile([inf, inf], 50) == inf


def done(rid, due, sent, token_t, max_tokens=4, status=200, **kw):
    r = Request(rid, [1, 2, 3], max_tokens, {}, due=due, **kw)
    r.sent, r.sent_wall, r.status = sent, 1000.0 + sent, status
    r.token_t, r.tokens = list(token_t), list(range(len(token_t)))
    r.done, r.finish = True, "length" if len(token_t) == max_tokens else "stop"
    r.ended = token_t[-1] + 0.001 if token_t else sent + 0.01
    return r


def test_ttft_counts_from_due_time_and_reports_lateness():
    log = [
        done(0, due=1.0, sent=1.25, token_t=[1.5, 1.6, 1.7, 1.8]),
        done(1, due=2.0, sent=2.0, token_t=[2.1, 2.2, 2.3, 2.4]),
    ]
    s = summarise(log, seconds=10)
    assert s["attempted"] == 2 and s["failed"] == 0
    assert s["ttft_s"] == pytest.approx([0.5, 0.1])      # from DUE, not sent
    assert s["late_s"] == pytest.approx([0.25, 0.0])
    assert sorted(s["gaps_s"]) == pytest.approx([0.1] * 6)
    assert s["tokens_in_window"] == 8


def test_window_edges():
    log = [
        done(0, due=-1.0, sent=-1.0, token_t=[-0.5, 0.5, 1.5, 2.5]),  # ramp
        done(1, due=9.0, sent=9.0, token_t=[9.5, 10.5, 11.5, 12.5]),
        done(2, due=10.0, sent=10.0, token_t=[10.1, 10.2, 10.3, 10.4]),
    ]
    s = summarise(log, seconds=10)
    # attempted: due in [0, 10): only request 1; the ramp's request and
    # the one due at the closing edge are not attempted ...
    assert s["attempted"] == 1
    assert s["ttft_s"] == pytest.approx([0.5])
    # ... but tokens and gaps inside the window count whoever sent them:
    # request 0 has tokens at 0.5, 1.5, 2.5 and gaps ending there.
    assert s["tokens_in_window"] == 3 + 1
    assert sorted(s["gaps_s"]) == pytest.approx([1.0, 1.0, 1.0])


def test_a_failed_or_refused_request_misses():
    refused = Request(0, [1], 4, {}, due=1.0)
    refused.sent, refused.status, refused.error = 1.0, 429, "shed"
    refused.ended = 1.01
    too_many = done(1, due=2.0, sent=2.0, token_t=[2.1, 2.2, 2.3, 2.4, 2.5])
    early_end = done(2, due=3.0, sent=3.0, token_t=[3.1, 3.2])   # "stop"
    s = summarise([refused, too_many, early_end], seconds=10)
    assert s["attempted"] == 3 and s["failed"] == 2
    assert s["ttft_s"][0] == float("inf")
    assert s["ttft_s"][2] == pytest.approx(0.1)
    assert loadgen.legal(early_end) is None      # fewer tokens: a success


def test_end_of_sequence_as_first_token_is_a_success_without_tokens():
    r = done(0, due=1.0, sent=1.0, token_t=[])
    s = summarise([r], seconds=10)
    assert s["failed"] == 0 and s["ttft_s"] == pytest.approx([0.01])


def test_probes_are_not_traffic():
    probe = done(-1, due=1.0, sent=1.0, token_t=[1.1, 1.2, 1.3, 1.4],
                 kind="probe")
    s = summarise([probe], seconds=10)
    assert s["attempted"] == 0 and s["tokens_in_window"] == 0


def test_the_schedule_is_fixed_and_the_tokens_are_the_seed_s():
    mix = traffic("chat-steady")
    a = loadgen.open_schedule(mix, 7, 92544, 4.0, -5.0, 45.0)
    b = loadgen.open_schedule(mix, 7, 92544, 4.0, -5.0, 45.0)
    c = loadgen.open_schedule(mix, 8, 92544, 4.0, -5.0, 45.0)
    assert [(r.due, r.prompt, r.max_tokens, r.sampling) for r in a] == \
        [(r.due, r.prompt, r.max_tokens, r.sampling) for r in b]
    # another --seed: the same times and lengths, other tokens
    assert [(r.due, len(r.prompt), r.max_tokens) for r in a] == \
        [(r.due, len(r.prompt), r.max_tokens) for r in c]
    assert [r.prompt for r in a] != [r.prompt for r in c]
    # the count is fixed: 4/s over 5 s of ramp and 45 s of window
    assert len(a) == 200
    assert sum(1 for r in a if r.due >= 0) == 180
    assert all(-5.0 <= r.due < 45.0 for r in a)
    assert all(16 <= len(r.prompt) <= 1024 for r in a)
    assert all(8 <= r.max_tokens <= 256 for r in a)
    lengths = sorted(len(r.prompt) for r in a)
    assert 140 < lengths[len(lengths) // 2] < 260        # median ~192
    seeded = [r for r in a if "seed" in r.sampling]
    assert len(seeded) == len(a) // 2
    assert all(r.sampling["temperature"] == 0.7 for r in seeded)


def test_closed_loop_clients_draw_independent_streams():
    mix = traffic("decode-batch")
    one = loadgen.client_stream(mix, 3, 32768, 0)
    again = loadgen.client_stream(mix, 3, 32768, 0)
    other = loadgen.client_stream(mix, 3, 32768, 1)
    first = next(one)
    assert first.prompt == next(again).prompt
    assert first.prompt != next(other).prompt
    assert 256 <= first.max_tokens <= 512
    # the pool is stratified: its lengths' median is the file's
    pool = [next(loadgen.client_stream(mix, 3, 32768, c))
            for c in range(16)]
    assert len({r.rid for r in pool}) == 16


def test_stratified_lengths_offer_the_same_work_whatever_the_order():
    import numpy as np

    dist = traffic("chat-steady")["prompt_tokens"]
    a = loadgen.stratified_lengths(np.random.default_rng(1), dist, 200)
    b = loadgen.stratified_lengths(np.random.default_rng(2), dist, 200)
    assert abs(sum(a) - sum(b)) / sum(a) < 0.01
    assert sorted(a)[100] in range(180, 205)             # median 192
    assert min(a) >= 16 and max(a) <= 1024


def test_probe_prompts_do_not_depend_on_the_seed():
    mix = traffic("longprompt")
    a, b = loadgen.probe_requests(mix, 32768), loadgen.probe_requests(
        mix, 32768)
    assert [p.prompt for p in a] == [p.prompt for p in b]
    assert len(a) == 4 and all(p.sampling == {"temperature": 0.0}
                               for p in a)
