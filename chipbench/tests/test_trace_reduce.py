"""The reduction from trace to numbers: interval arithmetic on made-up
intervals, and the whole reduction on two small traces recorded on the
v5e (``data/``: one chip, two named programs with sleeps between them;
four chips, a sharded step with collectives), checked against sums made
here in the plainest way."""

import os

import pytest

from chipbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.union([[5, 7], [1, 3], [2, 4], [7, 8], [10, 11]]) == \
        [[1, 4], [5, 8], [10, 11]]
    assert tr.total(tr.union([[0, 10], [2, 3], [9, 12]])) == 12


def test_subtract_and_gaps():
    a = [[0, 10], [20, 30]]
    b = [[2, 3], [8, 22], [29, 40]]
    assert tr.subtract(a, b) == [[0, 2], [3, 8], [22, 29]]
    assert tr.subtract(a, []) == a
    assert tr.subtract([], b) == []
    assert tr.gaps([[2, 3], [5, 9]], 0, 10) == [[0, 2], [3, 5], [9, 10]]


def test_names():
    assert tr.program_name("jit__decode(1234567)") == "jit__decode"
    assert tr.op_name("all-gather-start.12") == "all-gather-start"
    assert tr.op_name("fusion.3") == "fusion"
    assert tr.op_name("copy") == "copy"


def made_up(lines: dict):
    """A profile of one device from {line name: [(name, start, end)]}."""
    from types import SimpleNamespace as NS

    return NS(planes=[NS(name="/device:TPU:0", lines=[
        NS(name=line, events=[NS(name=n, start_ns=s, duration_ns=e - s)
                              for n, s, e in events])
        for line, events in lines.items()])])


def test_control_flow_is_not_busy_and_async_collectives_count():
    reduced = tr.reduce_profile(made_up({
        "XLA Modules": [("jit_train_step(7)", 0, 100_000)],
        "XLA Ops": [("%while.3 = (...) while(...)", 0, 100_000),
                    ("%fusion.1 = bf16[8] fusion(...)", 10_000, 40_000),
                    ("%all-gather-done.2 = ...", 40_000, 50_000),
                    ("%all-reduce.5 = ...", 60_000, 70_000)],
        "Async XLA Ops": [("%all-gather-start.2 = ...", 20_000, 50_000),
                          ("%copy-start.9 = ...", 80_000, 90_000)]}))
    device = reduced["devices"][0]
    assert reduced["window_s"] == pytest.approx(100e-6)
    # the while spans everything and is left out: busy is 10-50, 60-70
    assert device["busy_s"] == pytest.approx(50e-6)
    # in flight 20-50 and on the core 60-70; 20-40 ran beside the fusion
    assert device["collective_s"] == pytest.approx(40e-6)
    assert device["collective_exposed_s"] == pytest.approx(20e-6)
    # the while is still listed by name, for the breakdown
    assert ["while", pytest.approx(100e-6), 1] in device["ops"]


def test_a_trace_with_modules_alone_is_busy_by_its_programs():
    reduced = tr.reduce_profile(made_up({
        "XLA Modules": [("jit__decode(1)", 0, 30), ("jit__decode(1)", 50, 60)]}))
    assert reduced["devices"][0]["busy_s"] == pytest.approx(40e-9)


def recorded(name):
    path = os.path.join(HERE, "data", name)
    if not os.path.exists(path):
        pytest.skip(f"{name} is not recorded")
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    return profile, tr.reduce_profile(profile)


def plain(profile, plane_name):
    """(ops, modules) of one device, as (name, start, end)."""
    for plane in profile.planes:
        if plane.name == plane_name:
            lines = {line.name: [(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns)
                                 for e in line.events]
                     for line in plane.lines}
            return lines.get("XLA Ops", []), lines.get("XLA Modules", [])
    raise AssertionError(plane_name)


def brute_busy(events, lo, hi, step=50):
    """Busy time by sampling the window every ``step`` ns."""
    events = sorted((s, e) for _, s, e in events)
    busy, i, t = 0, 0, lo
    while t < hi:
        while i < len(events) and events[i][1] <= t:
            i += 1
        if any(s <= t < e for s, e in events[i:i + 8]):
            busy += step
        t += step
    return busy


def test_one_chip_trace():
    profile, reduced = recorded("v5e_1chip.xplane.pb")
    assert len(reduced["devices"]) == 1
    device = reduced["devices"][0]
    ops, modules = plain(profile, device["name"])
    lo = min(s for _, s, _ in ops + modules)
    hi = max(e for _, _, e in ops + modules)
    assert reduced["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert device["busy_s"] * 1e9 == pytest.approx(
        brute_busy(ops, lo, hi), rel=0.02)
    assert 0 < device["busy_s"] < reduced["window_s"]
    # two programs, executed 6 and 3 times, their time summed plainly
    programs = device["programs"]
    decode = tr.program_time(reduced, r"_decode$")
    chunk = tr.program_time(reduced, r"_prefill_chunk$")
    assert decode[0] == 6 and chunk[0] == 3
    assert decode[1] == pytest.approx(sum(
        e - s for n, s, e in modules if "_decode" in n) / 1e9)
    assert sum(p["count"] for p in programs.values()) == len(modules)
    # the sleeps between the programs are the longest idle gaps
    longest = device["idle_gaps"][0]
    assert longest[1] > 0.002
    assert sum(g[1] for g in device["idle_gaps"]) <= \
        reduced["window_s"] - device["busy_s"] + 1e-9
    assert device["collective_s"] == 0.0
    b = tr.breakdown(reduced)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_four_chip_trace_has_collectives():
    profile, reduced = recorded("v5e_4chip.xplane.pb")
    assert len(reduced["devices"]) == 4
    for device in reduced["devices"]:
        ops, _ = plain(profile, device["name"])
        # by the operation's OWN name: the trace gives the whole HLO
        # line, and a fusion that reads %all-gather.3 is no collective
        coll = [(s, e) for n, s, e in ops
                if tr._COLLECTIVE.search(tr.op_name(n))]
        assert coll, "a sharded step without a collective"
        # (this trace has no collective on the asynchronous line)
        assert device["collective_s"] * 1e9 == pytest.approx(
            tr.total(tr.union([list(c) for c in coll])))
        assert 0 <= device["collective_exposed_s"] <= \
            device["collective_s"] + 1e-12
        assert tr.program_time(reduced, r"train_step$")[0] == 4
    worst = tr.worst_device(reduced, "collective_exposed_s")
    assert worst["collective_exposed_s"] == max(
        d["collective_exposed_s"] for d in reduced["devices"])
