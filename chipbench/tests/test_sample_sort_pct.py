"""``sample_sort_pct`` on a hand-made ``obs``: the share of the traced
window's decode steps whose sampler sorted — and None, never an
exception, where the program has no such counter (the parent of the PR
that added it), no step ran or nothing was traced."""

import pytest

from chipbench.layer_metrics.sample_sort_pct import read


def stats(decode_steps, **more):
    return {"tokens_generated": 0, "steps": decode_steps + 3,
            "decode_steps": decode_steps, **more}


def test_share_of_the_traced_steps_that_sorted():
    obs = {"traced": {
        "engine_before": stats(100, sample_sorted_steps=40,
                               sample_plain_steps=7),
        "engine": stats(380, sample_sorted_steps=208,
                        sample_plain_steps=7)}}
    assert read(obs) == pytest.approx(60.0)
    obs["traced"]["engine"]["sample_sorted_steps"] = 40
    assert read(obs) == 0.0


@pytest.mark.parametrize("obs", [
    {},
    {"traced": None},
    {"traced": {"engine": stats(380), "engine_before": stats(100)}},
    {"traced": {"engine": stats(100, sample_sorted_steps=4),
                "engine_before": stats(100, sample_sorted_steps=4)}},
], ids=["empty", "untraced", "parent", "no-step"])
def test_absent_source_reads_as_none(obs):
    assert read(obs) is None
