"""``moe_tile_fill_pct`` on a hand-made ``obs``: the share of the rows
the grouped kernel multiplied that were asked for — and None, never an
exception, where the program has no such counter (the parent of the PR
that added it), the counter stood still (XLA's kernel multiplies) or
nothing was traced."""

import pytest

from chipbench.layer_metrics.moe_tile_fill_pct import read


def stats(assignments, **more):
    return {"tokens_generated": 0, "steps": 7, "decode_steps": 5,
            "moe_assignments": assignments, **more}


def test_share_of_the_multiplied_rows_that_were_asked_for():
    obs = {"traced": {
        "engine_before": stats(1000, moe_tile_rows=4096),
        "engine": stats(1480, moe_tile_rows=4096 + 3200)}}
    assert read(obs) == pytest.approx(15.0)
    obs["traced"]["engine"]["moe_assignments"] = 1000
    assert read(obs) == 0.0


@pytest.mark.parametrize("obs", [
    {},
    {"traced": None},
    {"traced": {"engine": stats(1480), "engine_before": stats(1000)}},
    {"traced": {"engine": stats(1480, moe_tile_rows=0),
                "engine_before": stats(1000, moe_tile_rows=0)}},
    {"traced": {"engine": {"steps": 9, "moe_tile_rows": 64},
                "engine_before": {"steps": 2, "moe_tile_rows": 0}}},
], ids=["empty", "untraced", "parent", "xla-kernel", "dense"])
def test_absent_source_reads_as_none(obs):
    assert read(obs) is None
