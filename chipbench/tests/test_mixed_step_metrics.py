"""``chunk_fused_pct`` and ``mixed_step_ms`` on a hand-made ``obs``: the
share of the traced window's chunks that rode a decode step and the
device time of the program that ran them — and None, never an
exception, where the program has neither counter nor program (the
parent of the PR that added them), no chunk ran or nothing was traced."""

import pytest

from chipbench.layer_metrics import (chunk_fused_pct, decode_step_ms,
                                     mixed_step_ms, prefill_chunk_ms)


def stats(chunks, **more):
    return {"tokens_generated": 0, "steps": 300, "decode_steps": 290,
            "chunks": chunks, **more}


def trace(**programs):
    return {"devices": [{"programs": {
        name: {"count": count, "total_s": total_s}
        for name, (count, total_s) in programs.items()}}]}


def test_share_of_the_traced_chunks_that_rode_a_step():
    obs = {"traced": {"engine_before": stats(10, chunks_fused=4),
                      "engine": stats(42, chunks_fused=28)}}
    assert chunk_fused_pct.read(obs) == pytest.approx(75.0)
    obs["traced"]["engine"]["chunks_fused"] = 4
    assert chunk_fused_pct.read(obs) == 0.0


@pytest.mark.parametrize("obs", [
    {},
    {"traced": None},
    {"traced": {"engine": stats(42), "engine_before": stats(10)}},
    {"traced": {"engine": stats(10, chunks_fused=4),
                "engine_before": stats(10, chunks_fused=4)}},
], ids=["empty", "untraced", "parent", "no-chunk"])
def test_absent_counter_reads_as_none(obs):
    assert chunk_fused_pct.read(obs) is None


def test_device_time_of_the_mixed_program_alone():
    obs = {"trace": trace(jit__mixed_step=(32, 0.448),
                          jit__decode=(256, 3.2),
                          jit__prefill_chunk=(2, 0.023),
                          jit__sample_batch=(288, 0.003))}
    assert mixed_step_ms.read(obs) == pytest.approx(14.0)
    # the two programs it stands in for keep their own readers
    assert prefill_chunk_ms.read(obs) == pytest.approx(11.5)
    assert decode_step_ms.read(obs) == pytest.approx(
        1000.0 * (3.2 + 0.003) / 256)


@pytest.mark.parametrize("obs", [
    {}, {"trace": None}, {"trace": {"devices": []}},
    {"trace": trace(jit__decode=(256, 3.2), jit__prefill_chunk=(2, 0.02))},
], ids=["empty", "untraced", "no-device", "parent"])
def test_absent_program_reads_as_none(obs):
    assert mixed_step_ms.read(obs) is None
