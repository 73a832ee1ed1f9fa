"""The two per-layer metrics read from ``prompt_end_gaps`` of the
``llm:engine`` span (``itl_prompt_end_gaps_pct``,
``prompt_end_gap_p50_ms``) on the hand-made ``obs`` of
``test_itl_span_metrics.py``: a share and a median, the window's edges,
and None, never an exception, where a span does not say (the parent of
the PR that added the attribute) or nothing is traced."""

import importlib

import pytest

from test_itl_span_metrics import WINDOW, spans_obs, stream

NAMES = ("itl_prompt_end_gaps_pct", "prompt_end_gap_p50_ms")


def read(name, obs):
    return importlib.import_module(
        "chipbench.layer_metrics." + name).read(obs)


def ended(spans, gaps):
    """``spans`` (one ``stream``'s) whose ``llm:engine`` span says that
    a prompt ended across ``gaps``."""
    spans[2]["attrs"]["prompt_end_gaps"] = sorted(gaps)
    return spans


def twelve(gaps=(), **kw):
    """Twelve streams of 10 gaps each, first tokens a second apart."""
    return [s for i in range(12) for s in ended(
        stream(i, WINDOW + 1 + i, long_ms=30.0 + i, **kw), gaps)]


def test_prompt_end_gaps_are_a_share_and_a_median():
    # three gaps in ten saw a chunk, one of them a prompt's last: the
    # share counts the one, the median times it and not the others
    spans = twelve({4}, chunk_gaps={2, 4, 8})
    obs = spans_obs(spans)
    assert read("itl_chunk_gaps_pct", obs) == pytest.approx(30.0)
    assert read("itl_prompt_end_gaps_pct", obs) == pytest.approx(10.0)
    assert read("prompt_end_gap_p50_ms", obs) == pytest.approx(35.5)
    # a gap that ends outside the window is in neither: the early
    # stream's gaps 1 and 2 ended 15 and 5 ms before it, 3 to 10 end
    # inside, the 20 ms one across a prompt's end
    spans += ended(stream(100, WINDOW - 0.035, chunk_gaps={1, 6},
                          long_ms=20.0), {1, 6})
    obs = spans_obs(spans)
    assert read("itl_prompt_end_gaps_pct", obs) == pytest.approx(
        100.0 * 13 / 128)
    assert read("prompt_end_gap_p50_ms", obs) == pytest.approx(35.0)
    # the probes are told from the traffic by prompt length
    spans += ended(stream(103, WINDOW + 30, chunk_gaps={5}, long_ms=700.0,
                          prompt_tokens=96), {5})
    obs = spans_obs(spans, client={"requests": [(128, [0.1, 0.2])]})
    assert read("prompt_end_gap_p50_ms", obs) == pytest.approx(35.0)


def test_no_prompt_ended_beside_a_decoding_request():
    obs = spans_obs(twelve(chunk_gaps={4}))
    assert read("itl_prompt_end_gaps_pct", obs) == 0.0
    assert read("prompt_end_gap_p50_ms", obs) is None


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("obs", [
    {},
    {"spans": None, "traced": None, "trace": None},
    # the parent: hand-overs and chunk gaps, nothing on a prompt's end
    spans_obs([s for i in range(12) for s in stream(
        i, WINDOW + 1 + i, chunk_gaps={4})], gaps_pct=10.0),
    # ONE span among them that does not say
    spans_obs(twelve({4}, chunk_gaps={4})
              + stream(100, WINDOW + 20, chunk_gaps={4}), gaps_pct=10.0),
    # too few streams
    spans_obs(twelve({4}, chunk_gaps={4})[:9 * 3]),
    # no request's gap lies in the window
    spans_obs([s for i in range(12) for s in ended(
        stream(i, WINDOW - 30 + i, chunk_gaps={4}), {4})]),
], ids=["empty", "untraced", "parent", "one-span-silent", "too-few",
        "all-before-the-window"])
def test_absent_source_reads_as_none(name, obs):
    assert read(name, obs) is None
    if "gaps_pct" in obs:     # the gaps are there: only these two are deaf
        assert read("itl_chunk_gaps_pct", obs) == pytest.approx(
            obs["gaps_pct"])
