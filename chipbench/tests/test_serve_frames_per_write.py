"""``serve_frames_per_write`` on a hand-made ``obs``: ``chunks`` over
``writes`` of the window's streams — 1.0 where every frame had a write
of its own, above it where frames shared one — and None, never an
exception, where no ``http:`` span carries ``writes`` (the parent of the
PR that added it) or no stream wrote in the window."""

import pytest

from chipbench.layer_metrics import serve_frames_per_write

WINDOW, SECONDS = 1000.0, 50


def stream(i, received, chunks, writes=None):
    """The two spans of a stream of ``chunks`` frames, the first written
    0.2 s after ``received``, one every 10 ms."""
    trace = f"{i:032x}"
    http = {"trace_id": trace, "name": "http:/v1/completions",
            "ts": received, "dur_s": 1.0,
            "attrs": {"stream": True, "status": 200, "chunks": chunks,
                      "frame_ms": [200.0 + 10.0 * k
                                   for k in range(chunks)],
                      "pull_wait_ms": [0.1] * chunks}}
    if writes is not None:
        http["attrs"]["writes"] = writes
    engine = {"trace_id": trace, "name": "llm:engine",
              "ts": received + 0.004, "dur_s": 0.9,
              "stages": {"queue": 0.01, "prefill": 0.18, "decode": 0.7},
              "attrs": {"prompt_tokens": 128, "output_tokens": chunks - 1,
                        "emit_ms": [10.0 * k for k in range(chunks - 1)]}}
    return [http, engine]


def obs_of(streams):
    return {"spans": [s for pair in streams for s in pair],
            "window_wall": WINDOW, "seconds": SECONDS}


def test_every_frame_its_own_write_reads_one():
    obs = obs_of([stream(i, WINDOW + i, 20, writes=20) for i in range(12)])
    assert serve_frames_per_write.read(obs) == pytest.approx(1.0)


def test_frames_that_shared_writes_read_above_one():
    # half written five frames at a time, half in 12 writes: 240 / 96
    obs = obs_of([stream(i, WINDOW + i, 20, writes=12 if i % 2 else 4)
                  for i in range(12)])
    assert serve_frames_per_write.read(obs) == pytest.approx(2.5)


def test_a_stream_outside_the_window_does_not_count():
    inside = [stream(i, WINDOW + i, 20, writes=20) for i in range(12)]
    before = stream(99, WINDOW - 30, 20, writes=1)
    after = stream(98, WINDOW + SECONDS + 1, 20, writes=1)
    assert serve_frames_per_write.read(
        obs_of(inside + [before, after])) == pytest.approx(1.0)


@pytest.mark.parametrize("obs", [
    {},
    {"spans": [], "window_wall": WINDOW, "seconds": SECONDS},
    obs_of([stream(i, WINDOW + i, 20) for i in range(12)]),
    obs_of([stream(i, WINDOW + i, 20, writes=20) for i in range(3)]),
    obs_of([stream(i, WINDOW - 30, 20, writes=20) for i in range(12)]),
], ids=["empty", "no-span", "parent", "too-few", "none-in-window"])
def test_absent_writes_read_as_none(obs):
    assert serve_frames_per_write.read(obs) is None
