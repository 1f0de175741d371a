"""The training CLI's own timing and its profiler trace of the program's
phases and host spans."""
import glob
import os

import pytest

from repro.launch import train


def test_done_line_keeps_the_compiling_chunk_apart(monkeypatch):
    now = iter([100.0, 130.0, 134.0, 138.0])  # start, then three chunk ends
    monkeypatch.setattr(train.time, "time", lambda: next(now))
    clock = train.ChunkClock()
    for _ in range(3):
        clock.begin()
        clock.end(2, None)
    assert clock.done() == (
        "done: 6 steps in 38.0s; first chunk (2 steps, compile included) "
        "30.0s; then 2.000s/step over 2 chunks")


def test_trace_dir_holds_the_device_phases_and_host_spans(tmp_path):
    from jax.profiler import ProfileData

    train.main(["--arch", "albert-large", "--reduced", "--mesh", "1x1",
                "--steps", "8", "--scan-steps", "2",
                "--trace-dir", str(tmp_path)])
    files = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    names = {e.name for p in ProfileData.from_file(files[0]).planes
             for line in p.lines for e in line.events}
    spans = {n for n in names if n.startswith("btard.host.")}
    assert spans == {"btard.host.dispatch", "btard.host.fetch",
                     "btard.host.membership", "btard.host.checksum"}


@pytest.mark.parametrize("n_chunks", [0, 1])
def test_done_line_with_no_steady_chunk(n_chunks):
    clock = train.ChunkClock()
    for _ in range(n_chunks):
        clock.end(3, None)
    assert clock.done().startswith("done: %d steps" % (3 * n_chunks))
    assert "then" not in clock.done()
