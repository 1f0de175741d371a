"""The reduction from a profiler trace to device times."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"


def test_reduce_hand_counted():
    tr = {
        "devices": {
            "TPU:0": [["while.1", 0, 150, "other"],  # encloses the next two
                      ["fusion.1", 0, 100, "other"],
                      ["all-to-all.2", 100, 50, "collective"],
                      ["custom-call.3", 300, 50, "custom_call"],
                      ["fusion.1", 400, 100, "other"]],
            "TPU:1": [["fusion.1", 0, 300, "other"]],
        },
        "host": [["bench.fetch", 140, 200], ["bench.boundary", 360, 30]],
    }
    red = trace.reduce(tr)
    d0 = red["devices"]["TPU:0"]
    # busy union of [0,150] [300,350] [400,500] = 300 ns
    assert d0["busy_s"] == pytest.approx(300e-9)
    assert d0["other"] == pytest.approx(200e-9)
    assert d0["collective"] == pytest.approx(50e-9)
    assert d0["custom_call"] == pytest.approx(50e-9)
    assert red["busy_s"] == pytest.approx((300e-9 + 300e-9) / 2)
    # gap [150,300] lies under bench.fetch; gap [350,400] under bench.boundary
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"bench.fetch": 150e-9, "bench.boundary": 50e-9})
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx((200e-9 + 300e-9) / 2)


@pytest.mark.parametrize("text,kind", [
    ("%all-to-all.3 = f32[4,19555840]{1,0} all-to-all(f32[4,19555840]{1,0} %p)",
     "collective"),
    ("%all-gather-start.1 = (f32[19555840]{0}, f32[78223360]{0}) "
     "all-gather-start(f32[19555840]{0} %x)", "collective"),
    # a fusion that reads the result of an XLA-internal custom call
    ("%fusion.636 = bf16[16,512,1024]{2,1,0} fusion(bf16[4096,1024]{1,0} "
     "%custom-call.98), kind=kOutput, calls=%fused_computation.49", "other"),
    ("%custom-call.12 = bf16[4096,1024]{1,0} custom-call(), "
     'custom_call_target="AllocateBuffer"', "other"),
    ("%custom-call.4 = f32[1,19555840]{1,0} custom-call(f32[4,19555840]{1,0} %s), "
     'custom_call_target="tpu_custom_call"', "custom_call"),
])
def test_kind_of_an_operation(text, kind):
    assert trace._kind(text) == kind


def test_reduce_trace_recorded_on_the_chip():
    """105 ms of albert-large.btard.1chip traced on a TPU v5e, checked
    against a second count: busy time by a sweep over the interval ends,
    per-kind time over the ops inside which no other op starts."""
    rec = json.loads((DATA / "trace_albert_1chip.json").read_text())
    evs = rec["trace"]["devices"]["TPU:0"]
    start = np.array([e[1] for e in evs])
    end = start + np.array([e[2] for e in evs])
    ends = np.concatenate([start, end])
    step = np.concatenate([np.ones_like(start), -np.ones_like(end)])
    order = np.lexsort((-step, ends))
    depth = np.cumsum(step[order])
    busy = np.sum(np.diff(ends[order])[depth[:-1] > 0]) * 1e-9
    srt = np.sort(start)
    inside = np.searchsorted(srt, end, "left") - np.searchsorted(srt, start, "left")
    innermost = inside <= 1
    red = trace.reduce(rec["trace"])
    dev = red["devices"]["TPU:0"]
    assert dev["busy_s"] == pytest.approx(busy, rel=1e-9)
    assert dev["other"] == pytest.approx(
        sum(e[2] for e, leaf in zip(evs, innermost) if leaf) * 1e-9, rel=1e-9)
    assert dev["custom_call"] == 0.0 and dev["collective"] == 0.0
    assert innermost.sum() < len(evs)  # the CenteredClip loop nests its body
    assert 0.0 < red["busy_s"] < rec["window_s"]
    idle = sum(v for _, v in red["idle_gaps"])
    assert idle == pytest.approx(end.max() * 1e-9 - start.min() * 1e-9 - busy, rel=1e-6)
    assert [name for name, _ in red["idle_gaps"]][0].startswith("bench.")
