"""``pallas_ms_per_step``: the Pallas kernels' device time a step, read from
the reduced trace that ``xla_ms_per_step`` reads."""
import json
from pathlib import Path

import pytest

from bench import catalog, trace

DATA = Path(__file__).resolve().parent / "data"


def _ctx(tr, steps):
    return {"trace": trace.reduce(tr), "steps": steps}


def test_kernel_time_hand_counted():
    tr = {
        "devices": {
            "TPU:0": [["while.1", 0, 400, "other"],  # encloses the next three
                      ["fusion.1", 0, 100, "other"],
                      ["flash_attention.3", 100, 200, "custom_call"],
                      ["all-to-all.2", 300, 100, "collective"],
                      ["flash_mha_bwd_dq.4", 500, 300, "custom_call"]],
            "TPU:1": [["flash_attention.3", 0, 100, "custom_call"]],
        },
        "host": [],
    }
    ctx = _ctx(tr, steps=2)
    pallas = catalog.reader("pallas_ms_per_step")(ctx)
    # (500 ns + 100 ns) over two chips, over two steps, in ms
    assert pallas == pytest.approx(1e3 * (600e-9 / 2) / 2)
    xla = catalog.reader("xla_ms_per_step")(ctx)
    inner = sum(d for _, _, d, k in tr["devices"]["TPU:0"][1:]
                if k != "collective") + 100
    # the two readers split the innermost non-collective time between them
    assert pallas + xla == pytest.approx(1e3 * inner * 1e-9 / 2 / 2)


def test_trace_recorded_on_the_chip_reads_no_kernel():
    """The committed albert-large.btard.1chip trace ran the jnp attention
    and the jnp CenteredClip: no custom call, so 0 ms a step."""
    rec = json.loads((DATA / "trace_albert_1chip.json").read_text())
    assert catalog.reader("pallas_ms_per_step")(_ctx(rec["trace"], 1)) == 0.0


def test_no_device_reads_nothing():
    assert catalog.reader("pallas_ms_per_step")(
        _ctx({"devices": {}, "host": []}, 1)) is None
