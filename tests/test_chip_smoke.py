"""chip_smoke.py on the CPU: its entry refuses to run without a TPU, and
its phase functions pass at a reduced size (kernels interpreted)."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402


def test_entry_refuses_non_tpu_backend(capsys):
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--four-chips"]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_phases_pass_on_cpu_at_reduced_size():
    launch = chip_smoke.phase_launch(
        "albert-large", reduced=True, seq=8, batch=2, steps=2,
        scan_steps=2, clip_iters=1)
    assert launch["native_kernel"] is False  # the CPU interprets kernels
    bans = chip_smoke.phase_engine(
        "albert_large", reduced=True, seq=8, steps=2, clip_iters=1)
    assert bans[True] == bans[False] == {3: 1}
