"""The yardstick's arithmetic against hand counts."""
import dataclasses
import json

import pytest

from bench import catalog, flops


def _config(name):
    """A configuration file, whether or not a cell runs it."""
    with open(catalog.BENCH / "configs" / f"{name}.json") as f:
        return json.load(f)


def _model(name):
    return _config(name)["model"]


def test_albert_large_flops_per_token():
    m = _model("albert-large-e1024")
    # 24 applications of one layer (4 x 1024^2 + 2 x 1024 x 4096) + the head,
    # which is the embedding, tied
    assert flops.matmul_params(m) == 24 * (4 * 1024**2 + 2 * 1024 * 4096) + 1024 * 30000
    assert flops.matmul_params(m) == 332_709_888
    attention = 12 * 24 * 16 * 64 * 512
    assert flops.flops_per_token(m, 512) == 6 * 332_709_888 + attention
    assert flops.flops_per_token(m, 512) == 2_147_254_272


def test_mamba2_flops_per_token():
    m = _model("mamba2-2.7b-d4")
    in_proj = 2560 * (2 * 5120 + 2 * 128 + 80)
    out_proj = 5120 * 2560
    # the head (the embedding, tied) multiplies once a token
    assert flops.matmul_params(m) == 4 * (in_proj + out_proj) + 2560 * 50280
    ssd = 3 * (2 * 256 * 128 + 2 * 256 * 80 * 64 + 4 * 128 * 80 * 64)
    assert flops.sequence_flops(m, 2048) == 4 * ssd
    assert flops.flops_per_token(m, 2048) == 6 * (289_443_840) + 4 * 15_925_248


def test_aggregation_bytes_follow_the_pass_model():
    d = 47_503_360
    # one owner over all of d: 20 clip passes + 1 digest pass + the aggregate
    assert flops.aggregation_bytes(1, d, 20) == 21 * d * 4 + d * 4
    # four owners: each stack is 4 x ceil(d / 4), the same bytes a peer
    part = -(-d // 4)
    assert flops.aggregation_bytes(4, d, 20) == 21 * 4 * part * 4 + part * 4


def test_peaks_are_keyed_by_device_kind():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("name", [
    pytest.param("albert-large-e1024", id="albert-large"), "mamba2-2.7b-d4"])
def test_configuration_file_is_what_the_program_runs(name):
    from repro.configs import get_config
    from repro.models import Model

    cfg_file = _config(name)
    cfg = dataclasses.replace(get_config(cfg_file["arch"]), **cfg_file["overrides"])
    got = dataclasses.asdict(cfg)
    got["pattern"] = [list(s.values()) for s in got["pattern"]]
    assert {k: got[k] for k in cfg_file["model"]} == cfg_file["model"]
    assert Model(cfg).param_count() == cfg_file["params"]
    assert set(cfg_file["reduced"]) <= set(cfg_file["overrides"])
