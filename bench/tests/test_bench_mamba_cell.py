"""The Mamba-2 cell's files, found by name as the harness finds them, and
its configuration against the program's."""
import dataclasses
import math

from bench import catalog, harness, reference, weights
from repro.configs import get_config
from repro.models import Model

CELL = "mamba2-2.7b-d4.btard.1chip"


def _pieces():
    bm = catalog.load_benchmark()
    cell = catalog.workload(bm, CELL)
    return (cell, catalog.config(bm, cell["config"]),
            catalog.traffic(cell["traffic"]), catalog.limits(CELL))


def test_the_cells_files_load_by_name_on_one_chip():
    cell, config, traffic, limits = _pieces()
    assert cell["chips"] == 1 and traffic["mesh"] == [1, 1]
    assert config["name"] == cell["config"] and traffic["name"] == cell["traffic"]
    assert limits


def test_configuration_agrees_with_the_program():
    """``_check_config`` passes on get_config("mamba2-2.7b") plus the file's
    overrides, and the program's parameters are the reference's template,
    289,585,600 of them."""
    _, config, _, _ = _pieces()
    cfg = dataclasses.replace(get_config(config["arch"]), **config["overrides"])
    harness._check_config(cfg, config["model"])
    tmpl = weights.template_of(Model(cfg).abstract_params())
    ref = {p: (tuple(s), d) for p, (s, d) in reference.template(config["model"]).items()}
    assert tmpl == ref
    n = sum(math.prod(s) for s, _ in tmpl.values())
    assert n == config["params"] == 289_585_600
