"""The harness finds a cell's builder, driver and metric readers by name,
so a later cell comes as new files."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench_testlib import harness  # noqa: E402

from chipbench import plugins  # noqa: E402

SPEC = harness.load_json(harness.REPO / "BENCHMARK.json")


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_its_code_by_name(workload):
    cell = harness.load_cell(workload)
    assert callable(plugins.load("builders", cell.config["kind"]).build)
    drivers = plugins.load("drivers", cell.traffic["kind"])
    assert callable(drivers.driver) and callable(drivers.control_readings)
    for m in cell.end_to_end + cell.per_layer:
        assert callable(plugins.load("metrics", m["name"]).read)
    assert "setup_s" in {m["name"] for m in cell.end_to_end}
    assert cell.per_layer


def test_a_module_is_loaded_once():
    a = plugins.load("drivers", "closed_loop_queries")
    assert plugins.load("drivers", "closed_loop_queries") is a


@pytest.mark.parametrize("group,name,error", [
    ("drivers", "no_such_kind", LookupError),
    ("builders", "no_such_kind", LookupError),
    ("elsewhere", "archive", ValueError),
])
def test_an_unknown_name_is_refused(group, name, error):
    with pytest.raises(error):
        plugins.load(group, name)
