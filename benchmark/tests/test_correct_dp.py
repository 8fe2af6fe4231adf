"""``correct`` in the data-parallel cell. Run by hand, on the CPU's virtual
devices (``tests/conftest.py`` gives the backend eight):

    JAX_PLATFORMS=cpu python3 -m pytest tests/test_benchmark_correct_dp.py -q

``run.measure`` through kind ``train_loop_dp`` at a size a test can hold
(32,768 rows over four devices, 15 leaves, a one-second window, the device
ingest forced on so that the float32 route is the one taken) under the LIMITS
OF THE CELL, unchanged:

* a sound run passes, every guard of the kind at 0, and the bfloat16 CONTROL
  fails at least one number;
* the fault that belongs to the mechanism, one chip's histograms left out of
  the sum (``plants_dp.py``, through the program's own seam for its
  collectives), fails; so does half of the rows left out;
* each of the kind's guards trips on the run it is there for;
* a program that cannot say a float32 matrix stays float32 is stopped before
  any data is made.
"""
import argparse
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent.parent)]
import plants_dp
import reference
import run

CELL = "criteo_dp4.train"
CUT = {"data": {"rows": 32768},
       "params": {"num_leaves": 15, "tpu_ingest": 1}}
DEVICE = {"platform": "cpu", "kind": "test", "count": 4}
GUARDS = ("guard.serial_fallback", "guard.float64_route",
          "guard.unsharded_bins")


@pytest.fixture(autouse=True)
def _mesh_and_counters():
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    from lightgbm_tpu.obs import registry as obs
    c = obs.counter("learner/serial_fallbacks")
    c.add(-c.value)
    yield
    from lightgbm_tpu.parallel import learners
    learners.set_network_functions()


def drive(seed, plant=None, lower=False, params=None, kind_hook=None,
          rows=None):
    bench, cell, config, traffic = run.load_cell(CELL)
    kind = run.load_module(run.HERE / "kinds" / f"{traffic['kind']}.py")
    if plant is not None:
        kind.make_system = plant(kind)
    if kind_hook is not None:
        kind_hook(kind)
    cut = {"data": {"rows": rows or CUT["data"]["rows"]},
           "params": {**CUT["params"], **(params or {})}}
    ns = argparse.Namespace(workload=CELL, seed=seed, seconds=1.0, trace=0)
    line, res = run.measure(ns, bench, cell, config, traffic, DEVICE,
                            on_chip=False, overrides=cut, lower=lower, kind=kind)
    over = [k for k, c in line["checks"].items() if not c["value"] <= c["limit"]]
    return line, res, over


@pytest.mark.parametrize("seed", [4000000007])
def test_sound_run_passes_and_the_control_fails(seed):
    line, res, over = drive(seed, lower=True)
    assert line["correct"], over
    assert line["attempted"] >= 1 and line["failed"] == 0
    json.dumps(line)
    for g in GUARDS:
        assert line["checks"][g] == {"value": 0.0, "limit": 0.0}, g
    assert res["facts"]["chips"] == 4 and res["facts"]["rows"] == 32768
    control = res["facts"]["reference"]["control"]
    ok, rows = reference.decide({**res["numbers"], **control}, res["limits"])
    assert not ok and [k for k, v, lim in rows if v > lim]


@pytest.mark.parametrize("plant", list(plants_dp.ALL.values()),
                         ids=list(plants_dp.ALL))
def test_a_broken_sum_is_not_correct(plant):
    # two of the reference's row blocks: "half" leaves out the odd ones
    line, _res, over = drive(23, plant=plant, rows=65536)
    assert line["correct"] is False
    assert set(over) - set(GUARDS), "no compared NUMBER went over its limit"
    print(plant.__name__, "failed:", over)


@pytest.mark.parametrize("guard, params", [
    ("guard.serial_fallback", {"tree_learner": "serial"}),
    ("guard.serial_fallback", {"num_machines": 2}),
    ("guard.float64_route", {"tpu_ingest": 0}),
], ids=["serial_learner", "two_of_four_chips", "host_binner"])
def test_each_guard_trips_on_its_own_run(guard, params):
    line, _res, over = drive(31, params=params)
    assert line["correct"] is False and guard in over, over


def test_float64_data_trips_the_route_guard():
    def float64_data(kind):
        make = kind._loop.datagen.make
        kind._loop.datagen.make = lambda *a: (
            lambda X, L, y: (X.astype("float64"), L, y))(*make(*a))
    line, _res, over = drive(31, kind_hook=float64_data)
    assert "guard.float64_route" in over and not line["correct"]


def test_a_program_without_the_float32_route_is_stopped_at_once(monkeypatch, capsys):
    import lightgbm_tpu.basic as basic
    monkeypatch.delattr(basic, "keeps_float32")
    made = []

    def no_data(kind):
        kind._loop.datagen.make = lambda *a: made.append(a)
    with pytest.raises(SystemExit) as stop:
        drive(31, kind_hook=no_data)
    assert stop.value.code == 5 and not made
    assert len(capsys.readouterr().err.strip().splitlines()) == 1
