"""The benchmark harness is driven by data: cells, deployments, traffic
mixes and per-layer metrics are found by name, and a run without a TPU
reports nothing."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
from bench import harness, testing, traffic  # noqa: E402

BENCH = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_loads_and_builds_its_grid(name):
    cell = harness.load_cell(REPO, name)
    mix, config = cell["mix"], cell["config"]
    assert config["reduced"] == [] and config["source"]
    assert cell["end_to_end"] and cell["per_layer"]
    for m in cell["per_layer"]:
        assert os.path.exists(os.path.join(REPO, "bench", "layers",
                                           m["name"] + ".py"))
    sched = traffic.schedule(mix)
    assert sched.t_arrive.shape == (len(mix["rows"]),)
    grid = traffic.grid(mix, 2**31 + 5, 1)
    assert grid.seed.shape == (traffic.points_per_dispatch(mix),)
    assert int(mix["sample"]) <= traffic.points_per_dispatch(mix)
    sim = harness.sim_config(config, mix["ticks"])
    assert sim.ticks == mix["ticks"] and sim.spot.enabled


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_has_limits_of_its_own(name):
    """``bench/limits/<cell>.json`` holds a limit for each number that the
    check compares, and the gap above which a point is off."""
    limits = harness.load_json(os.path.join(REPO, "bench", "limits",
                                            name + ".json"))
    numbers = harness.check_numbers(np.zeros((4, 3)), limits["tol"])
    assert set(limits) == set(numbers) | {"tol"}
    assert 0.0 < limits["tol"] < 1.0
    # Two points of the sample may be off, three may not.
    sample = int(harness.load_cell(REPO, name)["mix"]["sample"])
    assert 2 <= limits["diverged_share"] * sample < 3


@pytest.mark.parametrize("traffic_name,ttc,gap", [
    ("suite_frontier", 7500.0, 1), ("suite_headline", 6300.0, 5)])
def test_the_suite_generator_reproduces_the_paper_schedule(traffic_name,
                                                            ttc, gap):
    from repro.sim.workloads import paper_schedule
    mix = harness.load_json(os.path.join(REPO, "bench", "mixes",
                                         traffic_name + ".json"))
    mine = traffic.schedule(mix)
    theirs = paper_schedule(ttc=ttc, arrival_gap_ticks=gap).as_jax()
    for field in traffic.Schedule._fields:
        np.testing.assert_array_equal(np.asarray(getattr(theirs, field)),
                                      getattr(mine, field), err_msg=field)


def test_grid_seeds_follow_the_seed_and_the_dispatch():
    mix = harness.load_json(os.path.join(REPO, "bench", "mixes",
                                         "suite_frontier.json"))
    big = 2**33 + 17
    a = traffic.grid_seeds(mix, big, 1)
    np.testing.assert_array_equal(a, traffic.grid_seeds(mix, big, 1))
    assert not np.array_equal(a, traffic.grid_seeds(mix, big, 2))
    assert not np.array_equal(a, traffic.grid_seeds(mix, big + 1, 1))
    assert a.dtype == np.int32 and a.min() >= 0
    assert a.max() < traffic.SEED_BOUND


def test_a_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = testing.tiny_root(str(tmp_path / "root"))
    bench = os.path.join(root, "bench")
    cfg = harness.load_json(os.path.join(bench, "configs",
                                         "spot_frontier.json"))
    cfg["platform"]["spot"]["p_spike_per_core"] = 0.01
    with open(os.path.join(bench, "configs", "calmer.json"), "w") as fh:
        json.dump(cfg, fh)
    shutil.copy(os.path.join(bench, "mixes", "suite_frontier.json"),
                os.path.join(bench, "mixes", "short_suite.json"))
    shutil.copy(os.path.join(bench, "limits", "spot_frontier.suite.json"),
                os.path.join(bench, "limits", "calmer.short.json"))
    with open(os.path.join(bench, "layers", "dispatch_count.py"), "w") as fh:
        fh.write("def read(ctx):\n    return ctx['point_ticks']\n")
    spec = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    spec["configs"].append(dict(spec["configs"][0], name="calmer",
                                file="bench/configs/calmer.json"))
    spec["workloads"].append({"name": "calmer.short", "config": "calmer",
                              "traffic": "short_suite", "chips": 1,
                              "why": "a cell added by data alone"})
    spec["per_layer"].append({
        "name": "dispatch_count", "unit": "point-ticks", "better": "higher",
        "source": "program_counter", "layer": "sweep facade and host",
        "moves": "point_ticks_per_s", "workloads": ["calmer.short"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(spec, fh)

    found = harness.load_cell(root, "calmer.short")
    assert found["config"]["platform"]["spot"]["p_spike_per_core"] == 0.01
    assert [m["name"] for m in found["per_layer"]][-1] == "dispatch_count"
    assert "dispatch_count" not in [
        m["name"] for m in harness.load_cell(root, "spot_frontier.suite")[
            "per_layer"]]
    reader = harness.load_module(os.path.join(bench, "layers",
                                              "dispatch_count.py"), "dc")
    assert reader.read({"point_ticks": 7}) == 7

    rc, result, err = testing.run_cell(root, "calmer.short")
    assert rc == 0, err
    assert result["correct"] is True, err
    assert set(result["metrics"]) == {"point_ticks_per_s", "setup_s"}
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("trace", ["0", "1"])
def test_without_a_tpu_the_run_refuses_and_reports_nothing(tmp_path, trace):
    root = testing.tiny_root(str(tmp_path / "root"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"),
         "--workload", "spot_frontier.suite", "--seed", "3000000017",
         "--seconds", "1", "--trace", trace],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
