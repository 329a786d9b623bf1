"""The harness end to end on the CPU at a tiny size: a cell added as new
files is found and runs correct while the fp8 control is not; each fault
planted under the timed path makes ``correct`` false; off the chip the entry
point refuses to report."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

import check
import chipbench_tiny as tiny
import faults
import harness
from registry import Registry

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]

NEW_METRIC = '''"""A metric added as a file: requests scored in the window."""


def read(ctx):
    return float(len(ctx.requests))
'''


@pytest.fixture(scope="module")
def base(tmp_path_factory):
    return tiny.layout(tmp_path_factory.mktemp("cell"))


def _run(base, engine_cls=None, trace=False, bench=None, cell=tiny.CELL,
         seed=2 ** 32 + 11, with_control=False):
    reg = Registry(bench or tiny.bench(), base)
    return harness.run(base, cell, seed, 1.0, trace, time.perf_counter(),
                       require_chip=False, registry=reg,
                       engine_cls=engine_cls, with_control=with_control,
                       log=lambda *a: None)


def test_a_cell_added_as_files_is_found_and_runs_correct(base):
    closed = dict(tiny.REC, loop={"kind": "closed", "outstanding": 6},
                  warm_requests=6)
    (base / "traffic" / "tiny_closed.json").write_text(json.dumps(closed))
    (base / "metrics" / "scored_in_window.py").write_text(NEW_METRIC)
    (base / "limits" / "tiny.closed.json").write_text(
        json.dumps(tiny.LIMITS))
    bench = tiny.bench(
        cells=[(tiny.CELL, "tiny", "tiny_rec"),
               ("tiny.closed", "tiny", "tiny_closed")],
        per_layer=[{"name": "scored_in_window", "unit": "requests",
                    "better": "higher", "source": "program_counter",
                    "layer": "test", "moves": "scored_rps",
                    "workloads": ["tiny.closed"]}])
    res = _run(base, trace=True, bench=bench, cell="tiny.closed",
               with_control=True)
    assert res["correct"], res["checks"]
    # the fp8 control, put in the program's place, is not correct
    ctl = check.verdict(res["control"], tiny.LIMITS,
                        res["checks"]["checked"]["value"], 0)
    assert not check.is_correct(ctl), ctl
    assert res["metrics"]["scored_in_window"]["value"] > 0
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_fault_under_the_timed_path_is_not_correct(base, fault):
    res = _run(base, engine_cls=faults.FAULTS[fault])
    assert not res["correct"], res["checks"]


def test_the_entry_point_refuses_without_a_chip(tmp_path):
    """Off the chip: exit non-zero, no result line. Also in a directory
    that holds only the benchmark's files (no program to run)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    cell = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]
    args = ["--workload", cell["name"], "--seed", "1", "--seconds", "1"]
    p = subprocess.run([sys.executable, str(CHIP / "run.py"), *args],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "refused" in p.stderr
    alone = tmp_path / "alone"
    shutil.copytree(CHIP, alone / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", alone / "BENCHMARK.json")
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py", *args],
                       cwd=alone, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
