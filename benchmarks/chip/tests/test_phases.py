"""The partition of device idle inside engine steps by host phase
(``phases.py``): on synthetic planes, where every class can be counted by
hand, and on the trace recorded on a v5e, which has no ``engine.*`` span.
Also the reader of ``kv_insert_ms.fresh``, on records with and without
phases and through the harness on a tiny cell."""
import json
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

import chipbench_tiny as tiny
import harness
import phases
import trace_reduce
from registry import Registry
from repro.serving.tracing import BatchRecord

CHIP = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
NS = 1e-9
KV_INSERT = {"name": "kv_insert_ms.fresh", "unit": "ms", "better": "lower",
             "source": "program_span", "layer": "KV tiers",
             "moves": "prompt_tokens_per_s", "workloads": [tiny.CELL]}


def _plane(name, lines):
    return {"name": name, "lines": [{"name": n, "events": ev}
                                    for n, ev in lines]}


def _idle_in_steps(red):
    return red["step_s"] - red["busy_in_steps_s"]


def _parts(red):
    return (red["idle_in_program_s"] + sum(red["idle_in_phase_s"].values())
            + red["idle_outside_phases_s"])


def _synthetic():
    """Two steps, 10-90 and 110-190, in a window 0-200. Step one: a program
    15-60 whose two ops leave 30-40 uncovered, a kv_gather span 62-70 and a
    dispatch span 70-75; a second thread's longer span 60-80 overlaps both.
    Step two: one op 120-130 and a kv_insert span 130-150."""
    return [
        _plane("/host:CPU", [
            ("main", [("bench_window", 0, 200)]),
            ("engine", [("bench_step", 10, 80), ("engine.step", 11, 78),
                        ("engine.kv_gather", 62, 8),
                        ("engine.dispatch", 70, 5),
                        ("bench_step", 110, 80), ("engine.step", 111, 78),
                        ("engine.kv_insert", 130, 20)]),
            ("router", [("engine.score", 60, 20)])]),
        _plane("/device:TPU:0", [
            ("XLA Ops", [("fusion.1", 15, 15), ("fusion.2", 40, 20),
                         ("fusion.3", 120, 10)]),
            ("XLA Modules", [("jit_a", 15, 45), ("jit_b", 120, 10)])]),
    ]


def test_classes_partition_idle_in_steps_exactly():
    planes = _synthetic()
    red = trace_reduce.reduce(planes)
    got = phases.reduce(planes)
    # the uncovered op gap 30-40 lies inside program jit_a
    assert got["idle_in_program_s"] == pytest.approx(10 * NS)
    # 60-62 and 75-80: only the other thread's score span is open; 62-70
    # and 70-75: the shorter kv_gather and dispatch spans are innermost
    assert got["idle_in_phase_s"] == {
        "score": pytest.approx(7 * NS), "kv_gather": pytest.approx(8 * NS),
        "dispatch": pytest.approx(5 * NS),
        "kv_insert": pytest.approx(20 * NS)}
    # 10-15, 80-90, 110-120, 150-190: inside steps, no leaf span open
    assert got["idle_outside_phases_s"] == pytest.approx(65 * NS)
    assert _parts(got) == pytest.approx(_idle_in_steps(red), abs=1e-15)
    assert got["programs_in_window"] == 2 and got["engine_steps"] == 2
    red.update(got)
    sh = phases.shares(red)
    assert sum(v for k, v in sh.items() if k != "programs_per_step") == \
        pytest.approx(100.0 * (1 - red["busy_in_steps_s"] / red["step_s"]))
    assert sh["programs_per_step"] == 1.0


def test_device_clock_offset_applies_to_programs():
    """A device plane 5 ns behind the host's: moved onto the host clock,
    the program runs 10-45 and its ops 10-15 and 40-45, so its op gap 15-40
    is in-program and only 45-50 of the dispatch span 10-50 is the
    phase's."""
    planes = [
        _plane("/host:CPU", [
            ("main", [("bench_window", 0, 100),
                      (trace_reduce.DONE, 45, 1)]),
            ("engine", [("bench_step", 0, 100),
                        ("engine.dispatch", 10, 40)])]),
        _plane("/device:TPU:0", [
            ("XLA Ops", [("fusion.1", 5, 5), ("fusion.2", 35, 5)]),
            ("XLA Modules", [("jit_a", 5, 35)])]),
    ]
    red = trace_reduce.reduce(planes)
    assert red["clock_offset_s"] == [pytest.approx(5 * NS)]
    got = phases.reduce(planes)
    assert got["idle_in_program_s"] == pytest.approx(25 * NS)
    assert got["idle_in_phase_s"] == {"dispatch": pytest.approx(5 * NS)}
    assert got["idle_outside_phases_s"] == pytest.approx(60 * NS)
    assert _parts(got) == pytest.approx(_idle_in_steps(red), abs=1e-15)


def test_no_window_or_no_device_gives_nothing():
    planes = _synthetic()
    assert phases.reduce(planes[:1]) is None
    assert phases.reduce([planes[0] | {"lines": []}, planes[1]]) is None


@pytest.mark.skipif(not (DATA / "v5e_steps.xplane.pb").is_file(),
                    reason="no recorded chip trace")
def test_recorded_chip_trace_has_no_phase_idle(capsys):
    """The v5e trace predates the engine's spans: no phase idle, the
    partition still sums to idle in steps, and merging it leaves every key
    of ``trace_reduce.reduce`` as it was."""
    path = str(DATA / "v5e_steps.xplane.pb")
    planes = trace_reduce.load(path)
    red = trace_reduce.reduce(planes)
    got = phases.reduce(planes)
    assert got["idle_in_phase_s"] == {} and got["engine_steps"] == 0
    assert got["programs_in_window"] == 6
    assert _parts(got) == pytest.approx(_idle_in_steps(red), abs=1e-15)
    assert phases.main([path]) == 0
    merged = json.loads(capsys.readouterr().out)
    for k, v in red.items():
        assert merged[k] == pytest.approx(v) if isinstance(v, float) \
            else merged[k] == json.loads(json.dumps(v))
    assert "programs_per_step" not in merged["shares"]


def test_kv_insert_reader_averages_fresh_steps():
    read = Registry({}, CHIP).metric("kv_insert_ms.fresh")
    rec = [BatchRecord(step=0, ts=0.0, jit_path="fresh",
                       phases={"kv_insert": 0.002, "dispatch": 1.0}),
           BatchRecord(step=1, ts=0.0, jit_path="fresh",
                       phases={"kv_insert": 0.004}),
           BatchRecord(step=2, ts=0.0, jit_path="suffix",
                       phases={"kv_insert": 1.0})]
    assert read(SimpleNamespace(batches=rec)) == pytest.approx(3.0)
    # records of a program without phases: nothing to read
    old = [SimpleNamespace(jit_path="fresh", wall=0.1)]
    assert read(SimpleNamespace(batches=old)) is None
    assert read(SimpleNamespace(batches=rec[2:])) is None


def test_kv_insert_metric_in_a_traced_run(tmp_path):
    """One caller and no prefix cache, so that every step runs one request
    on the fresh path."""
    base = tiny.layout(tmp_path)
    (base / "traffic" / "tiny_rec.json").write_text(json.dumps(
        dict(tiny.REC, loop={"kind": "closed", "outstanding": 1})))
    (base / "configs" / "tiny.json").write_text(json.dumps(
        dict(tiny.CONFIG, cache_tokens=0)))
    reg = Registry(tiny.bench(per_layer=[KV_INSERT]), base)
    res = harness.run(base, tiny.CELL, 2 ** 31 + 7, 2.0, True,
                      time.perf_counter(), require_chip=False, registry=reg,
                      log=lambda *a: None)
    assert res["correct"], res["checks"]
    assert res["metrics"]["kv_insert_ms.fresh"]["value"] > 0
