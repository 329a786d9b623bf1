"""Architectures as files and replicas per chip, on the CPU at a tiny size.

The dense module gives bit for bit what the harness computed before it took
an architecture from the configuration; a configuration naming a missing
module fails before any work; an architecture written as a new file, with a
leaf the dense layout lacks (an untied LM head), runs correct; a cell that
asks for two chips runs two replicas, both of which score requests, and a
fault in one of them is caught. Also the metrics read from the merged
trace partition, compiles counted by thread, and the check's sample over
replicas."""
import json
import threading
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny as tiny
import check
import faults
import flops
import harness
import phases
import reference
import trace_reduce
import weights as W
from compile_clock import CompileClock
from registry import Registry
from served import TracedEngine

CHIP = Path(__file__).resolve().parents[1]
QWEN = json.loads((CHIP / "configs" / "qwen1.5-0.5b.json").read_text())
PHASE_METRICS = ("idle_in_program.offline", "idle_in_kv_gather.offline",
                 "idle_in_kv_insert.offline", "idle_in_dispatch.offline",
                 "programs_per_step.offline")

UNTIED = '''"""The dense block with an untied LM head, a leaf the dense layout
lacks: ``lm_head`` (d_model, vocab), drawn from its own key."""
import math

import jax
import jax.numpy as jnp
import numpy as np

import flops
import reference as R
import weights as W

request_flops = flops.request_flops


def _head(m, seed, dtype):
    key = jax.random.fold_in(W.seed_key(seed), 1000)
    D, V = m["d_model"], m["vocab_size"]
    return (jax.random.normal(key, (D, V), jnp.float32)
            / math.sqrt(D)).astype(dtype)


def make_params(m, w, seed, dtype):
    tree = W.to_program_tree(W.make_all(m, w, seed, dtype),
                             bool(m.get("qkv_bias")))
    return dict(tree, lm_head=_head(m, seed, dtype))


def label_logits(cfg, seed, prompts, labels, quant=None):
    m, ws = cfg["model"], cfg["weights"]
    D, H, KV, hd, _, _, L = W.dims(m)
    dtype = jnp.dtype(m["param_dtype"])
    eps, q = float(cfg["rms_norm_eps"]), quant == "fp8"
    key = W.seed_key(seed)
    f32 = lambda t: {k: v.astype(jnp.float32) for k, v in t.items()}
    glob = f32(W.make_globals(m, ws, key, dtype))
    xs = []
    for p in prompts:
        toks = np.zeros((R.padded_len(len(p)),), np.int32)
        toks[:len(p)] = p
        xs.append(glob["embed"][jnp.asarray(toks)])
    for layer in range(L):
        lw = f32(W.make_layer(m, ws, key, layer, dtype))
        xs = [R._block_fn(x.shape[0], D, H, KV, hd, float(m["rope_theta"]),
                          eps, bool(m.get("qkv_bias")), q, 512)(lw, x)
              for x in xs]
    head = _head(m, seed, dtype).astype(jnp.float32)[:, jnp.asarray(labels)]
    return np.stack([np.asarray(R._mm(R._rms(x[len(p) - 1],
                                             glob["final_norm"], eps)[None],
                                      head, q)[0], np.float64)
                     for p, x in zip(prompts, xs)])
'''


def _dense():
    return Registry({}, CHIP).arch("dense")


def _run(base, bench, cell=tiny.CELL, seed=2 ** 32 + 11, seconds=1.0,
         engine_cls=None, log=None):
    return harness.run(base, cell, seed, seconds, False, time.perf_counter(),
                       require_chip=False, registry=Registry(bench, base),
                       engine_cls=engine_cls, log=log or (lambda *a: None))


@pytest.mark.parametrize("qkv_bias", [True, False])
def test_dense_params_are_the_weights_modules_bit_for_bit(qkv_bias):
    m = dict(tiny.MODEL, qkv_bias=qkv_bias)
    ws = tiny.CONFIG["weights"]
    got = _dense().make_params(m, ws, 2 ** 33 + 3, "bfloat16")
    want = W.to_program_tree(W.make_all(m, ws, 2 ** 33 + 3, "bfloat16"),
                             qkv_bias)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("quant", [None, "fp8"])
def test_dense_reference_is_the_reference_modules(quant):
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 512, n).tolist() for n in (40, 150)]
    got = _dense().label_logits(tiny.CONFIG, 9, prompts, [3, 77],
                                quant=quant)
    want = reference.label_logits(tiny.CONFIG, 9, prompts, [3, 77],
                                  quant=quant)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("m", [tiny.MODEL, QWEN["model"]],
                         ids=["tiny", "qwen1.5-0.5b"])
def test_dense_flops_are_the_flops_modules(m):
    for c, p in [(1, 0), (150, 14336), (16384, 0), (177, 16207)]:
        assert _dense().request_flops(m, c, p) == flops.request_flops(m, c, p)


def test_a_missing_arch_fails_before_any_work(tmp_path):
    base = tiny.layout(tmp_path)
    (base / "configs" / "tiny.json").write_text(json.dumps(
        dict(tiny.CONFIG, arch="nosuch")))
    said = []
    with pytest.raises(FileNotFoundError) as e:
        _run(base, tiny.bench(), log=said.append)
    assert str(base / "arch" / "nosuch.py") in str(e.value)
    assert said == []


def test_an_arch_added_as_a_file_runs_correct(tmp_path):
    base = tiny.layout(tmp_path)
    (base / "arch" / "untied.py").write_text(UNTIED)
    (base / "configs" / "tiny_untied.json").write_text(json.dumps(
        dict(tiny.CONFIG, arch="untied",
             model=dict(tiny.MODEL, tie_embeddings=False))))
    (base / "limits" / "tiny.untied.json").write_text(
        json.dumps(tiny.LIMITS))
    bench = tiny.bench(cells=[("tiny.untied", "tiny_untied", "tiny_rec")])
    arch = Registry(bench, base).arch("untied")
    params = arch.make_params(tiny.MODEL, tiny.CONFIG["weights"], 5,
                              "bfloat16")
    assert params["lm_head"].shape == (64, 512)
    res = _run(base, bench, cell="tiny.untied")
    assert res["correct"], res["checks"]
    assert res["checks"]["checked"]["value"] > 0


@pytest.fixture(scope="module")
def base(tmp_path_factory):
    """The tiny cell; ``tiny.warm``: six users and six callers after a
    warm-up stream, so that the window's shapes are compiled before it
    opens (with the tiny cell's three users, set-up's first requests all
    hash to ``inst1``, whose cached profiles can then draw every request
    while a loaded CPU skews the replicas' fitted profiles); ``tiny.x2``:
    eight callers for each of two replicas, so that each packs its steps,
    and twelve requests checked, so that the sample holds packed rows."""
    base = tiny.layout(tmp_path_factory.mktemp("two"))
    (base / "traffic" / "tiny_warm.json").write_text(json.dumps(
        dict(tiny.REC, loop={"kind": "closed", "outstanding": 6},
             warm_requests=12, groups=dict(tiny.REC["groups"], count=6))))
    (base / "traffic" / "tiny_x2.json").write_text(json.dumps(
        dict(tiny.REC, loop={"kind": "closed", "outstanding": 16},
             check_requests=12)))
    for cell in ("tiny.warm", "tiny.x2"):
        (base / "limits" / f"{cell}.json").write_text(
            json.dumps(tiny.LIMITS))
    return base


def test_two_chips_run_two_replicas_that_both_score(base):
    bench = tiny.bench(cells=[("tiny.warm", "tiny", "tiny_warm")], chips=2)
    res = _run(base, bench, cell="tiny.warm", seconds=10.0)
    assert res["correct"], res["checks"]
    assert sorted(res["replicas"]) == ["inst0", "inst1"]
    assert all(r["scored"] > 0 for r in res["replicas"].values()), \
        res["replicas"]
    assert res["device"]["count"] == len(jax.devices()[:2])


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_fault_under_two_replicas_is_not_correct(base, fault):
    bench = tiny.bench(cells=[("tiny.x2", "tiny", "tiny_x2")], chips=2)
    res = _run(base, bench, cell="tiny.x2", seconds=3.0,
               engine_cls=faults.FAULTS[fault])
    assert not res["correct"], res["checks"]


def test_a_fault_in_one_replica_of_two_is_not_correct(base):
    """Only ``inst0`` alters its answers; ``inst1`` is sound."""
    made = []

    def engine(*a, **kw):
        cls = faults.AnswerAltered if not made else TracedEngine
        made.append(cls)
        return cls(*a, **kw)

    bench = tiny.bench(cells=[("tiny.warm", "tiny", "tiny_warm")], chips=2)
    res = _run(base, bench, cell="tiny.warm", seconds=10.0, engine_cls=engine)
    assert made == [faults.AnswerAltered, TracedEngine]
    assert all(r["scored"] > 0 for r in res["replicas"].values()), \
        res["replicas"]
    assert not res["correct"], res["checks"]


def test_the_sample_takes_the_longest_of_each_replica():
    recs = [{"n_input": n, "path": p, "instance": i}
            for n, p, i in [(900, "fresh", "inst0"), (800, "suffix", "inst0"),
                            (700, "fresh", "inst1"), (100, "suffix", "inst2"),
                            (500, "suffix", "inst1"), (300, "fresh", "inst0")]]
    got = check.sample(recs, 4, 7)
    assert [r["n_input"] for r in got[:4]] == [900, 800, 700, 100]
    # one replica: the same requests as a sample over step paths alone
    one = [dict(r, instance="inst0") for r in recs]
    assert check.sample(one, 4, 7) == check.sample(one, 4, 7, keys=("path",))


def test_compiles_are_counted_by_the_thread_that_compiled():
    clock = CompileClock()
    try:
        t0 = time.perf_counter()
        th = threading.Thread(
            target=lambda: jax.jit(lambda x: x * 3.0 + 0.25)(
                jnp.arange(13.0)).block_until_ready(),
            name="engine-inst7")
        th.start()
        th.join(timeout=120)
        assert not th.is_alive()
        t1 = time.perf_counter()
        by = clock.by_thread(t0, t1)
        assert by.get("engine-inst7", 0) >= 1
        assert sum(by.values()) == clock.between(t0, t1)[0]
    finally:
        clock.close()


def _plane(name, lines):
    return {"name": name, "lines": [{"name": n, "events": ev}
                                    for n, ev in lines]}


def test_phase_metrics_read_the_merged_partition():
    """Two steps of 80 ns: 10 ns idle inside a program, 5 under dispatch,
    8 under kv_gather, 20 under kv_insert; two programs."""
    planes = [
        _plane("/host:CPU", [
            ("main", [("bench_window", 0, 200)]),
            ("engine", [("bench_step", 10, 80), ("engine.step", 11, 78),
                        ("engine.kv_gather", 62, 8),
                        ("engine.dispatch", 70, 5),
                        ("bench_step", 110, 80), ("engine.step", 111, 78),
                        ("engine.kv_insert", 130, 20)])]),
        _plane("/device:TPU:0", [
            ("XLA Ops", [("fusion.1", 15, 15), ("fusion.2", 40, 20),
                         ("fusion.3", 120, 10)]),
            ("XLA Modules", [("jit_a", 15, 45), ("jit_b", 120, 10)])]),
    ]
    red = trace_reduce.reduce(planes)
    part = phases.reduce(planes)
    assert {k: red[k] for k in part} == part
    reg = Registry({}, CHIP)
    got = {n: reg.metric(n)(SimpleNamespace(trace=red))
           for n in PHASE_METRICS}
    assert got == {
        "idle_in_program.offline": pytest.approx(100 * 10 / 160),
        "idle_in_kv_gather.offline": pytest.approx(100 * 8 / 160),
        "idle_in_kv_insert.offline": pytest.approx(100 * 20 / 160),
        "idle_in_dispatch.offline": pytest.approx(100 * 5 / 160),
        "programs_per_step.offline": 1.0}
    # a trace with no engine spans (a program without them): nothing read
    bare = [_plane("/host:CPU", [("main", [("bench_window", 0, 200)]),
                                 ("engine", [("bench_step", 10, 80)])]),
            planes[1]]
    red = trace_reduce.reduce(bare)
    assert all(reg.metric(n)(SimpleNamespace(trace=red)) is None
               for n in PHASE_METRICS)
    assert all(reg.metric(n)(SimpleNamespace(trace=None)) is None
               for n in PHASE_METRICS)
