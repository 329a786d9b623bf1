"""Unit tests of the benchmark's yardstick: operation counts, traffic,
weights, the reference, the comparison and the trace reduction."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import check
import flops
import reference
import trace_reduce
import weights as W
from chipbench_tiny import CONFIG, MODEL, REC
from workload import Mix, quantiles

DATA = Path(__file__).resolve().parent / "data"


def _brute_flops(m, computed, cached):
    D, F, V, L = m["d_model"], m["d_ff"], m["vocab_size"], m["num_layers"]
    H, KV, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    mats = [(D, H * hd), (D, KV * hd), (D, KV * hd), (H * hd, D),
            (D, F), (D, F), (F, D)]
    total = 0
    for _ in range(L):
        for t in range(computed):
            total += sum(2 * a * b for a, b in mats)
            keys = cached + t + 1
            for _ in range(H):
                total += 2 * hd * keys + 2 * hd * keys   # scores, values
    return total + 2 * D * V


@pytest.mark.parametrize("computed,cached", [(1, 0), (7, 0), (5, 33)])
def test_flops_match_a_brute_force_count(computed, cached):
    assert flops.request_flops(MODEL, computed, cached) == _brute_flops(
        MODEL, computed, cached)


def test_every_seed_gets_the_same_work():
    a, b = Mix(REC, 512, 1), Mix(REC, 512, 2 ** 33 + 5)
    sa = [a.next_prompt() for _ in range(40)]
    sb = [b.next_prompt() for _ in range(40)]
    assert [(p.group, len(p.tokens)) for p in sa] == [
        (p.group, len(p.tokens)) for p in sb]
    assert sa[0].tokens != sb[0].tokens and a.labels != b.labels
    again = Mix(REC, 512, 1)
    again = [again.next_prompt() for _ in range(40)]
    assert [p.tokens for p in again] == [p.tokens for p in sa]
    assert all(0 <= t < 512 for p in sa for t in p.tokens)


def test_quantiles_follow_the_distribution():
    q = quantiles({"kind": "lognormal", "median": 160, "sigma": 0.8,
                   "min": 16, "max": 1000}, 101)
    assert q[50] == 160 and min(q) >= 16 and max(q) <= 1000
    assert q == sorted(q)


def test_one_layer_alone_has_the_whole_models_values():
    ws = CONFIG["weights"]
    whole = W.make_all(MODEL, ws, 12345, "bfloat16")
    key = W.seed_key(12345)
    make = jax.jit(lambda k, l: W.make_layer(MODEL, ws, k, l, jnp.bfloat16))
    for layer in range(MODEL["num_layers"]):
        one = make(key, layer)
        for name, arr in one.items():
            np.testing.assert_array_equal(np.asarray(whole[name][layer]),
                                          np.asarray(arr))


def test_reference_matches_the_program_forward_in_float32():
    """At float32 the program's prefill and the reference compute the same
    model from the same weights."""
    import dataclasses
    from repro.configs.base import ModelConfig
    from repro.models import transformer as tfm
    seed, labels = 7, [3, 99, 400]
    mcfg = dataclasses.replace(ModelConfig(**MODEL), dtype="float32",
                               param_dtype="float32")
    w = W.make_all(MODEL, CONFIG["weights"], seed, "bfloat16")
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    W.to_program_tree(w, True))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, n).tolist() for n in (37, 130)]
    ref = reference.label_logits(CONFIG, seed, prompts, labels)
    for p, r in zip(prompts, ref):
        with jax.default_matmul_precision("highest"):
            logits, _ = tfm.prefill(params, mcfg, {"tokens": jnp.asarray([p])})
        np.testing.assert_allclose(np.asarray(logits[0])[labels], r,
                                   rtol=0, atol=2e-3)


def test_fp8_control_departs_from_the_reference():
    prompts = [np.random.default_rng(1).integers(0, 512, 90).tolist()]
    ref = reference.label_logits(CONFIG, 3, prompts, [5, 6])
    ctl = reference.label_logits(CONFIG, 3, prompts, [5, 6], quant="fp8")
    assert check.control_numbers(ctl[0], ref[0])["logodds_err"] > 1e-3


def test_comparison_numbers():
    ref = np.array([1.0, 3.0])
    p = np.exp(ref - ref.max())
    p /= p.sum()
    good = check.served_numbers({5: p[0], 6: p[1]}, 6, [5, 6], ref)
    assert good["logodds_err"] < 1e-9 and good["label_gap"] == 0.0
    bad = check.served_numbers({5: p[1], 6: p[0]}, 5, [5, 6], ref)
    assert bad["label_gap"] == 2.0 and bad["logodds_err"] == pytest.approx(2)
    v = check.verdict(check.summary([good]), {"logodds_err": 0.1,
                                             "label_gap": 0.1}, 1, 0)
    assert check.is_correct(v)
    assert not check.is_correct(check.verdict(
        check.summary([good, bad]), {"logodds_err": 0.1, "label_gap": 0.1},
        2, 0))
    assert check.summary([good, bad])["logodds_rms"] == pytest.approx(
        2 ** 0.5)
    assert not check.is_correct(check.verdict(
        check.summary([good, bad]), {"logodds_rms": 1.0}, 2, 0))


def _plane(name, lines):
    return {"name": name, "lines": [{"name": n, "events": ev}
                                    for n, ev in lines]}


def test_trace_reduction_on_a_synthetic_trace():
    planes = [
        _plane("/host:CPU", [
            ("main", [("bench_window", 0, 100)]),
            ("engine", [("bench_step", 10, 30), ("gather", 20, 8),
                        ("bench_step", 60, 30)])]),
        _plane("/device:TPU:0", [
            ("XLA Ops", [("fusion.1", 15, 5), ("fusion.2", 30, 10),
                         ("fusion.1", 65, 5)]),
            ("XLA Modules", [("jit_fn", 15, 25)])]),
    ]
    r = trace_reduce.reduce(planes)
    ns = 1e-9
    assert r["window_s"] == pytest.approx(100 * ns)
    assert r["busy_s"] == pytest.approx(20 * ns)
    assert r["step_s"] == pytest.approx(60 * ns)
    assert r["busy_in_steps_s"] == pytest.approx(20 * ns)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(10 * ns)]
    gaps = dict((n, d) for n, d in r["idle_gaps"])
    # idle 0-15 and 40-65 (no span), 20-30 (gather open), 70-100 (a step)
    assert gaps == {"(no host span)": pytest.approx(40 * ns),
                    "gather": pytest.approx(10 * ns),
                    "bench_step": pytest.approx(30 * ns)}
    assert trace_reduce.reduce(planes[:1]) is None


@pytest.mark.skipif(not (DATA / "v5e_steps.xplane.pb").is_file(),
                    reason="no recorded chip trace")
def test_trace_reduction_on_a_recorded_chip_trace():
    """A trace recorded on a v5e: three bench_step spans of two matmul
    programs each inside one bench_window span."""
    r = trace_reduce.reduce(trace_reduce.load(
        str(DATA / "v5e_steps.xplane.pb")))
    # six runs of one 180.8-us program, all launched inside the steps: once
    # the device clock is moved onto the host's, all of it lies in them
    assert r["busy_s"] == pytest.approx(6 * 180.85e-6, rel=1e-3)
    assert r["busy_in_steps_s"] == pytest.approx(r["busy_s"])
    assert r["step_s"] < r["window_s"]
    assert 1.2e-3 < r["clock_offset_s"][0] < 2.5e-3
    assert sum(d for _, d in r["device_ops"]) == pytest.approx(r["busy_s"])
