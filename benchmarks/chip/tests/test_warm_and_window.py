"""Set-up's sweep of cache-hit shapes, and the window that waits for all it
sent: every shape a window request can meet is warmed once, with a prompt
that reuses exactly that much of its group's prefix; a request that returns
after the window's time is up still counts, over the time it took."""
import time

import pytest

import chipbench_tiny as tiny
import harness
from registry import Registry
from served import Sent
from workload import Mix, dist_bounds, ladder_bucket

LADDER = (64, 128, 256, 512, 1024, 2048)
POST_REC = dict(tiny.REC, groups={"count": 20, "prefix_tokens": {
    "kind": "normal", "mean": 14000, "std": 3000, "min": 11000,
    "max": 17000}}, body_tokens={"kind": "uniform", "min": 120, "max": 180})


def _every_shape(mix, step, ladder):
    """Brute force: each group, each reuse length, each body length."""
    lo, hi = dist_bounds(mix.spec["body_tokens"])
    return {(p, ladder_bucket(len(pre) - p + body, ladder))
            for pre in mix.prefixes
            for p in range(step, len(pre) + 1, step)
            for body in range(lo, hi + 1)}


@pytest.mark.parametrize("spec,step,ladder", [
    (POST_REC, 1024, LADDER),
    (tiny.REC, 64, LADDER),
    (dict(tiny.REC, body_tokens={"kind": "uniform", "min": 1, "max": 300}),
     64, (16, 32, 64)),
])
def test_the_sweep_covers_every_cache_hit_shape_once(spec, step, ladder):
    mix = Mix(spec, 151936, 2 ** 33 + 5)
    shapes = mix.reuse_shapes(step, ladder)
    listed = [(p, ladder_bucket(s, ladder)) for mine in shapes
              for p, s in mine]
    assert len(listed) == len(set(listed))
    assert set(listed) == _every_shape(mix, step, ladder)
    lo, hi = dist_bounds(spec["body_tokens"])
    for pre, mine in zip(mix.prefixes, shapes):
        assert [p for p, _ in mine] == sorted((p for p, _ in mine),
                                              reverse=True)
        for p, s in mine:
            assert len(pre) - p + lo <= s <= len(pre) - p + hi


def test_the_sweeps_prompts_reuse_exactly_their_share_of_a_prefix():
    mix = Mix(POST_REC, 151936, 7)
    shapes = mix.reuse_shapes(1024, LADDER)
    prompts = mix.warm_prompts(1024, LADDER)
    assert len(prompts) == mix.n_groups + sum(map(len, shapes))
    it = iter(prompts)
    for g, mine in enumerate(shapes):
        first = next(it)
        assert first.group == g
        assert first.tokens[:len(mix.prefixes[g])] == mix.prefixes[g]
        for p, s in mine:
            q = next(it)
            assert q.group == g and len(q.tokens) == p + s
            assert q.tokens[:p] == mix.prefixes[g][:p]
            assert q.tokens[p:p + 16] != mix.prefixes[g][p:p + 16]


def test_a_request_answered_after_the_windows_time_counts_over_its_time():
    t0 = 100.0
    sent = [Sent([1] * 10, 10, sent=100.5, done=103.0, result={}),
            Sent([1] * 30, 30, sent=101.0, done=112.0, result={}),
            Sent([1] * 50, 50, sent=109.0)]
    # the window's time was up at 110; its last answer came at 112
    assert harness.end_to_end("scored_rps", sent, t0, 112.0, 1.0) == 2 / 12
    assert harness.end_to_end("prompt_tokens_per_s", sent, t0, 112.0,
                              1.0) == 40 / 12


def test_a_cell_that_warms_its_hit_shapes_runs_correct(tmp_path):
    base = tiny.layout(tmp_path)
    lines = []
    res = harness.run(base, tiny.CELL, 2 ** 32 + 3, 1.0, False,
                      time.perf_counter(), require_chip=False,
                      registry=Registry(tiny.bench(), base),
                      log=lambda *a: lines.append(" ".join(map(str, a))))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    mix = Mix(tiny.REC, tiny.MODEL["vocab_size"], 1)
    n = sum(map(len, mix.reuse_shapes(64, LADDER)))
    assert n > tiny.REC["groups"]["count"]
    assert any(ln == f"set-up: {n + tiny.REC['groups']['count']} requests "
               "one at a time" for ln in lines)
    assert res["metrics"]["scored_rps"]["value"] > 0


def test_a_traced_run_sends_for_at_most_the_traced_windows_seconds(
        tmp_path, monkeypatch):
    base = tiny.layout(tmp_path)
    monkeypatch.setattr(harness, "TRACE_SECONDS", 0.5)
    lines = []
    t = time.perf_counter()
    res = harness.run(base, tiny.CELL, 2 ** 32 + 5, 600.0, True, t,
                      require_chip=False, registry=Registry(tiny.bench(),
                                                            base),
                      log=lambda *a: lines.append(" ".join(map(str, a))))
    assert res["correct"], res["checks"]
    assert any(ln.startswith("window 0.5s:") for ln in lines)
    assert time.perf_counter() - t < 300
