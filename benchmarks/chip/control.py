"""Readings for setting a cell's limits: the program's compared numbers and
the fp8 control's, on the same sampled prompts, for each of several seeds;
or, with ``--fault``, the numbers of the program with one planted fault
(``faults.py``).

    python3 benchmarks/chip/control.py --workload qwen1.5-0.5b.post_rec \\
        --seeds 11,12,13 --seconds 10 [--fault StalePrefix]

Prints one JSON line per seed: ``{"seed", "fault", "program": {...},
"correct", "control": {...}, "control_correct", "attempted", "failed"}``.
``correct`` and ``control_correct`` are each verdict against the cell's
limits, as ``check.verdict`` and ``check.is_correct`` give it. All seeds
run in one process. The benchmark's own runs never compute the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as R


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    R.setup_paths(R.ROOT)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(R.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import check
    import faults
    import harness
    from registry import Registry
    limits = Registry.from_file(R.ROOT / "BENCHMARK.json").limits(
        args.workload)
    engine_cls = faults.FAULTS[args.fault] if args.fault else None
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run(R.ROOT, args.workload, seed, args.seconds, False,
                          time.perf_counter(), engine_cls=engine_cls,
                          with_control=engine_cls is None)
        out = {"seed": seed, "fault": args.fault,
               "program": res["readings"],
               "correct": res["correct"], "attempted": res["attempted"],
               "failed": res["failed"], "metrics": res["metrics"]}
        if "control" in res:
            out["control"] = res["control"]
            out["control_correct"] = check.is_correct(check.verdict(
                res["control"], limits, res["checks"]["checked"]["value"],
                0))
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
