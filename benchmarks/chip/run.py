"""Run one cell of the on-chip benchmark once and print its result.

    python3 benchmarks/chip/run.py --workload qwen1.5-0.5b.post_rec \\
        --seed 7 --seconds 30 --trace 0

Run from the root of a checkout. The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number compared, beside its limit (also the last lines of
standard error). Exits non-zero, printing no result, when JAX finds no TPU
or fewer chips than the cell asks for.

JAX's persistent compilation cache lives at ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def setup_paths(root: Path) -> None:
    """The checkout's compile cache and import paths (the program under
    ``src``, the benchmark's modules beside this file)."""
    (root / ".jax_cache").mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    for p in (str(root / "src"), str(HERE)):
        if p not in sys.path:
            sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    setup_paths(ROOT)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import harness
    try:
        result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                             bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    print(json.dumps(_strict(result)))
    return 0


def _strict(x):
    """The result with each non-finite number (a reading of a request
    that gave no score) as the string "inf", so the line is strict JSON."""
    if isinstance(x, dict):
        return {k: _strict(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_strict(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return "inf" if x > 0 else "-inf"
    return x


if __name__ == "__main__":
    sys.exit(main())
