"""END-TO-END DRIVER: serve the paper's post-recommendation trace through
the async serving subsystem — a pool of PrefillOnly instances behind an
AsyncServer (real forwards, real prefix-KV reuse, Algorithm-1 scheduling,
JCT-aware routing, open-loop real-time arrivals).

    PYTHONPATH=src python examples/serve_trace.py [--qps 20] [--requests 40]
"""
import argparse

from repro.launch.serve import serve_trace
from repro.runtime.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--qps", type=float, default=20.0)
    ap.add_argument("--requests", type=int, default=40)
    ap.add_argument("--instances", type=int, default=2)
    ap.add_argument("--policy", default="srjf_calibrated")
    ap.add_argument("--router", default="least_backlog",
                    choices=["user_hash", "least_backlog"])
    args = ap.parse_args()
    enable_compile_cache()

    out = serve_trace("qwen1.5-0.5b", "post_recommendation", qps=args.qps,
                      n_instances=args.instances, policy=args.policy,
                      router=args.router,
                      scale_tokens=0.02, max_requests=args.requests)
    print("\n=== serve_trace results ===")
    for k, v in out.items():
        if k == "per_instance":
            for name, st in v.items():
                print(f"  {name}: hit_rate={st['hit_rate']:.2f} "
                      f"steps={st['steps']}")
        elif k == "metrics":
            print("--- telemetry ---")
            print(v)
        else:
            print(f"{k}: {v}")


if __name__ == "__main__":
    main()
