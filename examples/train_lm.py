"""Train a language model with the full fault-tolerant stack (ZeRO-1
shardings, microbatching, async checkpoints, NaN guard, resume).

Default: a reduced qwen on CPU for a quick demonstration. ``--full-size``
uses the real 0.5B config (~463M params — the "train a ~100M+ model" shape;
expect TPU-scale hardware for a few hundred steps).

    PYTHONPATH=src python examples/train_lm.py --steps 60
"""
import argparse

from repro.launch.train import train
from repro.runtime.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/prefillonly_train_ck")
    ap.add_argument("--full-size", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    losses = train(args.arch, steps=args.steps, seq_len=args.seq_len,
                   global_batch=args.global_batch,
                   reduced=not args.full_size, ckpt_dir=args.ckpt_dir,
                   ckpt_every=20, log_every=5)
    print(f"\ntrained {len(losses)} steps: "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    print(f"checkpoints in {args.ckpt_dir} (re-run to resume)")


if __name__ == "__main__":
    main()
