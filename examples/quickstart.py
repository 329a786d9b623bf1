"""Quickstart: a prefill-only request through the PrefillOnly engine.

Builds a reduced qwen1.5-0.5b, submits the paper's recommendation-style
prompt shape ([user profile] + [post] -> Yes/No), and prints the constrained
single-token scores. Run:

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduce_config
from repro.core.engine import EngineConfig, PrefillOnlyEngine
from repro.models.model import build
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.sharding import materialize


def main():
    enable_compile_cache()
    cfg = reduce_config(get_config("qwen1.5-0.5b"))
    api = build(cfg)
    params = materialize(jax.random.PRNGKey(0), api.defs(), jnp.float32)

    engine = PrefillOnlyEngine(cfg, params, EngineConfig(
        policy="srjf_calibrated", lam=0.05, cache_capacity_tokens=4096))

    # the paper's profile run: fit the JCT model on this host
    r = engine.profile((64, 128))
    print(f"profile run: JCT ~ {engine.jct_model.a:.2e}s/token "
          f"(pearson {r:.3f})")

    rng = np.random.default_rng(0)
    YES, NO = 5, 9                      # stand-in token ids
    profile = rng.integers(0, cfg.vocab_size, 120).tolist()  # user profile

    # 3 posts for the same user — requests 2 and 3 hit the profile's prefix KV
    for post_id in range(3):
        post = rng.integers(0, cfg.vocab_size, 24).tolist()
        rid = engine.submit(profile + post, allowed_tokens=(YES, NO),
                            user_id="demo-user")
        engine.step()
        res = engine.results[rid]
        print(f"post {post_id}: P(yes)={res['scores'][YES]:.3f} "
              f"P(no)={res['scores'][NO]:.3f} "
              f"cached={res['n_cached']}/{res['n_input']} tokens "
              f"latency={res['latency']*1e3:.0f}ms")
    print("engine stats:", engine.stats())


if __name__ == "__main__":
    main()
