"""JCT models (paper §6.3) + the MIL/prefix-budget memory model (§3.1/§4)."""
import numpy as np
from hypothesis import given, strategies as st

from repro.configs import get_config
from repro.core.jct import (GridJCT, LinearProxyJCT, RooflineJCT, pearson,
                            tp_comm_bytes_per_token)
from repro.core.kv_policy import MemoryModel
from repro.runtime.hw import TPU_V5E


def test_linear_proxy_fit_recovers_slope():
    samples = [(n, c, 2e-4 * (n - c) + 0.01)
               for n in range(1000, 20000, 1000) for c in (0, n // 2)]
    m = LinearProxyJCT().fit(samples)
    assert abs(m.a - 2e-4) / 2e-4 < 1e-6
    assert m.pearson_r > 0.999


def test_proxy_pearson_on_roofline_samples():
    """The paper reports r=0.987 between JCT and miss tokens; our roofline
    JCT over the profiling grid correlates comparably."""
    cfg = get_config("llama3.1-8b")
    model = RooflineJCT(cfg)
    samples = model.samples(max_len=60_000, granularity=2_000)
    miss = [s[0] - s[1] for s in samples]
    t = [s[2] for s in samples]
    assert pearson(miss, t) > 0.97


def test_grid_jct_beats_proxy_on_quadratic_regime():
    cfg = get_config("llama3.1-8b")
    model = RooflineJCT(cfg)
    samples = model.samples(max_len=120_000, granularity=4_000)
    lin = LinearProxyJCT().fit(samples)
    grid = GridJCT().fit(samples)
    err_l = np.mean([abs(lin.predict(n, c) - t) for n, c, t in samples])
    err_g = np.mean([abs(grid.predict(n, c) - t) for n, c, t in samples])
    assert err_g <= err_l


@given(st.integers(1_000, 100_000), st.integers(0, 99_000))
def test_jct_monotonicity(n_input, n_cached):
    """More cache can never hurt; longer input can never be faster."""
    cfg = get_config("llama3.1-8b")
    model = RooflineJCT(cfg)
    n_cached = min(n_cached, n_input)
    t = model.predict(n_input, n_cached)
    assert t >= model.predict(n_input, min(n_input, n_cached + 1000)) - 1e-12
    assert model.predict(n_input + 1000, n_cached) >= t - 1e-12


def test_tp_comm_bytes_positive_and_scaling():
    cfg = get_config("llama3.1-8b")
    assert tp_comm_bytes_per_token(cfg, 1) == 0.0
    b2 = tp_comm_bytes_per_token(cfg, 2)
    b4 = tp_comm_bytes_per_token(cfg, 4)
    assert 0 < b2 < b4  # (k-1)/k grows with k


# ---- memory model / MIL (Table 2 + Fig 10 analog) --------------------------

def test_mil_ordering_matches_paper():
    """Table 2's qualitative ordering on a single accelerator:
    paged < discard-only < chunked < hybrid; TP-2 > paged."""
    cfg = get_config("llama3.1-8b")
    mm = MemoryModel(cfg, TPU_V5E, weight_bytes_per_param=1.0)
    mil = mm.mil_table()
    assert mil["paged"] < mil["discard"]
    assert mil["paged"] < mil["chunked"]
    assert mil["chunked"] < mil["hybrid"]
    assert mil["hybrid"] > 2 * mil["paged"]      # ">= upto 5x" headline
    assert mil["tp"] > mil["paged"]


def test_discard_alone_is_marginal():
    """Paper §2.6: naive KV discard gives only ~1.6x (intermediates bound)."""
    cfg = get_config("llama3.1-8b")
    mm = MemoryModel(cfg, TPU_V5E, weight_bytes_per_param=1.0)
    mil = mm.mil_table()
    assert mil["discard"] / mil["paged"] < 2.5


def test_mlp_intermediates_dominate_one_layer_kv():
    """Fig 4: intermediate tensors ~14x one-layer KV on Llama-3.1-8B."""
    cfg = get_config("llama3.1-8b")
    mm = MemoryModel(cfg, TPU_V5E)
    ratio = mm.mlp_int_per_token / mm.kv_one_layer_per_token
    assert 10 < ratio < 20


def test_prefix_budget_positive_at_workload_mil():
    cfg = get_config("llama3.1-8b")
    mm = MemoryModel(cfg, TPU_V5E, weight_bytes_per_param=1.0)
    assert mm.prefix_budget_tokens(20_000) > 10_000


def test_hybrid_micro_optimizations_increase_mil():
    """§4.3 output-preallocation / in-place ablation (Fig 10 steps)."""
    cfg = get_config("llama3.1-8b")
    base = MemoryModel(cfg, TPU_V5E, weight_bytes_per_param=1.0,
                       output_prealloc=False, inplace=False)
    opt = MemoryModel(cfg, TPU_V5E, weight_bytes_per_param=1.0)
    assert opt.max_input_length("hybrid") >= base.max_input_length("hybrid")
    # chunked technique depends on the act coefficient too
    assert opt.peak_bytes(32_768, "paged") < base.peak_bytes(32_768, "paged")
