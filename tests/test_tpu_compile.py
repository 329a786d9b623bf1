"""Compiles for a described TPU v5e: the Pallas kernels at qwen1.5-0.5b widths
and the full-width fresh prefill step, through the chip's own compiler with
no chip attached. A compile that passes is not a chip run; what it catches is
what the chip's compiler refuses (illegal tilings, fast-memory overruns,
programs that do not fit), at no chip time.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import serving_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_mlp import fused_mlp
from repro.kernels.rmsnorm import rmsnorm
from repro.models import transformer as tfm
from repro.models.model import build
from repro.runtime.sharding import abstract_params

QWEN = serving_config("qwen1.5-0.5b", published_widths=True)
H, D_HEAD = QWEN.num_heads, QWEN.head_dim          # 16, 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                        # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("B", [1, 2])
@pytest.mark.parametrize("mode", ["causal", "segmented", "positioned"])
def test_flash_attention_compiles_at_qwen_widths(one_chip, mode, B):
    """Flash attention at H=16, d=64, S=1024, bf16. Segment and position ids
    ride as (B, 1, S) so the B=2 tiling is legal on Mosaic."""
    S = 1024
    q = _spec((B, H, S, D_HEAD), jnp.bfloat16, one_chip)
    ids = _spec((B, S), jnp.int32, one_chip)

    def fn(q, k, v, seg, pos):
        kw = {}
        if mode != "causal":
            kw.update(seg_q=seg, seg_k=seg)
        if mode == "positioned":
            kw.update(pos_q=pos, pos_k=pos)
        return flash_attention(q, k, v, block_q=256, block_k=256,
                               interpret=False, **kw)

    txt = _compile(fn, q, q, q, ids, ids).as_text()
    assert "tpu_custom_call" in txt or "custom-call" in txt


def test_positioned_kernel_compiles_for_tpu(one_chip):
    """The positioned (prefix-aware) and segmented kernels compile to a
    Mosaic TPU custom call: the f32 tile-skip reductions keep Mosaic's
    no-integer-reductions constraint satisfied."""
    q = _spec((1, 2, 256, 128), jnp.float32, one_chip)
    k = _spec((1, 1, 256, 128), jnp.float32, one_chip)
    ids = _spec((1, 256), jnp.int32, one_chip)

    def positioned(q, k, v, seg, pos):
        return flash_attention(q, k, v, seg_q=seg, seg_k=seg, pos_q=pos,
                               pos_k=pos, block_q=128, block_k=128,
                               interpret=False)

    def segmented(q, k, v, seg, pos):
        return flash_attention(q, k, v, seg_q=seg, seg_k=seg, block_q=128,
                               block_k=128, interpret=False)

    for fn in (positioned, segmented):
        lowered = jax.jit(fn).lower(q, k, k, ids, ids)
        assert "tpu_custom_call" in lowered.as_text()
        lowered.compile()


def test_fused_mlp_compiles_at_qwen_widths(one_chip):
    """SwiGLU MLP at d_model 1024; d_ff 2816 padded to the 512-wide f block
    (3072) as ``ops.fused_mlp`` pads it."""
    T, Dm, F = 1024, QWEN.d_model, 3072
    _compile(lambda x, wg, wu, wd: fused_mlp(x, wg, wu, wd, block_t=256,
                                             block_f=512, interpret=False),
             _spec((T, Dm), jnp.bfloat16, one_chip),
             _spec((Dm, F), jnp.bfloat16, one_chip),
             _spec((Dm, F), jnp.bfloat16, one_chip),
             _spec((F, Dm), jnp.bfloat16, one_chip))


def test_rmsnorm_compiles_at_qwen_widths(one_chip):
    _compile(lambda x, w: rmsnorm(x, w, block_t=256, interpret=False),
             _spec((1024, QWEN.d_model), jnp.bfloat16, one_chip),
             _spec((QWEN.d_model,), jnp.bfloat16, one_chip))


def test_fresh_prefill_step_compiles_at_published_widths(one_chip):
    """The engine's fresh step (``tfm.prefill`` with the prefix KV kept) for
    qwen1.5-0.5b at published widths, hybrid prefilling on, bf16 weights:
    it compiles for one v5e chip and its arguments fit in its HBM."""
    S = 512
    params = jax.tree_util.tree_map(
        lambda s: _spec(s.shape, s.dtype, one_chip),
        abstract_params(build(QWEN).defs(), jnp.bfloat16))
    compiled = _compile(
        lambda p, t, li: tfm.prefill(p, QWEN, {"tokens": t}, kv_keep=S,
                                     last_index=li),
        params, _spec((1, S), jnp.int32, one_chip),
        _spec((1,), jnp.int32, one_chip))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 16 * 2**30 // 8
