"""The dense decoder block (RMSNorm, grouped-query attention with RoPE and
optional QKV bias, SwiGLU MLP, tied embedding), as ``weights.py``,
``reference.py`` and ``flops.py`` define it.

An architecture module gives the harness three things, and a configuration
names its module with ``"arch"``:

``make_params(m, w, seed, dtype)``
    the program's parameter tree from the seed, made on the default device
    (``m`` the configuration's model block, ``w`` its weights block)
``label_logits(cfg, seed, prompts, labels, quant=None)``
    the plain float32 reference's logits of ``labels`` at the last position
    of each prompt; ``quant="fp8"`` is the control
``request_flops(m, computed, cached)``
    the operations one scored request needs
"""
from __future__ import annotations

from typing import Dict

import flops
import reference
import weights as W

label_logits = reference.label_logits
request_flops = flops.request_flops


def make_params(m: Dict, w: Dict, seed: int, dtype) -> Dict:
    return W.to_program_tree(W.make_all(m, w, seed, dtype),
                             bool(m.get("qkv_bias")))

