"""Logical-axis sharding (MaxText-style) + declarative parameter definitions.

Model code never names mesh axes directly. It tags tensors/params with
*logical* axes ("batch", "heads", "d_ff", ...) and a rule table maps those to
mesh axes per workload. With no active rules (CPU smoke tests) every
constraint is a no-op, so the same model code runs unsharded.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axes = Tuple[Optional[str], ...]
MeshAxes = Union[None, str, Tuple[str, ...]]


# --------------------------------------------------------------------------
# rule tables
# --------------------------------------------------------------------------

# Baseline rules. "batch" spans the full data-parallel extent (pod x data when
# the pod axis exists; resolve() silently drops axes absent from the mesh).
DEFAULT_RULES: Dict[str, MeshAxes] = {
    "batch": ("pod", "data"),
    "seq": None,             # activations: sequence unsharded by default
    "attn_seq": None,        # attention q/k/v seq dim (never SP-sharded)
    "kv_seq": None,          # KV-cache sequence dim (context parallelism opt-in)
    "d_model": None,
    "heads": "model",        # attention head dim of activations / weights
    "kv_heads": "model",     # dropped automatically when not divisible
    "head_dim": None,
    "qkv": "model",          # fused q/k/v output dim of weight matrices
    "d_ff": "model",
    "vocab": "model",
    "experts": None,         # None = TP-within-expert; "model" = EP
    "expert_cap": None,
    "ssm_inner": "model",
    "ssm_heads": "model",
    "state": None,
    "conv": None,
    "layers": None,          # stacked-layer leading dim: never sharded
    "shards": ("pod", "data"),  # explicit device-local token grouping (MoE)
}


def make_rules(**overrides: MeshAxes) -> Dict[str, MeshAxes]:
    rules = dict(DEFAULT_RULES)
    rules.update(overrides)
    return rules


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: Optional[Dict[str, MeshAxes]] = None


_CTX = _Ctx()


@contextlib.contextmanager
def use_sharding(mesh: Optional[Mesh], rules: Optional[Dict[str, MeshAxes]] = None):
    """Activate a mesh + logical rule table for model code in this thread."""
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh = mesh
    _CTX.rules = dict(rules or DEFAULT_RULES)
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def active_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def _axis_size(mesh: Mesh, names: Tuple[str, ...]) -> int:
    size = 1
    for n in names:
        size *= mesh.shape[n]
    return size


def resolve_spec(axes: Axes, shape: Optional[Sequence[int]] = None,
                 mesh: Optional[Mesh] = None,
                 rules: Optional[Dict[str, MeshAxes]] = None) -> P:
    """Logical axes -> PartitionSpec under the active (or given) rules.

    Drops any mesh axis that (a) is absent from the mesh, (b) does not divide
    the corresponding dim (when ``shape`` is given), or (c) already appears in
    an earlier dim of this spec (a mesh axis may shard at most one dim).
    """
    mesh = mesh or _CTX.mesh
    rules = rules or _CTX.rules or DEFAULT_RULES
    used: set = set()
    out = []
    for i, name in enumerate(axes):
        entry: MeshAxes = rules.get(name) if name else None
        if entry is None:
            out.append(None)
            continue
        names = (entry,) if isinstance(entry, str) else tuple(entry)
        names = tuple(n for n in names
                      if mesh is not None and n in mesh.shape and n not in used)
        if not names:
            out.append(None)
            continue
        if shape is not None and mesh is not None:
            if shape[i] % _axis_size(mesh, names) != 0:
                out.append(None)
                continue
        used.update(names)
        out.append(names[0] if len(names) == 1 else names)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def constrain(x: jax.Array, axes: Axes) -> jax.Array:
    """with_sharding_constraint via logical axes; no-op without active mesh."""
    mesh = _CTX.mesh
    if mesh is None:
        return x
    spec = resolve_spec(axes, shape=x.shape, mesh=mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_map(fn, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off: our collective
    schedules (psum of combined partials, all-gathered K/V) are hand-pinned
    and the checker rejects them."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# --------------------------------------------------------------------------
# declarative parameter definitions
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ParamDef:
    """Shape + logical axes + initializer for one parameter tensor."""

    shape: Tuple[int, ...]
    axes: Axes
    init: str = "normal"        # normal | zeros | ones | scaled
    scale: float = 0.02

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def pdef(shape: Sequence[int], axes: Sequence[Optional[str]],
         init: str = "normal", scale: float = 0.02) -> ParamDef:
    return ParamDef(tuple(int(s) for s in shape), tuple(axes), init, scale)


def is_paramdef_leaf(x: Any) -> bool:
    return isinstance(x, ParamDef)


def _tree_map_pdef(fn: Callable[[ParamDef], Any], tree: Any) -> Any:
    return jax.tree_util.tree_map(fn, tree, is_leaf=is_paramdef_leaf)


def materialize(rng: jax.Array, defs: Any, dtype: Any) -> Any:
    """Initialize real arrays from a ParamDef tree (smoke tests / examples)."""
    leaves, treedef = jax.tree_util.tree_flatten(defs, is_leaf=is_paramdef_leaf)
    keys = jax.random.split(rng, len(leaves))
    out = []
    for key, d in zip(keys, leaves):
        if d.init == "zeros":
            arr = jnp.zeros(d.shape, dtype)
        elif d.init == "ones":
            arr = jnp.ones(d.shape, dtype)
        elif d.init == "scaled":
            fan_in = d.shape[-2] if len(d.shape) >= 2 else d.shape[-1]
            arr = (jax.random.normal(key, d.shape, jnp.float32)
                   * (1.0 / np.sqrt(fan_in))).astype(dtype)
        else:
            arr = (jax.random.normal(key, d.shape, jnp.float32) * d.scale).astype(dtype)
        out.append(arr)
    return jax.tree_util.tree_unflatten(treedef, out)


def abstract_params(defs: Any, dtype: Any) -> Any:
    """ParamDef tree -> ShapeDtypeStruct tree (dry-run: zero allocation)."""
    return _tree_map_pdef(
        lambda d: jax.ShapeDtypeStruct(d.shape, jnp.dtype(dtype)), defs)


def param_shardings(defs: Any, mesh: Mesh,
                    rules: Optional[Dict[str, MeshAxes]] = None) -> Any:
    """ParamDef tree -> NamedSharding tree under the rule table."""
    return _tree_map_pdef(
        lambda d: NamedSharding(
            mesh, resolve_spec(d.axes, shape=d.shape, mesh=mesh, rules=rules)),
        defs)


def optimizer_shardings(defs: Any, mesh: Mesh,
                        rules: Optional[Dict[str, MeshAxes]] = None) -> Any:
    """ZeRO-1: master params + moments additionally sharded over the
    data-parallel axes. For each param we take its weight PartitionSpec and
    shard the first still-unsharded dim divisible by the DP extent; bf16
    compute weights are all-gathered once per step by XLA (driven by the
    sharding constraint in the train step)."""
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]

    def one(d: ParamDef):
        spec = list(resolve_spec(d.axes, shape=d.shape, mesh=mesh,
                                 rules=rules))
        spec += [None] * (len(d.shape) - len(spec))
        used = set()
        for s in spec:
            used.update((s,) if isinstance(s, str) else (s or ()))
        # FSDP rules may already shard a dim over dp — nothing to add then
        if dp > 1 and not used.intersection(dp_axes):
            for i in range(len(d.shape) - 1, -1, -1):  # prefer trailing dims
                if spec[i] is None and d.shape[i] % dp == 0:
                    spec[i] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
                    break
        return NamedSharding(mesh, P(*spec))

    return _tree_map_pdef(one, defs)


def param_count(defs: Any) -> int:
    leaves = jax.tree_util.tree_leaves(defs, is_leaf=is_paramdef_leaf)
    return int(sum(int(np.prod(d.shape)) for d in leaves))
