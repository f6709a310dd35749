"""Sharding policy: logical-axis constraints + parameter PartitionSpecs.

Model code calls ``constrain(x, "batch", None, "model")`` with *logical* axis
names; outside a mesh context this is the identity, inside one it resolves

    "batch" → every present data-parallel mesh axis ("pod", "data")
    "model" → the tensor-parallel mesh axis
    None    → replicated

and silently drops any axis that does not divide the dimension — the policy
degrades to replication rather than failing to compile (the divisibility
fallbacks of DESIGN.md §5).

``param_specs`` assigns PartitionSpecs to every parameter leaf by name:
column-parallel projections shard their output features over "model",
row-parallel ones their input features; MoE experts shard over "model" (EP)
when the expert count divides it, otherwise per-expert tensor-parallel; with
``cfg.fsdp`` large weights are additionally sharded over "data" (FSDP-style —
XLA inserts the per-layer all-gathers).
"""

from __future__ import annotations

from contextlib import contextmanager

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_ACTIVE: list = []          # stack of (mesh, options) contexts

BATCH_AXES = ("pod", "data")
MODEL_AXIS = "model"


@contextmanager
def activate(mesh: Mesh):
    """Enable sharding constraints for model code under this mesh."""
    _ACTIVE.append(mesh)
    try:
        yield
    finally:
        _ACTIVE.pop()


def active_mesh() -> Mesh | None:
    return _ACTIVE[-1] if _ACTIVE else None


def _axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def _resolve(elem, mesh):
    """Map a logical spec element to mesh axes present in `mesh`."""
    if elem is None:
        return None
    if elem == "batch":
        present = tuple(a for a in BATCH_AXES
                        if a in mesh.axis_names and mesh.shape[a] > 1)
        return present if present else None
    if isinstance(elem, tuple):
        present = tuple(a for a in elem
                        if a in mesh.axis_names and mesh.shape[a] > 1)
        return present if present else None
    return elem if (elem in mesh.axis_names and mesh.shape[elem] > 1) else None


def resolve_spec(spec, shape, mesh) -> P:
    """Logical spec → PartitionSpec with divisibility fallback."""
    if len(spec) < len(shape):
        spec = (None,) * (len(shape) - len(spec)) + tuple(spec)
    elems = []
    for dim, elem in zip(shape, spec):
        r = _resolve(elem, mesh)
        if r is not None and dim % _axis_size(mesh, r) != 0:
            r = None
        elems.append(r)
    return P(*elems)


def constrain(x, *spec):
    """with_sharding_constraint under the active mesh; identity otherwise."""
    mesh = active_mesh()
    if mesh is None:
        return x
    ps = resolve_spec(spec, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, ps))


# --------------------------------------------------------------------------
# parameter partitioning policy
# --------------------------------------------------------------------------

# base (right-aligned) logical specs per parameter leaf name
_COL = (None, "model")        # output features sharded
_ROW = ("model", None)        # input features sharded
_PARAM_SPECS: dict[str, tuple] = {
    # attention
    "wq": _COL, "wk": _COL, "wv": _COL, "wo": _ROW,
    "bq": ("model",), "bk": ("model",), "bv": ("model",),
    # MLA
    "wdq": _COL, "wuq": _COL, "wdkv": (None, None), "wkr": (None, None),
    "wuk": _COL, "wuv": _COL,
    "q_norm": (None,), "kv_norm": (None,),
    # MLP
    "w1": _COL, "w3": _COL, "w2": _ROW,
    "b1": ("model",), "b2": (None,),
    # embeddings / head
    "embed": ("model", None), "lm_head": (None, "model"),
    "patch_proj": (None, None),
    # router / norms / scalars
    "router": (None, None),
    "scale": (None,), "bias": (None,),
    # SSM
    "in_proj": _COL, "out_proj": _ROW,
    "conv_w": (None, None), "conv_b": (None,),
    "A_log": (None,), "D": (None,), "dt_bias": (None,),
    "ssm_norm": (None,),
}

# MoE expert tensors: (E, D, F) / (E, F, D)
_MOE_SPECS = {
    "w1": ("model", None, None), "w3": ("model", None, None),
    "w2": ("model", None, None),
}
_MOE_TP_SPECS = {   # when E doesn't divide the model axis: per-expert TP
    "w1": (None, None, "model"), "w3": (None, None, "model"),
    "w2": (None, "model", None),
}

_FSDP_LEAVES = {"w1", "w2", "w3", "wq", "wk", "wv", "wo", "embed", "lm_head",
                "in_proj", "out_proj", "wuq", "wuk", "wuv"}


def _leaf_name(path) -> str:
    last = path[-1]
    return getattr(last, "key", getattr(last, "name", str(last)))


def _in_experts(path) -> bool:
    return any(getattr(p, "key", None) in ("experts", "moe") for p in path)


def spec_for_param(path, shape, cfg, mesh) -> P:
    name = _leaf_name(path)
    if _in_experts(path):
        model_size = mesh.shape.get(MODEL_AXIS, 1)
        table = (_MOE_SPECS if cfg.n_experts % max(model_size, 1) == 0
                 else _MOE_TP_SPECS)
        base = table.get(name, (None,) * len(shape))
    else:
        base = _PARAM_SPECS.get(name, (None,) * len(shape))

    if len(base) < len(shape):
        base = (None,) * (len(shape) - len(base)) + tuple(base)

    # FSDP: shard one replicated dim of big weights over 'data'
    if getattr(cfg, "fsdp", False) and name in _FSDP_LEAVES:
        data_size = mesh.shape.get("data", 1)
        base = list(base)
        for i in range(len(base) - 1, -1, -1):
            if base[i] is None and shape[i] % max(data_size, 1) == 0 \
                    and shape[i] >= data_size and data_size > 1:
                base[i] = "data"
                break
        base = tuple(base)
    return resolve_spec(base, shape, mesh)


def param_shardings(params_shape, cfg, mesh):
    """NamedSharding pytree matching a params (shape-)pytree."""
    def f(path, leaf):
        return NamedSharding(mesh, spec_for_param(path, leaf.shape, cfg, mesh))
    return jax.tree_util.tree_map_with_path(f, params_shape)


# cache leaves: name → base logical spec (right-aligned)
_CACHE_SPECS = {
    "k": ("batch", None, "model", None),       # (B,W,K,hd): KV heads on model
    "v": ("batch", None, "model", None),
    "k_scale": ("batch", None, "model"),       # (B,W,K) int8-KV scales
    "v_scale": ("batch", None, "model"),
    "ckv": ("batch", None, None),              # (B,W,r)
    "krope": ("batch", None, None),
    "state": ("batch", "model", None, None),   # (B,H,P,N)
    "conv": ("batch", None, None),             # (B,kconv-1,convdim)
    "pos": (None,), "t": (), "enc": ("batch", None, None),
}

# sequence-parallel variant (cfg.seq_parallel_kv): the cache *window* dim is
# sharded over the model axis → decode attention reduces over a sharded axis
# with small partial-softmax combines instead of full-cache all-gathers
_CACHE_SPECS_SEQPAR = {
    "k": ("batch", "model", None, None),
    "v": ("batch", "model", None, None),
    "ckv": ("batch", "model", None),
    "krope": ("batch", "model", None),
    "k_scale": ("batch", "model", None),
    "v_scale": ("batch", "model", None),
    "pos": ("model",),
}


def cache_shardings(cache_shape, cfg, mesh):
    seqpar = getattr(cfg, "seq_parallel_kv", False)

    def f(path, leaf):
        name = _leaf_name(path)
        base = None
        if seqpar:
            base = _CACHE_SPECS_SEQPAR.get(name)
        if base is None:
            base = _CACHE_SPECS.get(name, (None,) * len(leaf.shape))
        return NamedSharding(mesh, resolve_spec(base, leaf.shape, mesh))
    return jax.tree_util.tree_map_with_path(f, cache_shape)


def data_sharding(shape, mesh, batch_dim: int = 0):
    spec = [None] * len(shape)
    spec[batch_dim] = "batch"
    return NamedSharding(mesh, resolve_spec(tuple(spec), shape, mesh))
