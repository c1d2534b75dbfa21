"""Logical-axis sharding rules (MaxText-style) + param Builder.

Every parameter is created through :class:`Builder`, which records a tuple of
*logical axis names* per array dimension alongside the initialized array.  At
jit time the logical names are resolved to mesh axes through a rules table,
with an automatic divisibility check: if a dimension is not divisible by the
mesh-axis size the sharding silently falls back to replication (e.g. gemma2's
8 query heads on a 16-way 'model' axis) — this keeps every arch lowerable on
the fixed production mesh while sharding everything that *can* be sharded.

FSDP (ZeRO-3 analogue of the paper's §2.4.1 sharded grads/optimizer states)
is expressed by mapping the ``embed``/``fsdp`` logical axes onto the 'data'
mesh axis.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (or tuple of mesh axes, or None = replicate).
# Mesh axes that do not exist in the current mesh are dropped at resolve time.
DEFAULT_RULES: Dict[str, Any] = {
    "batch": ("pod", "data"),
    "seq": None,
    "kv_seq": None,            # context-sharded over 'data' for long-decode
    "embed": None,
    "embed_fsdp": "data",      # param d_model dim under FSDP
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "experts": "model",
    "moe_mlp": None,
    "layers": None,
    "conv": None,
    "ssm_inner": "model",
    "ssm_state": None,
    "index_heads": None,
    "topk": None,
    "lora": None,
}


def make_rules(mesh: Mesh, *, fsdp: bool = True,
               context_parallel_kv: bool = False,
               overrides: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    rules = dict(DEFAULT_RULES)
    if not fsdp:
        rules["embed_fsdp"] = None
    if context_parallel_kv:
        rules["kv_seq"] = "data"
        rules["batch"] = "pod" if "pod" in mesh.axis_names else None
    if overrides:
        rules.update(overrides)
    return rules


def _mesh_axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        return math.prod(_mesh_axis_size(mesh, a) for a in axis)
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1)


def resolve_spec(logical_axes: Sequence[Optional[str]],
                 shape: Sequence[int],
                 rules: Dict[str, Any],
                 mesh: Mesh) -> P:
    """Logical axes + concrete shape -> PartitionSpec with divisibility guard."""
    used: set = set()
    parts = []
    for dim, name in zip(shape, logical_axes):
        axis = rules.get(name) if name else None
        # drop mesh axes that don't exist in this mesh
        if isinstance(axis, (tuple, list)):
            axis = tuple(a for a in axis if a in mesh.axis_names)
            axis = axis if axis else None
            if isinstance(axis, tuple) and len(axis) == 1:
                axis = axis[0]
        elif axis is not None and axis not in mesh.axis_names:
            axis = None
        # divisibility + single-use guards
        if axis is not None:
            size = _mesh_axis_size(mesh, axis)
            flat = tuple(axis) if isinstance(axis, tuple) else (axis,)
            if dim % size != 0 or any(a in used for a in flat):
                axis = None
            else:
                used.update(flat)
        parts.append(axis)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def tree_shardings(params: Any, specs: Any, rules: Dict[str, Any],
                   mesh: Mesh) -> Any:
    """Map a (params, logical-spec) tree pair to NamedShardings."""
    def one(leaf, axes):
        shape = leaf.shape if hasattr(leaf, "shape") else np.shape(leaf)
        return NamedSharding(mesh, resolve_spec(axes, shape, rules, mesh))
    return jax.tree.map(one, params, specs,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(e, (str, type(None))) for e in x))


def constrain(x: jax.Array, logical_axes: Sequence[Optional[str]],
              rules: Optional[Dict[str, Any]], mesh: Optional[Mesh]) -> jax.Array:
    """with_sharding_constraint via logical names; no-op without mesh/rules."""
    if mesh is None or rules is None or not _in_jit_with_mesh(mesh):
        return x
    spec = resolve_spec(logical_axes, x.shape, rules, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _in_jit_with_mesh(mesh: Mesh) -> bool:
    return mesh is not None and not mesh.empty


def constrain_batch_seq(x: jax.Array, mesh: Optional[Mesh]) -> jax.Array:
    """Megatron-SP analogue: residual stream sharded over BOTH batch
    ('pod','data') and sequence ('model') between blocks.  XLA then lowers
    the TP boundary as all-gather(seq) + reduce-scatter(seq) instead of two
    full all-reduces — ~half the wire bytes, and norms compute on 1/16 of
    the tokens per rank (beyond-paper optimization; see EXPERIMENTS §Perf).
    """
    if mesh is None or getattr(mesh, "empty", True) or x.ndim < 2:
        return x
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if not axes or "model" not in mesh.axis_names:
        return constrain_batch(x, mesh)
    bsz = math.prod(_mesh_axis_size(mesh, a) for a in axes)
    msz = _mesh_axis_size(mesh, "model")
    if x.shape[0] % bsz != 0 or x.shape[1] % msz != 0:
        return constrain_batch(x, mesh)
    spec = P(axes, "model", *([None] * (x.ndim - 2)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_batch(x: jax.Array, mesh: Optional[Mesh]) -> jax.Array:
    """Anchor an activation's leading batch dim to the ('pod','data') axes.

    Without this, XLA's sharding propagation can prefer the FSDP weight
    sharding and silently *replicate the batch* (observed: 32k-seq scan
    residuals materialized at global batch on every device).  Called at
    block boundaries; no-op when the batch isn't divisible (e.g. batch=1
    long-decode) or off-mesh."""
    if mesh is None or getattr(mesh, "empty", True):
        return x
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if not axes:
        return x
    size = math.prod(_mesh_axis_size(mesh, a) for a in axes)
    if x.ndim == 0 or x.shape[0] % size != 0 or x.shape[0] == 0:
        return x
    spec = P(axes, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Param builder
# ---------------------------------------------------------------------------

class Builder:
    """Collects params + logical axis specs during init.

    ``b.param('wq', (d, h*dh), ('embed_fsdp','heads'), scale=...)`` creates a
    normal-initialized array and records its logical axes.  ``b.sub('attn')``
    opens a nested dict.  ``build_*`` functions in layers/ take a Builder.
    """

    def __init__(self, key: jax.Array, dtype=jnp.float32,
                 abstract: bool = False):
        self._key = key
        self.dtype = dtype
        self.abstract = abstract
        self.params: Dict[str, Any] = {}
        self.specs: Dict[str, Any] = {}

    def _next_key(self) -> jax.Array:
        if self.abstract:
            return self._key
        self._key, k = jax.random.split(self._key)
        return k

    def sub(self, name: str) -> "Builder":
        child = Builder(self._next_key(), self.dtype, self.abstract)
        self.params[name] = child.params
        self.specs[name] = child.specs
        return child

    def param(self, name: str, shape: Tuple[int, ...],
              axes: Tuple[Optional[str], ...],
              init: str = "normal", scale: Optional[float] = None) -> jax.Array:
        assert len(shape) == len(axes), (name, shape, axes)
        if self.abstract:
            arr = jax.ShapeDtypeStruct(tuple(shape), self.dtype)
            self.params[name] = arr
            self.specs[name] = tuple(axes)
            return arr
        if init == "zeros":
            arr = jnp.zeros(shape, self.dtype)
        elif init == "ones":
            arr = jnp.ones(shape, self.dtype)
        elif init == "normal":
            if scale is None:
                fan_in = shape[0] if len(shape) >= 2 else max(shape[-1], 1)
                scale = 1.0 / math.sqrt(fan_in)
            # drawn in the target dtype: a bf16 model never has a float32
            # copy of a weight resident, even transiently
            arr = jax.random.normal(self._next_key(), shape,
                                    self.dtype) * scale
        elif init == "arange_log":   # mamba A_log init
            n = shape[-1]
            base = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))
            arr = jnp.broadcast_to(base, shape).astype(self.dtype)
        else:
            raise ValueError(init)
        self.params[name] = arr
        self.specs[name] = tuple(axes)
        return arr


def stack_init(build_fn: Callable[[Builder], None], n: int, key: jax.Array,
               dtype=jnp.float32, abstract: bool = False) -> Tuple[Dict, Dict]:
    """Initialize ``n`` copies of a layer stacked on a leading 'layers' axis
    (for lax.scan over layers).  Returns (stacked_params, specs)."""
    proto = Builder(jax.random.key(0), dtype, abstract=True)
    build_fn(proto)
    if abstract:
        params = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n,) + tuple(s.shape), s.dtype),
            proto.params)
    else:
        keys = jax.random.split(key, n)

        def one(k):
            b = Builder(k, dtype)
            build_fn(b)
            return b.params

        params = jax.vmap(one)(keys)
    specs = jax.tree.map(
        lambda axes: ("layers",) + axes, proto.specs,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))
    return params, specs


def spec_leaf(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x)
