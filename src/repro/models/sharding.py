"""Logical-axis sharding rules (MaxText-style) + the mesh context the model
layer reads.

The model code annotates tensors with *logical* axis names; the rules table
maps those to physical mesh axes. The launch layer installs a mesh +
(optionally overridden) rules; on a bare CPU (smoke tests) no mesh is set
and every annotation is a no-op, so the same model code runs everywhere.

Default physical mapping (production mesh (pod, data, model) or
(data, model)):

  batch        -> ("pod", "data")   data parallel (+ pod axis when present)
  seq          -> "model"           sequence parallelism for inter-block
                                    activations (Megatron-SP): saved
                                    activations are seq-sharded
  heads/kv     -> "model"           tensor parallel attention
  ffn/experts  -> "model"           tensor / expert parallel FFN
  vocab        -> "model"           vocab-sharded embedding + logits
  embed_fsdp   -> ("pod", "data")   ZeRO-3 style weight sharding on the
                                    embed dim of weight matrices
  kv_seq       -> "model"           seq-sharded KV cache in decode (the
                                    RPC-style distributed decode, §DESIGN 3)
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[str, Tuple[str, ...], None]

DEFAULT_RULES: dict[str, Axis] = {
    "batch": ("pod", "data"),
    "seq": "model",
    "heads": "model",
    "kv_heads": "model",
    "ffn": "model",
    "experts": "model",
    "vocab": "model",
    "embed": None,
    "embed_fsdp": ("pod", "data"),
    "kv_seq": "model",
    "stage": None,
    "frames": None,
}

_STATE = threading.local()


def _get() -> dict:
    if not hasattr(_STATE, "ctx"):
        _STATE.ctx = {"mesh": None, "rules": dict(DEFAULT_RULES)}
    return _STATE.ctx


@contextlib.contextmanager
def mesh_context(mesh: Optional[Mesh], rules: Optional[dict] = None):
    """Install mesh+rules for model tracing. Also enters jax.set_mesh so
    with_sharding_constraint works inside jit."""
    ctx = _get()
    prev = dict(ctx)
    ctx["mesh"] = mesh
    if rules is not None:
        ctx["rules"] = {**DEFAULT_RULES, **rules}
    try:
        if mesh is not None:
            with jax.sharding.set_mesh(mesh):
                yield
        else:
            yield
    finally:
        ctx.clear()
        ctx.update(prev)


def current_mesh() -> Optional[Mesh]:
    return _get()["mesh"]


def rules() -> dict:
    return _get()["rules"]


def resolve(*logical: Optional[str]) -> P:
    """Map logical axis names to a PartitionSpec under the current rules,
    dropping mesh axes that don't exist on the current mesh."""
    mesh = current_mesh()
    axes = set(mesh.axis_names) if mesh is not None else set()
    out = []
    for name in logical:
        if name is None:
            out.append(None)
            continue
        phys = rules().get(name)
        if phys is None:
            out.append(None)
        elif isinstance(phys, tuple):
            keep = tuple(a for a in phys if a in axes)
            out.append(keep if len(keep) > 1 else (keep[0] if keep else None))
        else:
            out.append(phys if phys in axes else None)
    return P(*out)


def logical(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """Annotate x with logical axes; no-op without a mesh."""
    mesh = current_mesh()
    if mesh is None:
        return x
    assert len(names) == x.ndim, (names, x.shape)
    return jax.lax.with_sharding_constraint(x, resolve(*names))


def named_sharding(*logical_names: Optional[str]) -> Optional[NamedSharding]:
    mesh = current_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, resolve(*logical_names))


def spec_for_tree(tree_of_logical):
    """Map a pytree of logical-name tuples to NamedShardings (or None)."""
    return jax.tree.map(lambda names: named_sharding(*names),
                        tree_of_logical,
                        is_leaf=lambda x: isinstance(x, tuple))
