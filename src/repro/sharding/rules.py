"""Name-based sharding rules over the production mesh.

The mesh axes are ("data", "model") for a single pod and
("pod", "data", "model") for the multi-pod configuration.  Policy:

- **Tensor parallel ("model")**: attention heads, FFN hidden dim, MoE
  experts, vocab (embed rows / head cols), SSM inner channels.
- **FSDP ("data", + "pod" when multi-pod)**: the d_model dim of every
  weight matrix is additionally sharded over the data axes (ZeRO-3
  analogue expressed purely through pjit PartitionSpecs) so that the
  340B-class configs fit per-device HBM.  XLA inserts the all-gathers.
- **Batch ("pod","data")**: the leading batch dim of activations.

Every rule is a *candidate list* per dim; the engine keeps the first
candidate whose axis-size product divides the dim and whose axes are not
already used by an earlier dim of the same param.  Anything unmatched is
replicated — so every architecture lowers even when a dim (e.g. kv-heads=4)
cannot be split 16-way.
"""
from __future__ import annotations

import contextlib
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Candidate axis-groups, in priority order, per *logical* role.
TP = ("model",)          # tensor-parallel group
FSDP = ("fsdp",)         # placeholder resolved per-mesh (data [+pod])
DP = ("dp",)             # batch data-parallel group (pod+data)


AxisCandidates = Sequence[Sequence[str]]  # e.g. [TP] or [TP, FSDP]

# map path-suffix regex -> right-aligned per-dim candidate lists.
# Each dim entry is a list of candidate axis-groups (first fit wins) or None.
_PARAM_RULES: List[Tuple[str, List[Optional[AxisCandidates]]]] = [
    # --- attention ---
    (r"(attn|cross)/wq$",        [[FSDP], [TP], None]),       # (d, H, hd)
    (r"(attn|cross)/w[kv]$",     [[FSDP], [TP], None]),       # (d, Hkv, hd)
    (r"(attn|cross)/wo$",        [[TP], None, [FSDP]]),       # (H, hd, d)
    # --- dense FFN ---
    (r"ffn/w_(in|gate)$",        [[FSDP], [TP]]),             # (d, ff)
    (r"ffn/w_out$",              [[TP], [FSDP]]),             # (ff, d)
    # --- MoE ---
    (r"moe/router$",             [[FSDP], None]),             # (d, E)
    (r"moe/w_(in|gate)$",        [[TP], [FSDP], None]),       # (E, d, de)
    (r"moe/w_out$",              [[TP], None, [FSDP]]),       # (E, de, d)
    (r"moe/w_shared_(in|gate)$", [[FSDP], [TP]]),
    (r"moe/w_shared_out$",       [[TP], [FSDP]]),
    # --- embeddings / unembedding ---
    (r"(^|/)embed$",             [[TP], [FSDP]]),             # (V, d)
    (r"(^|/)head$",              [[FSDP], [TP]]),             # (d, V)
    (r"(img|audio)_proj$",       [[FSDP], [TP]]),             # (d, d)
    # --- RWKV6 ---
    (r"w_(r|k|v|g|o|cr)$",       [[FSDP], [TP]]),             # (d, d)
    (r"w_ck$",                   [[FSDP], [TP]]),             # (d, ff)
    (r"w_cv$",                   [[TP], [FSDP]]),             # (ff, d)
    (r"lora_a$",                 [[FSDP], None]),
    (r"lora_b$",                 [None, [FSDP]]),
    (r"(^|/)u$",                 [[TP], None]),               # (H, hd)
    (r"(^|/)mu$",                [None, None]),               # (5, d)
    # --- Mamba2 ---
    (r"in_proj$",                [[FSDP], [TP]]),             # (d, d_in_all)
    (r"out_proj$",               [[TP], [FSDP]]),             # (d_inner, d)
    (r"conv_w$",                 [None, [TP]]),               # (width, ch)
    (r"conv_b$",                 [[TP]]),
    (r"(a_log|dt_bias|d_skip)$", [[TP]]),                     # (n_heads,)
]


def _resolve_group(group: Sequence[str], mesh: Mesh) -> Optional[Tuple[str, ...]]:
    """Map logical groups (fsdp/dp) onto concrete mesh axes."""
    names = mesh.axis_names
    out: List[str] = []
    for a in group:
        if a == "fsdp":
            out.extend([ax for ax in ("data",) if ax in names])
        elif a == "dp":
            out.extend([ax for ax in ("pod", "data") if ax in names])
        elif a in names:
            out.append(a)
        else:
            return None
    return tuple(out) if out else None


def _axes_size(axes: Tuple[str, ...], mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in axes]))


def spec_for_shape(shape: Tuple[int, ...],
                   dim_rules: List[Optional[AxisCandidates]],
                   mesh: Mesh,
                   priority: Optional[Sequence[int]] = None) -> P:
    """Right-align ``dim_rules`` against ``shape``; leading dims replicate.

    ``priority`` (optional, same length as dim_rules) assigns axes to
    higher-priority (smaller value) dims first, so e.g. a kv-heads dim can
    claim "model" before a fallback sequence dim does.
    """
    n_lead = len(shape) - len(dim_rules)
    assert n_lead >= 0, (shape, dim_rules)
    entries: List[Any] = [None] * len(shape)
    used: set = set()
    order = range(len(dim_rules))
    if priority is not None:
        order = sorted(order, key=lambda i: priority[i])
    for i in order:
        dim, cands = shape[n_lead + i], dim_rules[i]
        picked = None
        for group in (cands or []):
            axes = _resolve_group(group, mesh)
            if axes is None or any(a in used for a in axes):
                continue
            if dim % _axes_size(axes, mesh) == 0:
                picked = axes if len(axes) > 1 else axes[0]
                used.update(axes)
                break
        entries[n_lead + i] = picked
    # trim trailing Nones for cleanliness
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def param_specs(params_abstract, mesh: Mesh) -> Any:
    """PartitionSpec tree for a parameter pytree (name-rule matched)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_abstract)
    specs = []
    for path, leaf in flat:
        name = _path_str(path)
        spec = P()
        for pat, dims in _PARAM_RULES:
            if re.search(pat, name) and len(dims) <= len(leaf.shape):
                spec = spec_for_shape(tuple(leaf.shape), dims, mesh)
                break
        specs.append(spec)
    return jax.tree_util.tree_unflatten(treedef, specs)


def param_shardings(params_abstract, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params_abstract, mesh))


def opt_state_specs(params_abstract, mesh: Mesh) -> Dict[str, Any]:
    """AdamW state = {m, v, step}; m/v mirror the param shardings."""
    ps = param_specs(params_abstract, mesh)
    return {"m": ps, "v": ps, "step": P()}


# ---------------------------------------------------------------------------
# Activations / batches / caches
# ---------------------------------------------------------------------------


def batch_axes(mesh: Mesh) -> Optional[Tuple[str, ...]]:
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return axes if axes else None


def dp_axes(mesh: Mesh, global_batch: int) -> Optional[Tuple[str, ...]]:
    """The dp axes usable for ``global_batch`` — drops "pod" first, then
    "data", until the batch divides.  None when nothing fits."""
    axes = list(batch_axes(mesh) or ())
    while axes and global_batch % _axes_size(tuple(axes), mesh) != 0:
        axes.pop(0)
    return tuple(axes) if axes else None


def _ambient_mesh():
    """The mesh set by an enclosing ``jax.set_mesh(mesh)`` or
    ``mesh_context(mesh)``; None when unset."""
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None


def mesh_context(mesh):
    """Context that makes ``mesh`` (abstractly) ambient while a jitted
    function traces, so code deep in the model — the paged-attention
    kernel's ``shard_map``, the ``constrain_*`` helpers — sees the mesh
    the function is compiled for.  A no-op context for ``mesh=None``."""
    if mesh is None:
        return contextlib.nullcontext()
    return jax.sharding.use_abstract_mesh(mesh.abstract_mesh)


def constrain_dims(x, entries: Sequence[Any]) -> Any:
    """``with_sharding_constraint`` with divisibility/ambient-mesh safety.

    ``entries``: one entry per dim — None, an axis name, a tuple of axis
    names, or "dp" (expands to the pod+data axes).  Entries whose axes are
    absent or don't divide the dim are dropped.  No-op outside a mesh."""
    mesh = _ambient_mesh()
    if mesh is None or not mesh.axis_names or mesh.size <= 1:
        return x
    used: set = set()
    spec: List[Any] = []
    for dim, e in zip(x.shape, entries):
        if e is None:
            spec.append(None)
            continue
        if e == "dp":
            axes: Tuple[str, ...] = tuple(
                a for a in ("pod", "data") if a in mesh.axis_names)
        elif isinstance(e, str):
            axes = (e,) if e in mesh.axis_names else ()
        else:
            axes = tuple(a for a in e if a in mesh.axis_names)
        while axes and (any(a in used for a in axes)
                        or dim % _axes_size(axes, mesh) != 0):
            axes = axes[1:]
        if not axes:
            spec.append(None)
            continue
        used.update(axes)
        spec.append(axes if len(axes) > 1 else axes[0])
    return jax.lax.with_sharding_constraint(x, P(*spec))


def constrain_batch(x, *, extra: Tuple[Any, ...] = ()) -> Any:
    """``with_sharding_constraint`` pinning dim 0 of ``x`` to the dp axes of
    the *ambient* mesh (no-op outside a mesh context or when the batch does
    not divide).  Used inside model forward passes so the SPMD partitioner
    keeps activations batch-sharded over ("pod","data") instead of
    replicating across the pod axis (anchored only by weight shardings, the
    propagation otherwise collapses onto the FSDP axes).

    ``extra`` optionally pins dims 1.. (e.g. vocab over "model")."""
    mesh = _ambient_mesh()
    if mesh is None or not mesh.axis_names or mesh.size <= 1:
        return x
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    while axes and x.shape[0] % _axes_size(tuple(axes), mesh) != 0:
        axes.pop(0)
    if not axes:
        return x
    bspec = tuple(axes) if len(axes) > 1 else axes[0]
    rest: List[Any] = list(extra) + [None] * (x.ndim - 1 - len(extra))
    # validate extras against mesh/divisibility
    cleaned = []
    for d, e in zip(x.shape[1:], rest):
        if e is None or e not in mesh.axis_names \
                or d % mesh.shape[e] != 0:
            cleaned.append(None)
        else:
            cleaned.append(e)
    return jax.lax.with_sharding_constraint(x, P(bspec, *cleaned))


def _dp_bspec(mesh: Mesh, global_batch: int):
    axes = dp_axes(mesh, global_batch)
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def batch_spec(batch_abstract, mesh: Mesh, *, global_batch: int) -> Any:
    """Shard the leading batch dim of every input over the dp axes (dropping
    axes until the batch divides — long_500k with batch=1 replicates)."""
    bspec = _dp_bspec(mesh, global_batch)

    def one(leaf):
        return P(*((bspec,) + (None,) * (len(leaf.shape) - 1)))

    return jax.tree.map(one, batch_abstract)


def logits_spec(mesh: Mesh, *, global_batch: int, ndim: int = 3,
                vocab: Optional[int] = None) -> P:
    bspec = _dp_bspec(mesh, global_batch)
    tp = "model" if "model" in mesh.axis_names else None
    if tp and vocab is not None and vocab % mesh.shape[tp] != 0:
        tp = None   # odd vocab (e.g. whisper's 51865) cannot split
    mid = (None,) * (ndim - 2)
    return P(*((bspec,) + mid + (tp,)))


# Cache entries, by key name -> (right-aligned dim rules, priority).
#   attention caches (..., B, S, Hkv, hd): batch over dp, heads over model,
#   with S-over-model as the fallback when kv-heads don't divide (heads get
#   first claim via the priority vector).
_KV = ([[DP], [TP], [TP], None], [0, 2, 1, 3])
# block-paged pools (..., num_pages, Hkv, page_size, hd): pages are a
# *shared* arena — any slot's pages live anywhere in it, so the page dims
# must stay replicated across dp (a dp-shard owns whole copies of the
# pool for its slots' gathers); only kv-heads split, over "model".  The
# page tables (B, max_pages) replicate per data shard: they are tiny
# int32 and feed scalar-prefetch/gather indices on every shard.
_PAGED_KV = ([None, [TP], None, None], None)
_CACHE_RULES: Dict[str, Tuple[List[Optional[AxisCandidates]],
                              Optional[List[int]]]] = {
    "k":   _KV,
    "v":   _KV,
    "xk":  _KV,
    "xv":  _KV,
    "ck":  _KV,
    "cv":  _KV,
    # ssm / rwkv states: (..., B, heads, hd, state)
    "wkv": ([[DP], [TP], None, None], None),
    "ssm": ([[DP], [TP], None, None], None),
    "conv": ([[DP], None, [TP]], None),
    "att_shift": ([[DP], None], None),
    "ffn_shift": ([[DP], None], None),
    "pos": ([], None),
    "page_table": ([], None),
}


def cache_specs(cache_abstract, mesh: Mesh, *, global_batch: int) -> Any:
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_abstract)
    # dp axes usable for this batch size
    dp = list(dp_axes(mesh, global_batch) or ())
    # a page table marks the cache as block-paged: its k/v leaves are the
    # shared page pool, not (L, B, S, ...) rectangles — different rule
    paged = isinstance(cache_abstract, dict) and \
        "page_table" in cache_abstract

    def resolve(name, leaf):
        rule = _PAGED_KV if (paged and name in ("k", "v")) \
            else _CACHE_RULES.get(name)
        if rule is None or not leaf.shape:
            return P()
        dims, prio = rule
        # substitute the concrete dp axes for the DP placeholder
        subst: List[Optional[AxisCandidates]] = []
        for d in dims:
            if d is None:
                subst.append(None)
            else:
                groups = []
                for g in d:
                    if g == DP:
                        if dp:
                            groups.append(tuple(dp))
                    else:
                        groups.append(g)
                subst.append(groups or None)
        if len(subst) > len(leaf.shape):
            subst = subst[-len(leaf.shape):]
            prio = prio[-len(leaf.shape):] if prio else None
        return spec_for_shape(tuple(leaf.shape), subst, mesh, prio)

    specs = [resolve(_path_str(path).split("/")[-1], leaf)
             for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# Speculative-engine state (serve/engine.py)
# ---------------------------------------------------------------------------

# engine-state keys that are NOT batch-leading (replicated scalar step state)
_ENGINE_SCALAR_KEYS = ("step_idx",)


def engine_state_specs(state_abstract, mesh: Mesh, *,
                       global_batch: int) -> Dict[str, Any]:
    """PartitionSpecs for the speculative-engine state dict.

    Model caches go through the cache rules (batch over dp, kv-heads /
    states over "model"); every other entry is a batch-leading per-sequence
    vector (window/last/history/…) sharded over the dp axes; scalar step
    state replicates.  Generic over added keys, so new per-sequence fields
    shard without a rules change."""
    out: Dict[str, Any] = {}
    for k, v in state_abstract.items():
        if k in ("t_cache", "d_cache"):
            out[k] = cache_specs(v, mesh, global_batch=global_batch)
        elif k in _ENGINE_SCALAR_KEYS or not getattr(v, "shape", ()):
            out[k] = P()
        else:
            out[k] = batch_spec({k: v}, mesh, global_batch=global_batch)[k]
    return out


def batch_leading_specs(tree_abstract, mesh: Mesh, *,
                        global_batch: int) -> Any:
    """Specs for a pytree of per-sequence buffers: leading dim over dp when
    it divides, scalars (0-d leaves) replicated.  Used for the engine's
    generation-loop carry (output buffers + counters)."""
    def one(leaf):
        if not leaf.shape:
            return P()
        return batch_spec({"x": leaf}, mesh, global_batch=global_batch)["x"]

    return jax.tree.map(one, tree_abstract)
