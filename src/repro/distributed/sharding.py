"""Sharding rules: param / optimizer / activation / cache PartitionSpecs
for a (pod, data, model) mesh.

Baseline scheme (megatron-style TP + expert parallel + DP):

- ``data`` (+ ``pod``): batch dim of every activation, label, and KV cache;
  gradient all-reduce in training.
- ``model``: tensor parallel — attention QKV/O projections on the head*dh
  (flattened) dim, FFN hidden dim, vocab dim of embedding/LM head, expert
  dim of MoE stacks (expert parallel), MLA latent up/down projections.

Rules are applied by *path pattern* over the param tree, with divisibility
guards: a dim is only sharded if it divides evenly by the mesh axis size
(GQA KV projections with kv_heads < model_size stay replicated).

Scanned-layer stacks ([L, ...] leaves) get the same spec shifted right by
one (the layer axis is never sharded).

TP serving
----------
The serving pool reuses the SAME megatron rules to shard one scheduler's
executables over a 1-D ``("model",)`` mesh (``distributed/tp_pool.py``):

- params via :func:`param_specs` with ``enable_tp=True`` (the
  ``TP_MIN_PARAMS`` gate is a *training* default; serving opts in
  explicitly so smoke-scale models shard too);
- the KV pool via :func:`cache_specs_tp` — HEAD-axis sharding so the
  column-sharded ``wk``/``wv`` outputs write their local heads without a
  collective, falling back to the :func:`cache_specs_seqsharded` sequence
  rule when ``n_kv_heads`` does not divide the mesh, else replicating;
- ``lengths`` / ``block_tables`` leaves stay replicated: block tables are
  host bookkeeping, identical on every device, so ``Scheduler`` /
  ``BlockPool`` / ``PrefixCache`` / preemption replay run unchanged.

Row-sharded projections (``wo``/``w2``) introduce a psum whose summation
order differs from single-device matmuls, so logits agree to the last
ulp, not bitwise — serving gates assert TOKEN identity (argmax /
per-stream fold_in sampling), which is exact.
"""
from __future__ import annotations

import re
from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

# params smaller than this stay fully replicated (pure data parallel):
# whisper-base, mamba2-130m, hstu — TP gains nothing at this scale.
TP_MIN_PARAMS = 1_000_000_000


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_spec(mesh: Mesh, batch: int, rank: int = 2,
               include_model: bool = False) -> P:
    """Spec for a [B, ...] activation: shard batch over (pod, data) when
    divisible — plus 'model' for pure-FSDP layouts — else replicate."""
    daxes = data_axes(mesh)
    if include_model and "model" in mesh.axis_names:
        daxes = daxes + ("model",)
    total = 1
    for a in daxes:
        total *= _axis_size(mesh, a)
    first = daxes if batch % max(total, 1) == 0 and total > 1 else None
    return P(first, *([None] * (rank - 1)))


# ---- param rules -----------------------------------------------------------
# (pattern, dim-to-shard) applied to 2D+ weight leaves; dim counted from the
# END of the shape so scanned [L, ...] stacks work unchanged.
# dim -1 = output dim, dim -2 = input dim.
_W = r"/w(_q_(wo|dyn))?$"  # matches bf16 'w' and AutoQuant'd 'w_q_*' leaves
_RULES = (
    (r"embed/table$", -2),          # [V, d] -> vocab sharded
    (r"lm_head" + _W, -1),          # [d, V] -> vocab sharded
    (r"attn/wq" + _W, -1),
    (r"attn/wk" + _W, -1),
    (r"attn/wv" + _W, -1),
    (r"attn/wo" + _W, -2),
    (r"q_up" + _W, -1),             # MLA
    (r"kv_up" + _W, -1),
    (r"ffn/w1" + _W, -1),
    (r"ffn/w3" + _W, -1),
    (r"ffn/w2" + _W, -2),
    (r"shared/w1" + _W, -1),
    (r"shared/w3" + _W, -1),
    (r"shared/w2" + _W, -2),
    (r"moe/w1$", -3),               # [E, d, f] -> expert parallel
    (r"moe/w3$", -3),
    (r"moe/w2$", -3),
    (r"uvqk" + _W, -1),             # HSTU
    (r"out" + _W, -2),
    (r"(proj_x|proj_gate)" + _W, -1),  # RG-LRU branches
    (r"proj_out" + _W, -2),
    (r"in_proj" + _W, -1),          # mamba in_proj
    (r"out_proj" + _W, -2),
    (r"(gate_a|gate_x)" + _W, -1),
)


def _path_str(path) -> str:
    return "/".join(
        str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p))))
        for p in path
    )


def _spec_for(path_s: str, leaf, mesh: Mesh, enable_tp: bool) -> P:
    ndim = leaf.ndim
    if not enable_tp or "model" not in mesh.axis_names or ndim < 2:
        return P()
    msize = _axis_size(mesh, "model")
    for pat, dim in _RULES:
        if re.search(pat, path_s):
            axis = ndim + dim  # dim counted from the end
            if 0 <= axis < ndim and leaf.shape[axis] % msize == 0:
                spec = [None] * ndim
                spec[axis] = "model"
                return P(*spec)
            return P()
    return P()


def param_specs(
    cfg: ModelConfig, params_like: Any, mesh: Mesh,
    enable_tp: Optional[bool] = None,
) -> Any:
    """PartitionSpec tree matching an (abstract) param tree."""
    if enable_tp is None:
        enable_tp = cfg.n_params() >= TP_MIN_PARAMS
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _spec_for(_path_str(path), leaf, mesh, enable_tp),
        params_like,
    )


def opt_state_specs(
    cfg: ModelConfig, opt_like: Any, mesh: Mesh,
    enable_tp: Optional[bool] = None,
) -> Any:
    """Adam moments shard exactly like their params; step is replicated."""
    if enable_tp is None:
        enable_tp = cfg.n_params() >= TP_MIN_PARAMS

    def spec(path, leaf):
        s = _path_str(path)
        if leaf.ndim == 0 or "step" in s:
            return P()
        # strip the leading 'mu/' / 'nu/' NamedTuple field from the path
        s = re.sub(r"^\.?(mu|nu)/", "", s)
        return _spec_for(s, leaf, mesh, enable_tp)

    return jax.tree_util.tree_map_with_path(spec, opt_like)


def cache_specs(cfg: ModelConfig, cache_like: Any, mesh: Mesh, batch: int) -> Any:
    """KV-cache specs: batch dim over (pod, data); everything else
    replicated in the baseline (kv_heads rarely divide the model axis).
    :func:`cache_specs_seqsharded` re-shards the S axis over 'model'."""
    daxes = data_axes(mesh)
    total = 1
    for a in daxes:
        total *= _axis_size(mesh, a)
    bshard = daxes if batch % max(total, 1) == 0 and total > 1 else None

    def spec(path, leaf):
        if leaf.ndim == 0:
            return P()
        s = _path_str(path)
        lead_layer = "scanned" in s  # [L, B, ...] stacked caches
        specs = [None] * leaf.ndim
        bdim = 1 if lead_layer else 0
        if leaf.ndim > bdim:
            specs[bdim] = bshard
        return P(*specs)

    return jax.tree_util.tree_map_with_path(spec, cache_like)


def cache_specs_seqsharded(
    cfg: ModelConfig, cache_like: Any, mesh: Mesh, batch: int
) -> Any:
    """Beyond-paper variant: shard the cache SEQUENCE axis over 'model'
    (flash-decode sequence parallelism). Applies to [.., S, H, D] KV leaves
    with S divisible; GSPMD partitions the attention reduction over it."""
    base = cache_specs(cfg, cache_like, mesh, batch)
    msize = _axis_size(mesh, "model")

    def upgrade(path, leaf, spec):
        s = _path_str(path)
        if leaf.ndim >= 3 and re.search(r"(k|v|c_kv|k_rope)$", s):
            sdim = 2 if "scanned" in s else 1
            if leaf.shape[sdim] % msize == 0:
                parts = list(spec) + [None] * (leaf.ndim - len(spec))
                parts[sdim] = "model"
                return P(*parts)
        return spec

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: upgrade(path, leaf, _get(base, path)), cache_like
    )


def cache_specs_tp(
    cfg: ModelConfig, cache_like: Any, mesh: Mesh, batch: int
) -> Any:
    """Tensor-parallel serving pool specs: shard the KV HEAD axis over
    'model' so each device holds the heads its column-sharded wk/wv
    produce — paged writes stay local scatters, no collectives in the
    cache plumbing. Falls back to the sequence axis (flash-decode style,
    see :func:`cache_specs_seqsharded`) when ``n_kv_heads`` does not
    divide the mesh axis, else to the replicated base spec. Scalar /
    bookkeeping leaves (``lengths``, ``block_tables``) replicate — they
    are the host-state mirror the scheduler owns."""
    base = cache_specs(cfg, cache_like, mesh, batch)
    msize = _axis_size(mesh, "model")

    def upgrade(path, leaf, spec):
        s = _path_str(path)
        if leaf.ndim >= 3 and re.search(r"(k|v|c_kv|k_rope)$", s):
            sdim = 2 if "scanned" in s else 1
            hdim = sdim + 1
            parts = list(spec) + [None] * (leaf.ndim - len(spec))
            if hdim < leaf.ndim and leaf.shape[hdim] % msize == 0:
                parts[hdim] = "model"
                return P(*parts)
            if leaf.shape[sdim] % msize == 0:
                parts[sdim] = "model"
                return P(*parts)
        return spec

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: upgrade(path, leaf, _get(base, path)), cache_like
    )


def fsdp_upgrade(
    cfg: ModelConfig,
    tree_like: Any,
    spec_tree: Any,
    mesh: Mesh,
    axes: Tuple[str, ...] = ("data",),
) -> Any:
    """ZeRO-3-style upgrade (beyond-paper §Perf lever): additionally shard
    every large weight leaf over ``axes`` on its largest still-unsharded
    divisible dim. XLA GSPMD inserts the just-in-time all-gather before
    use and reduce-scatters gradients — per-device param+optimizer memory
    drops by the product of the axis sizes. ``axes=("data","model")`` is
    the pure-FSDP (no-TP) layout."""
    dsize = 1
    for a in axes:
        dsize *= _axis_size(mesh, a)
    if dsize <= 1:
        return spec_tree
    shard_as = axes if len(axes) > 1 else axes[0]

    def upgrade(path, leaf, spec):
        if leaf.ndim < 2 or leaf.size * dsize < 2 ** 24:
            return spec  # skip small leaves: all-gather latency dominates
        parts = list(spec) + [None] * (leaf.ndim - len(spec))
        cands = sorted(
            (i for i in range(leaf.ndim) if parts[i] is None),
            key=lambda i: -leaf.shape[i],
        )
        for i in cands:
            if leaf.shape[i] % dsize == 0:
                parts[i] = shard_as
                return P(*parts)
        return spec

    flat_specs, treedef = jax.tree_util.tree_flatten(
        spec_tree, is_leaf=lambda x: isinstance(x, P)
    )
    flat_leaves = jax.tree_util.tree_flatten_with_path(tree_like)[0]
    out = [
        upgrade(path, leaf, spec)
        for (path, leaf), spec in zip(flat_leaves, flat_specs)
    ]
    return jax.tree_util.tree_unflatten(treedef, out)


def _get(tree, path):
    for p in path:
        key = getattr(p, "key", getattr(p, "idx", None))
        tree = tree[key]
    return tree


def to_shardings(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


# ---- replica placement (core/router.py) ------------------------------------
# Step 1 of the multi-host serve plan pinned each ReplicaRouter pool to
# one whole device. Step 2 (the tensor-parallel pool) carves the host's
# devices into disjoint contiguous GROUPS of ``group_size`` — each group
# becomes one replica's ("model",) submesh. Groups are handed out whole:
# two replicas either share the SAME group (time-sharing, single-host
# CI) or touch no common device; a partial overlap is impossible by
# construction.

def replica_devices(
    n: int, devices: Optional[Sequence[Any]] = None, *, group_size: int = 1
) -> list:
    """Device pin per replica: round-robin over the host's devices (or an
    explicit pool), wrapping when replicas outnumber devices — replicas
    that share a device time-share it, which keeps the routing layer
    testable on single-device CI hosts.

    With ``group_size > 1`` (DP x TP) the pool is carved into disjoint
    contiguous groups of that size and whole GROUPS round-robin instead:
    wrapped replicas reuse an identical group, never a partially
    overlapping one. Returns one device per replica when ``group_size``
    is 1, else one tuple of devices per replica."""
    devs = list(devices) if devices is not None else list(jax.devices())
    if not devs:
        raise ValueError("no devices to place replicas on")
    if group_size <= 1:
        return [devs[i % len(devs)] for i in range(n)]
    n_groups = len(devs) // group_size
    if n_groups < 1:
        raise ValueError(
            f"group_size={group_size} needs at least that many devices, "
            f"have {len(devs)}"
        )
    groups = [
        tuple(devs[g * group_size:(g + 1) * group_size])
        for g in range(n_groups)
    ]
    return [groups[i % n_groups] for i in range(n)]


def place_replica(tree: Any, device: Any) -> Any:
    """Commit a pytree (params / cache) to one replica's device; ``None``
    leaves placement to JAX's default (single-device hosts share the one
    params object across replicas — no copy)."""
    if device is None:
        return tree
    return jax.device_put(tree, device)
