"""Logical-axis sharding rules -> PartitionSpecs for params/batches/caches.

Strategy (DESIGN.md §8): 2-D "FSDP x TP" —

* ``data`` (x ``pod`` when multi-pod) is the FSDP axis: batch is
  data-parallel over it AND every weight matrix shards its non-TP dim over
  it (GSPMD inserts the all-gathers; grads reduce-scatter back).
* ``model`` is the tensor-parallel axis: attention heads / ff / vocab.
* MoE expert dim shards over the FSDP axes (expert parallelism); each
  expert's ff still shards over ``model``.
* Decode KV caches shard batch over FSDP and the *sequence* dim over
  ``model`` (sequence parallelism — the only layout that fits 500k-token
  caches); recurrent states shard their width over ``model``.

Rules are name-based over the param pytree paths, with leading stacked
dims (scan units / layers) padded with None.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig

TP_AXIS = "model"

__all__ = ["TP_AXIS", "activate_mesh", "dp_axes", "param_pspecs",
           "batch_pspecs", "cache_pspecs", "named_shardings"]


def activate_mesh(mesh: Mesh):
    """Context manager activating ``mesh`` (``jax.set_mesh``)."""
    return jax.set_mesh(mesh)


def dp_axes(mesh: Mesh):
    """FSDP/DP axes present in the mesh ('pod' first when multi-pod)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def _norm_axes(axes):
    """Collapse a 1-tuple mesh-axis set to its element and () to None —
    PartitionSpec equality distinguishes ('data',) from 'data'."""
    if isinstance(axes, tuple):
        if not axes:
            return None
        if len(axes) == 1:
            return axes[0]
    return axes


def _path_names(path) -> list[str]:
    names = []
    for ent in path:
        if hasattr(ent, "key"):
            names.append(str(ent.key))
        elif hasattr(ent, "name"):
            names.append(str(ent.name))
    return names


_REPLICATED = {
    "ln1", "ln2", "lnx", "final_norm", "enc_norm", "q_norm", "k_norm",
    "b_gates", "conv_b", "lam", "router", "step",
}


def _axes_size(axes, mesh: Mesh) -> int:
    size = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        size *= mesh.shape[a]
    return size


def _base_spec(cfg: ModelConfig, names: list[str], name: str, fsdp, tp,
               shape=None, mesh: Mesh = None):
    """Spec for the *unstacked* trailing dims of a leaf."""
    if name in _REPLICATED:
        return P()
    if name == "embed":
        return P(tp, fsdp)              # (V, d): vocab over TP
    if name == "unembed":
        return P(fsdp, tp)
    if name in ("wq", "wk", "wv"):
        return P(fsdp, tp)
    if name == "wo":
        return P(tp, fsdp)
    if name in ("bq", "bk", "bv"):
        return P(tp)
    if name in ("w_in", "w_gate", "w_out"):
        is_moe = (cfg.moe is not None and "mlp" in names
                  and "dense" not in names)
        if is_moe:                       # (E, d, ff) / (E, ff, d)
            # expert-parallel over FSDP when E divides it (arctic 128e);
            # otherwise FSDP the d/ff dims (mixtral 8e < 16 devices —
            # replicated E would cost 18.9 GiB/device of expert weights)
            e_ok = (shape is not None and len(shape) == 3
                    and fsdp and shape[0] % _axes_size(fsdp, mesh) == 0)
            if name != "w_out":
                return P(fsdp, None, tp) if e_ok else P(None, fsdp, tp)
            return P(fsdp, tp, None) if e_ok else P(None, tp, fsdp)
        return P(fsdp, tp) if name != "w_out" else P(tp, fsdp)
    if name in ("w_x", "w_g", "w_up", "w_q", "w_k", "w_v", "w_gates",
                "r_gates", "w_if"):
        return P(fsdp, tp)
    if name in ("w_down", "w_out_proj"):
        return P(tp, fsdp)
    if name == "conv_w":
        return P(None, tp)
    if name in ("w_a", "w_i"):
        return P(None, tp)
    return P()                           # safe default: replicate


def param_pspecs(cfg: ModelConfig, params_tree, mesh: Mesh):
    """PartitionSpec pytree mirroring ``params_tree`` (arrays or SDS)."""
    fsdp = _norm_axes(dp_axes(mesh))
    tp = TP_AXIS if TP_AXIS in mesh.axis_names else None

    def spec_for(path, leaf):
        names = _path_names(path)
        name = names[-1] if names else ""
        # strip stacked leading dims before shape-aware rules
        base_probe = _base_spec(cfg, names, name, fsdp, tp)
        trail = leaf.shape[leaf.ndim - len(base_probe):] \
            if leaf.ndim >= len(base_probe) else leaf.shape
        base = _base_spec(cfg, names, name, fsdp, tp, shape=trail, mesh=mesh)
        extra = leaf.ndim - len(base)
        if extra < 0:                    # scalar against P() etc.
            return P()
        full = P(*([None] * extra + list(base)))
        # drop axes that don't divide the dim (e.g. tiny reduced configs)
        fixed = []
        for dim, ax in zip(leaf.shape, full):
            if ax is None:
                fixed.append(None)
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            fixed.append(ax if dim % size == 0 else None)
        return P(*fixed)

    return jax.tree_util.tree_map_with_path(spec_for, params_tree)


def _dp_if_divisible(b: int, mesh: Mesh):
    fsdp = dp_axes(mesh)
    size = 1
    for a in fsdp:
        size *= mesh.shape[a]
    return _norm_axes(fsdp) if (b % size == 0 and b >= size) else None


def cache_pspecs(cfg: ModelConfig, cache_tree, mesh: Mesh, batch: int):
    """Decode cache/state sharding: batch over FSDP, seq/width over TP.

    ``batch`` disambiguates the batch dim (caches may carry a leading
    stacked-layer dim).
    """
    tp = TP_AXIS if TP_AXIS in mesh.axis_names else None
    tp_size = mesh.shape[tp] if tp else 1

    def divis(dim: int) -> bool:
        return bool(tp) and dim % tp_size == 0 and dim >= tp_size

    def spec_for(path, leaf):
        names = _path_names(path)
        name = names[-1] if names else ""
        nd = leaf.ndim
        shape = leaf.shape
        spec = [None] * nd
        # locate the batch dim (0 or 1 depending on stacking)
        bidx = None
        for i in range(min(2, nd)):
            if shape[i] == batch and (i == 0 or shape[0] != batch):
                bidx = i
                break
        if bidx is None and nd >= 2 and shape[0] == batch:
            bidx = 0
        if bidx is not None:
            spec[bidx] = _dp_if_divisible(batch, mesh)
        kv_names = ("k", "v", "xk", "xv",
                    "codes_k", "codes_v", "signs_k", "signs_v",
                    "scale_k", "scale_v")
        if name in kv_names and nd >= 4 and bidx is not None:
            t = shape[bidx + 1]          # sequence-parallel KV (raw OR
            if divis(t):                 # pwrel-compressed leaves)
                spec[bidx + 1] = tp
        elif name in ("h", "c", "n", "m", "conv") and nd >= 2:
            if divis(shape[-1]):
                spec[-1] = tp            # state width over TP
        # "C" (hd x hd matrix memory) stays replicated over TP
        return P(*spec)

    return jax.tree_util.tree_map_with_path(spec_for, cache_tree)


def batch_pspecs(cfg: ModelConfig, specs: dict, mesh: Mesh):
    """Shardings for an input_specs dict (tokens/aux/frames/token/cache/pos)."""
    batch = next(v.shape[0] for k, v in specs.items()
                 if k in ("tokens", "token", "frames"))
    out = {}
    for k, v in specs.items():
        if k == "cache":
            out[k] = cache_pspecs(cfg, v, mesh, batch)
        elif k == "pos":
            out[k] = P()
        else:
            dp = _dp_if_divisible(v.shape[0], mesh)
            out[k] = P(*([dp] + [None] * (v.ndim - 1)))
    return out


def named_shardings(tree_specs, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree_specs,
        is_leaf=lambda x: isinstance(x, P))
