"""Parallelization methods (survey §3.2) as sharding rules.

Hybrid data+model parallelism in the Mesh-TensorFlow style the survey covers
[161]: every parameter tensor gets a PartitionSpec over mesh axes
("data", "model") [+ optional "pod"], assigned by *role*:

  column-parallel [in, out]   -> P("data", "model")   (TP on out, FSDP on in)
  row-parallel    [in, out]   -> P("model", "data")
  embedding       [V, d]      -> P("model", "data")   (vocab-parallel)
  MoE experts     [E, d, ff]  -> P("model", "data", None)  (expert-parallel,
                                  the survey's "parameter dimension")
  vectors / biases            -> replicated

Sharding the second dim over "data" is the ZeRO/FSDP choice: XLA inserts a
per-layer all-gather inside the scan, trading collective time for the n-fold
parameter-memory reduction that makes the 1T-param config representable.

Stacked (scanned) layers get leading None axes automatically.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P
from jax.tree_util import DictKey, GetAttrKey, SequenceKey

COL = ("data", "model")
ROW = ("model", "data")

# classification by the innermost meaningful key name
_COL_NAMES = {"wq", "wk", "wv", "w_q", "w_dkv", "w_krope", "w_uk", "w_uv",
              "w_gate", "w_up", "cm_k", "cm_r", "w_r", "w_k", "w_v", "w_g",
              "w_x", "w_gate_branch", "w_rg", "w_ig"}
_ROW_NAMES = {"wo", "w_o", "w_down", "w_out", "cm_v"}
_MOE_STACKED = {"w_gate", "w_up", "w_down"}


def _path_names(path) -> list[str]:
    names = []
    for k in path:
        if isinstance(k, DictKey):
            names.append(str(k.key))
        elif isinstance(k, SequenceKey):
            names.append(f"[{k.idx}]")
        elif isinstance(k, GetAttrKey):
            names.append(k.name)
        else:
            names.append(str(k))
    return names


def _trailing_spec(names: list[str], ndim: int) -> Tuple[Optional[str], ...]:
    """Spec for the trailing dims based on the leaf's role."""
    # skip dense-dict wrappers
    core = [n for n in names if n not in ("w", "b")]
    name = core[-1] if core else ""
    is_bias = names and names[-1] == "b"

    if is_bias or ndim <= 1:
        return (None,) * min(ndim, 1)
    if name == "embed":
        return ("model", "data")
    if name == "lm_head":
        return ("data", "model")
    if name in ("dec_pos", "u"):
        return (None, None)
    if name == "router":
        return ("data", None)
    if name == "conv_w":
        return (None, "model")
    if name == "wA":
        return ("data", None)
    if name == "wB":
        return (None, "data")
    in_moe = "moe" in core and "shared" not in core
    if in_moe and name in _MOE_STACKED:
        if name == "w_down":
            return ("model", None, "data")
        return ("model", "data", None)
    if name in _COL_NAMES:
        return COL
    if name in _ROW_NAMES:
        return ROW
    # unknown 2D+ leaf: replicate (safe default)
    return (None,) * min(ndim, 2)


def param_specs(params):
    """PartitionSpec pytree matching `params` (works on ShapeDtypeStructs):
    weights sharded over BOTH data (ZeRO-3 / FSDP) and model (TP)."""

    def one(path, leaf):
        names = _path_names(path)
        ndim = len(leaf.shape)
        trailing = _trailing_spec(names, ndim)
        lead = (None,) * (ndim - len(trailing))
        return P(*(lead + tuple(trailing)))

    return jax.tree_util.tree_map_with_path(one, params)


def model_axis_dim(path, ndim: int):
    """Dimension index a leaf shards over the "model"/tensor mesh axis,
    under the same role rules as ``param_specs`` — the bridge the hybrid
    mesh planner (``repro.parallel``) uses to turn these PartitionSpecs
    into explicit per-leaf tensor-axis shards.  Returns None for leaves
    the role table replicates (biases, vectors, unknown 2D+ leaves).

    ``path`` is a ``tree_flatten_with_path`` key path; ``ndim`` the leaf's
    rank *excluding* any leading stacked-stage dimension (pass
    ``leaf.ndim - 1`` for stage-stacked leaves and add 1 to the result)."""
    names = _path_names(path)
    trailing = _trailing_spec(names, ndim)
    lead = ndim - len(trailing)
    for i, ax in enumerate(trailing):
        if ax == "model":
            return lead + i
    return None


def data_axes(multi_pod: bool = False):
    """Mesh axes that shard the batch dimension."""
    return ("pod", "data") if multi_pod else ("data",)

