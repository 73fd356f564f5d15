"""GSPMD sharding rules: param-path regex -> PartitionSpec.

TP over 'model' (heads / ffn / vocab / experts), DP over ('pod','data')
on the batch, optional SP (sequence over 'model') via activation
constraints in the models.  Uneven dims (14 heads at TP=16, 40 experts at
EP=16) rely on GSPMD padding — flagged in the roofline notes.
"""
from __future__ import annotations

import re
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig


# first match wins; paths look like "layers/attn/wq/w" or "tok_embed"
_TRANSFORMER_RULES = [
    (r"tok_embed$", P("model", None)),
    (r"pos_embed$", P(None, None)),
    (r"meta_tokens$", P(None, None)),
    (r"lm_head/w$", P(None, "model")),
    # attention projections (leading layer-stack axis)
    (r"layers.*/(wq|wk|wv)/w$", P(None, None, "model")),
    (r"layers.*/wo/w$", P(None, "model", None)),
    # MLA
    (r"layers.*/wdq/w$", P(None, None, "model")),
    (r"layers.*/wuq/w$", P(None, "model", None)),
    (r"layers.*/wdkv/w$", P(None, None, None)),
    (r"layers.*/(wuk|wuv)/w$", P(None, None, "model")),
    # dense mlp
    (r"layers.*/mlp/(wi|wg)/w$", P(None, None, "model")),
    (r"layers.*/mlp/wo/w$", P(None, "model", None)),
    # moe (EP over 'model')
    (r"layers.*/moe/router/w$", P(None, None, None)),
    (r"layers.*/moe/(wi|wg)$", P(None, "model", None, None)),
    (r"layers.*/moe/wo$", P(None, "model", None, None)),
    # rwkv
    (r"layers.*/(wr|wk|wv|wg|cm_wk|cm_wr)/w$", P(None, None, "model")),
    (r"layers.*/(cm_wv)/w$", P(None, "model", None)),
    (r"layers.*/tm_w1$", P(None, None, None)),
    (r"layers.*/tm_w2$", P(None, None, None, None)),
    (r"layers.*/wl_a$", P(None, None, None)),
    (r"layers.*/wl_b$", P(None, None, None)),
    # hymba ssm
    (r"layers.*/in_proj/w$", P(None, None, "model")),
    # whisper enc/dec stacks
    (r"(enc|dec)_layers.*/(wq|wk|wv)/w$", P(None, None, "model")),
    (r"(enc|dec)_layers.*/wo/w$", P(None, "model", None)),
    (r"(enc|dec)_layers.*/mlp/wi/w$", P(None, None, "model")),
    (r"(enc|dec)_layers.*/mlp/wo/w$", P(None, "model", None)),
    # projection biases: qkv/mlp-in biases shard with their matmul's
    # output features; wo biases add AFTER the TP all-reduce, replicated
    (r"(wq|wk|wv|wg|wi)/b$", P(None, "model")),
    (r"(wo|cm_wv)/b$", P(None, None)),
    # norms (scale/bias) are elementwise over the replicated residual
    (r"(ln[0-9]?|ln_x|ln_out|norm)/(scale|bias)$", P(None, None)),
    # rwkv mixing vectors + per-head decay/bonus, hymba ssm scalars:
    # tiny per-channel state, replicated
    (r"layers.*/(cm_maa_k|cm_maa_r|maa_x|w0|dt_bias|A_log|D)$",
     P(None, None)),
    (r"layers.*/maa_wkvrg$", P(None, None, None)),
    (r"layers.*/u$", P(None, None, None)),
]


def match_for_path(path_str: str):
    """First rule matching ``path_str`` as ``(pattern, spec)``, or
    ``None`` when no rule covers the path — the silent-replication
    fallthrough ``tests/test_sharding_rules.py`` pins against."""
    for pat, spec in _TRANSFORMER_RULES:
        if re.search(pat, path_str):
            return pat, spec
    return None


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def spec_for_path(path_str: str, ndim: int) -> P:
    hit = match_for_path(path_str)
    if hit is not None:
        _, spec = hit
        if len(spec) == ndim:
            return spec
        # rank mismatch (e.g. an unstacked top-level norm): replicate
        return P(*([None] * ndim))
    return P(*([None] * ndim))


def _axis_size(entry, mesh: Mesh) -> int:
    if entry is None:
        return 1
    if isinstance(entry, (tuple, list)):
        n = 1
        for a in entry:
            n *= mesh.shape[a]
        return n
    return mesh.shape[entry]


def filter_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop (replicate) any spec axis whose mesh size does not divide the
    dim — explicit in_shardings require exact divisibility."""
    dims = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for entry, size in zip(dims, shape):
        if entry is not None and (
                entry not in mesh.axis_names
                and not isinstance(entry, (tuple, list))):
            entry = None                      # axis absent from this mesh
        if entry is not None and size % _axis_size(entry, mesh) != 0:
            entry = None
        out.append(entry)
    return P(*out)


def _add_fsdp_axis(spec: P, shape, n_data: int) -> P:
    """ZeRO/FSDP: additionally shard params (and thus opt state) over
    'data' on the first unsharded dim divisible by the data axis size.
    GSPMD inserts the per-layer all-gathers automatically."""
    dims = list(spec) + [None] * (len(shape) - len(spec))
    for i, (d, s) in enumerate(zip(dims, shape)):
        if d is None and s % n_data == 0 and s >= n_data:
            dims[i] = "data"
            return P(*dims)
    return spec


def param_specs(params_shape, mesh: Optional[Mesh] = None, *,
                fsdp: bool = False, n_data: int = 1) -> dict:
    """Tree of PartitionSpecs matching a params(-shaped) tree."""
    def one(path, leaf):
        spec = spec_for_path(_path_str(path), len(leaf.shape))
        if mesh is not None:
            spec = filter_spec(spec, leaf.shape, mesh)
        if fsdp and n_data > 1:
            spec = _add_fsdp_axis(spec, leaf.shape, n_data)
        return spec
    return jax.tree_util.tree_map_with_path(one, params_shape)


def param_shardings(params_shape, mesh: Mesh, *, fsdp: bool = False):
    n_data = mesh.shape.get("data", 1)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        param_specs(params_shape, mesh, fsdp=fsdp, n_data=n_data))


# ---------------------------------------------------------------------------
# batch / cache specs
# ---------------------------------------------------------------------------

def batch_axes(global_batch: int, mesh: Mesh):
    """Shard batch over ('pod','data') when divisible, else replicate."""
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    if global_batch % max(n, 1) == 0 and n > 1:
        return tuple(axes)
    return None


def batch_specs(batch_shape, mesh: Mesh, cfg: ModelConfig,
                seq_shard: bool = False):
    """Specs for a data batch tree {'tokens': (B,S), ...}."""
    def one(path, leaf):
        b_axes = batch_axes(leaf.shape[0], mesh)
        rest = [None] * (len(leaf.shape) - 1)
        return P(b_axes, *rest)
    return jax.tree_util.tree_map_with_path(one, batch_shape)


def cache_specs(cache_shape, mesh: Mesh, cfg: ModelConfig,
                *, seq_axis_shard: bool = False):
    """Specs for KV/state cache trees.

    The *sequence* axis of KV caches shards over 'model' (context
    parallelism for decode): it is always divisible, it parallelizes the
    bandwidth-bound cache reads across TP chips, and it works for MQA
    (kv=1) where head-sharding cannot.  GSPMD inserts the softmax
    reductions across shards.  With ``seq_axis_shard`` (long-context
    batch=1 cells) the T axis additionally takes 'data'.

    Layouts (leading layer-stack axis L):
      dense KV     (L, B, T, G, hd)  -> (None, batch, T_axes, None, None)
      MLA latents  (L, B, T, r)      -> (None, batch, T_axes, None)
      rwkv state   (L, B, H, N, V)   -> (None, batch, 'model', None, None)
      hymba ssm    (L, B, H, P, N)   -> (None, batch, 'model', None, None)
    """
    t_axes = ("model", "data") if (seq_axis_shard and "data" in
                                   mesh.axis_names) else "model"

    def one(path, leaf):
        name = _path_str(path)
        nd = len(leaf.shape)
        if name == "len" or nd == 0:
            return P()
        b_axes = batch_axes(leaf.shape[1], mesh) if nd > 1 else None
        if name in ("k", "v", "k_swa", "v_swa", "k_glb", "v_glb",
                    "ck", "cv"):
            spec = P(None, b_axes, t_axes, None, None)
        elif name in ("c_kv", "k_rope"):
            spec = P(None, b_axes, t_axes, None)
        elif name in ("wkv", "ssm"):
            spec = P(None, b_axes, "model", None, None)
        elif name in ("tm_x", "cm_x"):
            spec = P(None, b_axes, None)
        else:
            spec = P(*([None] * nd))
        return filter_spec(spec, leaf.shape, mesh)
    return jax.tree_util.tree_map_with_path(one, cache_shape)


def paged_cache_specs(cache_shape, mesh: Mesh, cfg: ModelConfig):
    """Specs for the PAGED pool cache (block arena + tables).

    The arena reuses the dense cache's leaf names but NOT its axis
    semantics — axis 1 is the block id and axis 2 the in-block slot, so
    ``cache_specs``'s sequence-over-'model' rule would shard the
    16-wide block_size axis.  Blocks are head-partitioned instead:

      dense arena  (L, nb, bs, G, hd) -> (None, None, None, 'model', None)
      MLA latents  (L, nb, bs, r)     -> replicated (no head axis)
      metadata     block_tables/lens/max_len -> replicated (host-mirrored)

    One logical block id therefore names one slice per shard — the
    host-side ``BlockPool`` free list stays shard-agnostic, and
    refcount/COW/sanitizer semantics carry over unchanged.
    ``filter_spec`` drops the 'model' axis when it does not divide the
    KV head count (explicit placement needs exact divisibility).
    """
    def one(path, leaf):
        name = _path_str(path)
        nd = len(leaf.shape)
        if name in ("k", "v") and nd == 5:
            spec = P(None, None, None, "model", None)
        else:
            spec = P(*([None] * nd))
        return filter_spec(spec, leaf.shape, mesh)
    return jax.tree_util.tree_map_with_path(one, cache_shape)


def paged_cache_shardings(cache_shape, mesh: Mesh, cfg: ModelConfig):
    return to_shardings(paged_cache_specs(cache_shape, mesh, cfg), mesh)


def to_shardings(spec_tree, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s,
        spec_tree)
