"""Logical-axis → PartitionSpec rules for every parameter tree.

Sharding scheme (DESIGN.md §5):
  * 'pod'   — pure data parallelism across pods (DCN boundary);
  * 'data'  — data parallelism inside a pod; with FSDP enabled it also
              shards the *contraction* dim of every large weight (ZeRO-3
              style scatter, gathered by GSPMD where needed);
  * 'model' — tensor parallelism: attention heads / MLP ff dim / MoE
              expert dim (EP) / vocab dim of the embedding.

Rules are name-based over the param-tree paths produced by
`models.init_params`, applied with tree_map_with_path so stacked stage
dims (leading axes added by scan-stacking) are handled by rank offset.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig

__all__ = ["param_specs", "batch_specs", "cache_specs", "DATA_AXES",
           "maybe_shard", "sanitize_specs"]

DATA_AXES = ("pod", "data")   # batch is sharded over both


def maybe_shard(x, *axes):
    """with_sharding_constraint that degrades to a no-op outside a mesh.

    `axes` name mesh axes per dim (None / "data" / "model" / a tuple);
    axes not present in the ambient abstract mesh are dropped, and "data"
    expands to every data axis present (("pod", "data") on the multi-pod
    mesh).  Models call this on activations so GSPMD keeps batch/ff/expert
    dims sharded instead of replicating large intermediates.
    """
    names = jax.sharding.get_abstract_mesh().axis_names
    if not names:
        return x

    def fix(a):
        if a is None:
            return None
        if a == "data":
            a = DATA_AXES
        if isinstance(a, (tuple, list)):
            t = tuple(ax for ax in a if ax in names)
            return t if t else None
        return a if a in names else None

    spec = P(*(fix(a) for a in axes))
    return jax.lax.with_sharding_constraint(x, spec)


def _leaf_spec(path: tuple, shape: tuple, cfg: ModelConfig,
               par: ParallelConfig) -> P:
    names = [getattr(k, "key", str(k)) for k in path]
    name = names[-1] if names[-1] != "w" else names[-2]
    data = "data" if par.fsdp else None
    tp = "model" if par.tp else None
    rank = len(shape)

    def with_stage_prefix(*dims):
        """Pad leading None for stacked stage dims."""
        pad = rank - len(dims)
        return P(*([None] * pad + list(dims)))

    # ---- embeddings -------------------------------------------------- #
    if name == "table":
        return P(tp, None)
    if name == "unembed":
        return P(None, tp)

    # ---- MoE stacked expert weights [E, d, ff] ----------------------- #
    # "2d" (default): E over 'model' + d over 'data' (ZeRO-3 style;
    # weights re-gathered per microbatch — the dominant collective on
    # deepseek-v3).  "ep_pod": E over ('pod','model') = 32-way EP on the
    # multi-pod mesh — weights fully resident, zero gathers, MoE
    # all-to-all rides DCN instead (EXPERIMENTS §Perf deepseek iter 3).
    if name in ("w_in", "w_gate", "w_out") and rank >= 3 and cfg.is_moe \
            and shape[-3] == cfg.n_experts:
        e_axis = ("pod", "model") if par.expert_layout == "ep_pod" \
            else "model"
        if name == "w_out":
            return with_stage_prefix(
                e_axis, None, data if par.expert_layout == "2d" else None)
        return with_stage_prefix(
            e_axis, data if par.expert_layout == "2d" else None, None)
    if name == "router":
        return with_stage_prefix(data, None)

    # ---- projections: contraction over d -> head/ff dim sharded ------ #
    if name in ("wq", "wk", "wv", "w_in", "w_gate", "wq_b", "wk_b",
                "wv_b", "wx", "wy", "wr", "wi", "wg", "ck", "cr",
                "w_lora_a", "w_lora_b", "wq_a", "wkv_a"):
        return with_stage_prefix(data, tp)
    # ---- output projections: sharded dim contracts ------------------- #
    if name in ("wo", "w_out", "cv"):
        return with_stage_prefix(tp, data)
    if name == "conv_w":
        return with_stage_prefix(None, tp)

    # ---- vectors ------------------------------------------------------ #
    if rank >= 1 and shape[-1] in (cfg.rglru_width or 0, cfg.d_model) \
            and name in ("lam", "u", "conv_b"):
        return with_stage_prefix(tp)
    return P(*([None] * rank))   # norms, mixes, biases: replicated


def param_specs(params, cfg: ModelConfig, par: ParallelConfig):
    """PartitionSpec tree matching `params` (works on ShapeDtypeStructs)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: _leaf_spec(path, x.shape, cfg, par), params)


def batch_specs(cfg: ModelConfig, batch: dict,
                data_axes=("data",), micro_split: bool = False) -> dict:
    """Input shardings: batch dim over the data axes, seq/features
    replicated.  `micro_split` marks a leading [n_micro] accumulation dim
    (replicated)."""
    da = tuple(data_axes)
    lead = [None] if micro_split else []
    specs = {}
    for k, v in batch.items():
        if k == "mrope_pos":                       # [(micro,)? 3, B, S]
            specs[k] = P(*(lead + [None, da, None]))
        elif hasattr(v, "ndim") and v.ndim >= 1:
            rest = v.ndim - len(lead) - 1
            specs[k] = P(*(lead + [da] + [None] * rest))
        else:
            specs[k] = P()
    return specs


def _cache_leaf_spec(path: tuple, shape: tuple, data_axes=("data",),
                     seq_shard: bool = True) -> P:
    """Caches: batch dim over (pod, data); long attention caches are also
    SEQUENCE-sharded over 'model' (context parallelism — the 32k KV cache
    is the decode memory hog; softmax over the sharded seq dim makes GSPMD
    insert the expected cross-shard max/sum collectives).  Layout per
    block type: attention k/v [stages?, B, W, Hkv, hd]; MLA ckv/krope
    [stages?, B, S, r]; rec h [stages?, B, rw], conv [stages?, B, W-1,
    rw]; rwkv state [stages?, B, H, dk, dv]; enc [B, S, d]."""
    names = [getattr(k, "key", str(k)) for k in path]
    rank = len(shape)
    has_stage = "stages" in names
    b_axis = 1 if has_stage else 0
    dims = [None] * rank
    if rank > b_axis:
        dims[b_axis] = tuple(data_axes)
    leaf = names[-1]
    if seq_shard and leaf in ("k", "v", "ckv", "krope") \
            and rank > b_axis + 1 and shape[b_axis + 1] >= 4096:
        dims[b_axis + 1] = "model"
    return P(*dims)


def cache_specs(cache, data_axes=("data",), seq_shard: bool = True) -> dict:
    return jax.tree_util.tree_map_with_path(
        lambda path, x: _cache_leaf_spec(path, x.shape, data_axes,
                                         seq_shard), cache)


def sanitize_specs(spec_tree, shape_tree, mesh):
    """Drop sharding on dims not divisible by the mesh-axis product.

    jit *argument* shardings require exact divisibility (e.g. granite's
    vocab 49155 is not divisible by 16); such dims fall back to
    replicated, which GSPMD handles fine internally."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def fix_leaf(spec, x):
        dims = list(spec) + [None] * (len(x.shape) - len(spec))
        out = []
        for d, axis in zip(x.shape, dims):
            if axis is None:
                out.append(None)
                continue
            axes = axis if isinstance(axis, tuple) else (axis,)
            prod = 1
            for a in axes:
                prod *= sizes.get(a, 1)
            out.append(axis if d % prod == 0 else None)
        return P(*out)

    return jax.tree.map(fix_leaf, spec_tree, shape_tree,
                        is_leaf=lambda t: isinstance(t, P))
