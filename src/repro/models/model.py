"""Unified model: every assigned architecture is a stack of pattern-typed
blocks (attn / local / global / rec / rwkv) + embeddings (+ encoder for
enc-dec, + frontends for VLM/audio, + MTP head for DeepSeek-V3).

Layer stacking uses `jax.lax.scan` over *stages* (one stage = one repeat
of `cfg.layer_pattern`), so HLO size is O(pattern), not O(n_layers) —
essential for compiling the 61-layer DeepSeek config.  A partial tail
stage (e.g. recurrentgemma's 38 = 12×3 + 2) is unrolled.

API (all pure functions of (cfg, params, ...)):
  init_params(cfg, key, dtype)                  # eval_shape-able
  forward(cfg, params, batch)  -> (logits, aux)
  loss_fn(cfg, params, batch)  -> scalar
  init_cache(cfg, batch, max_len, dtype)
  prefill(cfg, params, batch, max_len) -> (logits_last, cache)
  decode_step(cfg, params, cache, tokens, pos) -> (logits, cache)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.parallel.sharding import maybe_shard
from .attention import GQA, MLA, CrossAttention
from .layers import (embed, init_embedding, init_mlp, init_rms_norm, mlp,
                     rms_norm, unembed)
from .moe import MoE
from .recurrent import RGLRUBlock
from .rwkv import RWKV6Block

__all__ = ["init_params", "forward", "loss_fn", "init_cache", "prefill",
           "decode_step"]


# ---------------------------------------------------------------------- #
# block-level init / apply
# ---------------------------------------------------------------------- #
def _window_for(cfg: ModelConfig, kind: str) -> int | None:
    if kind == "local":
        return cfg.local_window
    if kind == "attn" and cfg.family == "hybrid":
        return cfg.local_window
    return None


def _attn_cls(cfg: ModelConfig):
    return MLA if cfg.use_mla else GQA


def _block_init(key, cfg: ModelConfig, kind: str, dtype,
                cross: bool = False) -> dict:
    keys = jax.random.split(key, 4)
    if kind == "rwkv":
        return {"ln": init_rms_norm(cfg.d_model, dtype),
                "rwkv": RWKV6Block.init(keys[0], cfg, dtype)}
    p = {"ln1": init_rms_norm(cfg.d_model, dtype),
         "ln2": init_rms_norm(cfg.d_model, dtype)}
    if kind == "rec":
        p["rec"] = RGLRUBlock.init(keys[0], cfg, dtype)
    else:
        p["attn"] = _attn_cls(cfg).init(keys[0], cfg, dtype)
    if cfg.is_moe:
        p["moe"] = MoE.init(keys[1], cfg, dtype)
    else:
        p["mlp"] = init_mlp(keys[1], cfg.d_model, cfg.d_ff, dtype)
    if cross:
        p["ln_x"] = init_rms_norm(cfg.d_model, dtype)
        p["xattn"] = CrossAttention.init(keys[2], cfg, dtype)
    return p


def _block_apply(p: dict, cfg: ModelConfig, kind: str, h, positions,
                 enc=None, impl: str = "auto"):
    """One block, full-sequence.  Returns (h, moe_aux)."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "rwkv":
        return RWKV6Block.apply(p["rwkv"], cfg,
                                rms_norm(p["ln"], h), impl=impl), aux
    if kind == "rec":
        h = h + RGLRUBlock.apply(p["rec"], cfg, rms_norm(p["ln1"], h),
                                 impl=impl)
    else:
        h = h + _attn_cls(cfg).apply(
            p["attn"], cfg, rms_norm(p["ln1"], h), positions,
            window=_window_for(cfg, kind), impl=impl)
    if "xattn" in p and enc is not None:
        h = h + CrossAttention.apply(p["xattn"], cfg,
                                     rms_norm(p["ln_x"], h), enc, impl=impl)
    x = rms_norm(p["ln2"], h)
    if cfg.is_moe:
        h = h + MoE.apply(p["moe"], cfg, x)
        aux = MoE.aux_loss(p["moe"], cfg, x)
    else:
        h = h + mlp(p["mlp"], x, cfg.hidden_act)
    return h, aux


def _block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                 dtype) -> dict:
    if kind == "rwkv":
        return RWKV6Block.init_cache(cfg, batch, dtype)
    if kind == "rec":
        return RGLRUBlock.init_cache(cfg, batch, dtype)
    return _attn_cls(cfg).init_cache(cfg, batch, max_len,
                                     window=_window_for(cfg, kind),
                                     dtype=dtype)


def _block_decode(p: dict, cfg: ModelConfig, kind: str, h, cache, pos,
                  enc=None):
    if kind == "rwkv":
        return RWKV6Block.apply_decode(p["rwkv"], cfg,
                                       rms_norm(p["ln"], h), cache, pos)
    if kind == "rec":
        y, cache = RGLRUBlock.apply_decode(p["rec"], cfg,
                                           rms_norm(p["ln1"], h),
                                           cache, pos)
        h = h + y
    else:
        y, cache = _attn_cls(cfg).apply_decode(
            p["attn"], cfg, rms_norm(p["ln1"], h), cache, pos,
            window=_window_for(cfg, kind))
        h = h + y
    if "xattn" in p and enc is not None:
        h = h + CrossAttention.apply(p["xattn"], cfg,
                                     rms_norm(p["ln_x"], h), enc)
    x = rms_norm(p["ln2"], h)
    if cfg.is_moe:
        h = h + MoE.apply(p["moe"], cfg, x)
    else:
        h = h + mlp(p["mlp"], x, cfg.hidden_act)
    return h, cache


# ---------------------------------------------------------------------- #
# stage (= one repeat of the pattern) helpers
# ---------------------------------------------------------------------- #
def _stages(cfg: ModelConfig) -> tuple[tuple[str, ...], int, tuple[str, ...]]:
    pattern = tuple(cfg.layer_pattern)
    n_stages = cfg.n_layers // len(pattern)
    tail = pattern[: cfg.n_layers % len(pattern)]
    return pattern, n_stages, tail


def _stage_init(key, cfg: ModelConfig, pattern, dtype, cross=False) -> dict:
    keys = jax.random.split(key, len(pattern))
    return {f"b{i}_{kind}": _block_init(k, cfg, kind, dtype, cross=cross)
            for i, (kind, k) in enumerate(zip(pattern, keys))}


def _stage_apply(sp: dict, cfg: ModelConfig, pattern, h, positions,
                 enc=None, impl="auto"):
    aux = jnp.zeros((), jnp.float32)
    for i, kind in enumerate(pattern):
        h, a = _block_apply(sp[f"b{i}_{kind}"], cfg, kind, h, positions,
                            enc=enc, impl=impl)
        aux = aux + a
    return h, aux


# ---------------------------------------------------------------------- #
# params
# ---------------------------------------------------------------------- #
def init_params(cfg: ModelConfig, key, dtype=jnp.float32) -> dict:
    pattern, n_stages, tail = _stages(cfg)
    keys = jax.random.split(key, 8)
    cross = cfg.n_encoder_layers > 0
    params = {
        "embed": init_embedding(keys[0], cfg, dtype),
        "final_ln": init_rms_norm(cfg.d_model, dtype),
        "stages": jax.vmap(
            lambda k: _stage_init(k, cfg, pattern, dtype, cross=cross))(
            jax.random.split(keys[1], n_stages)),
    }
    if tail:
        params["tail"] = _stage_init(keys[2], cfg, tail, dtype, cross=cross)
    if cfg.n_encoder_layers:
        params["encoder"] = {
            "stages": jax.vmap(
                lambda k: _stage_init(k, cfg, ("attn",), dtype))(
                jax.random.split(keys[3], cfg.n_encoder_layers)),
            "final_ln": init_rms_norm(cfg.d_model, dtype),
        }
    if cfg.mtp_depth:
        params["mtp"] = _stage_init(keys[4], cfg,
                                    ("attn",) * cfg.mtp_depth, dtype)
        params["mtp_ln"] = init_rms_norm(cfg.d_model, dtype)
    return params


# ---------------------------------------------------------------------- #
# forward
# ---------------------------------------------------------------------- #
def _positions_for(cfg: ModelConfig, batch: dict, B: int, S: int):
    if cfg.mrope_sections is not None:
        if "mrope_pos" in batch:
            return batch["mrope_pos"]                 # [3, B, S]
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        return jnp.stack([pos, pos, pos])
    return jnp.broadcast_to(jnp.arange(S)[None], (B, S))


def _encode(cfg: ModelConfig, params: dict, frames: jax.Array,
            impl="auto") -> jax.Array:
    """Run the (non-causal) encoder over precomputed frame embeddings."""
    h = frames
    B, S, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def body(hc, sp):
        # encoder blocks are bidirectional: plain attention, no mask
        blk = sp["b0_attn"]
        y = GQA.apply_bidirectional(blk["attn"], cfg,
                                    rms_norm(blk["ln1"], hc), positions,
                                    impl=impl)
        hc = hc + y
        hc = hc + mlp(blk["mlp"], rms_norm(blk["ln2"], hc), cfg.hidden_act)
        return hc, None

    h, _ = jax.lax.scan(body, h, params["encoder"]["stages"])
    return rms_norm(params["encoder"]["final_ln"], h)


def _inputs_to_hidden(cfg: ModelConfig, params: dict, batch: dict):
    """Token embedding + modality frontend stubs."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    h = embed(params["embed"], cfg, tokens)
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(h.dtype)     # [B, N, d]
        n = pe.shape[1]
        h = jnp.concatenate([pe, h[:, n:]], axis=1)
    enc = None
    if cfg.n_encoder_layers and "frame_embeds" in batch:
        enc = _encode(cfg, params, batch["frame_embeds"].astype(h.dtype))
    return h, enc


def forward(cfg: ModelConfig, params: dict, batch: dict,
            impl: str = "auto",
            remat: bool = False) -> tuple[jax.Array, jax.Array]:
    """batch: {"tokens": [B,S], optional frontend inputs}.
    Returns (logits [B,S,V], moe_aux scalar).  With `remat`, each stage of
    the layer scan is checkpointed: backward recomputes the stage instead
    of keeping its internals stacked across all n_stages iterations (the
    difference between ~30 MB and ~500 GB of per-device residuals)."""
    pattern, n_stages, tail = _stages(cfg)
    h, enc = _inputs_to_hidden(cfg, params, batch)
    B, S = batch["tokens"].shape
    positions = _positions_for(cfg, batch, B, S)

    def body(carry, sp):
        hc, aux = carry
        # barrier: stops XLA hoisting per-stage f32 converts of the carry
        # out of the loop as one full [n_stages, ...] f32 stack (14 GB on
        # deepseek-v3 — §Perf iteration)
        hc = jax.lax.optimization_barrier(hc)
        hc = maybe_shard(hc, "data", None, None)
        hc, a = _stage_apply(sp, cfg, pattern, hc, positions, enc=enc,
                             impl=impl)
        hc = maybe_shard(hc, "data", None, None)
        return (hc, aux + a), None

    if remat:
        body = jax.checkpoint(body)
    (h, aux), _ = jax.lax.scan(
        body, (h, jnp.zeros((), jnp.float32)), params["stages"])
    if tail:
        h, a = _stage_apply(params["tail"], cfg, tail, h, positions,
                            enc=enc, impl=impl)
        aux = aux + a
    h = rms_norm(params["final_ln"], h)
    logits = maybe_shard(unembed(params["embed"], cfg, h),
                         "data", None, "model")
    return logits, aux


def loss_fn(cfg: ModelConfig, params: dict, batch: dict,
            impl: str = "auto", aux_weight: float = 0.01,
            mtp_weight: float = 0.3, remat: bool = False) -> jax.Array:
    """Next-token cross entropy (+ MoE aux + MTP head for DeepSeek)."""
    tokens = batch["tokens"]
    logits, aux = forward(cfg, params, batch, impl=impl, remat=remat)
    lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(lp, tokens[:, 1:, None], axis=-1)[..., 0]
    loss = nll.mean()
    if cfg.is_moe:
        loss = loss + aux_weight * aux
    if cfg.mtp_depth and "mtp" in params:
        # MTP: one extra block on the pre-head hidden predicts t+2
        h, enc = _inputs_to_hidden(cfg, params, batch)
        B, S = tokens.shape
        positions = _positions_for(cfg, batch, B, S)
        h2, _ = _stage_apply(params["mtp"], cfg,
                             ("attn",) * cfg.mtp_depth, h, positions,
                             impl=impl)
        logits2 = unembed(params["embed"], cfg,
                          rms_norm(params["mtp_ln"], h2))
        lp2 = jax.nn.log_softmax(logits2[:, :-2].astype(jnp.float32), -1)
        nll2 = -jnp.take_along_axis(lp2, tokens[:, 2:, None], -1)[..., 0]
        loss = loss + mtp_weight * nll2.mean()
    return loss


# ---------------------------------------------------------------------- #
# decode
# ---------------------------------------------------------------------- #
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.float32) -> dict:
    pattern, n_stages, tail = _stages(cfg)

    def stage_cache():
        return {f"b{i}_{kind}": _block_cache(cfg, kind, batch, max_len,
                                             dtype)
                for i, kind in enumerate(pattern)}

    one = stage_cache()
    stacked = jax.tree.map(
        lambda x: jnp.zeros((n_stages,) + x.shape, x.dtype), one)
    cache = {"stages": stacked}
    if tail:
        cache["tail"] = {f"b{i}_{kind}": _block_cache(
            cfg, kind, batch, max_len, dtype)
            for i, kind in enumerate(tail)}
    return cache


def decode_step(cfg: ModelConfig, params: dict, cache: dict,
                tokens: jax.Array, pos: jax.Array,
                enc: jax.Array | None = None) -> tuple[jax.Array, dict]:
    """tokens [B] (current token), pos scalar.  Returns (logits [B,V],
    new cache).  For enc-dec pass `enc` (from prefill/cache["enc"])."""
    pattern, n_stages, tail = _stages(cfg)
    if enc is None:
        enc = cache.get("enc")
    h = embed(params["embed"], cfg, tokens[:, None])

    def body(hc, sp_cache):
        sp, cc = sp_cache
        new_cc = {}
        for i, kind in enumerate(pattern):
            key = f"b{i}_{kind}"
            hc, new_cc[key] = _block_decode(sp[key], cfg, kind, hc,
                                            cc[key], pos, enc=enc)
        return hc, new_cc

    h, new_stage_cache = jax.lax.scan(
        body, h, (params["stages"], cache["stages"]))
    new_cache = dict(cache)
    new_cache["stages"] = new_stage_cache
    if tail:
        new_tail = {}
        for i, kind in enumerate(tail):
            key = f"b{i}_{kind}"
            h, new_tail[key] = _block_decode(params["tail"][key], cfg,
                                             kind, h, cache["tail"][key],
                                             pos, enc=enc)
        new_cache["tail"] = new_tail
    h = rms_norm(params["final_ln"], h)
    logits = unembed(params["embed"], cfg, h)
    return logits[:, 0], new_cache


def prefill(cfg: ModelConfig, params: dict, batch: dict, max_len: int,
            impl: str = "auto") -> tuple[jax.Array, dict]:
    """Process the full prompt, returning (last-position logits, cache).

    The prompt forward pass (the dominant prefill cost, and what the
    `prefill_*` dry-run cells lower) runs here; the returned cache starts
    empty and the serving loop replays the prompt through `decode_step`
    to populate it (see launch/serve.py) — correctness of that path is
    covered by the decode-vs-forward equivalence tests."""
    logits, _ = forward(cfg, params, batch, impl=impl)
    B, S = batch["tokens"].shape
    cache = init_cache(cfg, B, max_len,
                       dtype=params["final_ln"]["scale"].dtype)
    if cfg.n_encoder_layers and "frame_embeds" in batch:
        cache["enc"] = _encode(
            cfg, params, batch["frame_embeds"].astype(logits.dtype))
    return logits[:, -1], cache
