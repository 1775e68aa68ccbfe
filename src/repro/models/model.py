"""Model assembly: init, sharding specs, forward, loss, decode.

One generic stack covers all ten assigned archs via ArchConfig:
  * layers are grouped into period-patterns (Jamba: 8-layer groups of
    7 mamba + 1 attn, MoE on odd layers) and scanned over groups with
    stacked params + remat — HLO stays O(period) regardless of depth.
  * q-heads are padded to a multiple of the model axis where needed
    (DESIGN.md §5); padded heads are masked before o_proj, which keeps the
    function exactly equal to the unpadded model while remaining shardable.
  * vocab is padded to a multiple of 128; padded logits are masked in the
    chunked cross-entropy.

Params and caches are plain nested dicts; ``param_specs``/``cache_specs``
mirror their structure with PartitionSpecs by leaf-name rules.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..sharding.rules import MeshCtx, logical_to_spec
from .attention import attention, decode_attention, nystrom_attention
from .config import ArchConfig
from .layers import (apply_mrope, apply_rope, lowp, mlp_apply, mlp_init,
                     ninit, rms_norm, sinusoidal_pos)
from .mamba2 import mamba_block, mamba_decode, mamba_init
from .moe import moe_apply, moe_init

Array = jax.Array
TP = 16  # model-axis width of the production mesh


def padded_vocab(cfg: ArchConfig) -> int:
    return (cfg.vocab_size + 127) // 128 * 128


def _dtype(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# =============================================================================
# init
# =============================================================================


def _attn_init(key: Array, cfg: ArchConfig, dtype) -> dict:
    hp = cfg.padded_heads(TP)
    kvp = hp if cfg.n_kv_heads == cfg.n_heads else cfg.n_kv_heads  # pad MHA kv too
    ks = jax.random.split(key, 6)
    p = {
        "wq": ninit(ks[0], (cfg.d_model, hp * cfg.head_dim), dtype=dtype),
        "wk": ninit(ks[1], (cfg.d_model, kvp * cfg.head_dim), dtype=dtype),
        "wv": ninit(ks[2], (cfg.d_model, kvp * cfg.head_dim), dtype=dtype),
        "wo": ninit(ks[3], (hp * cfg.head_dim, cfg.d_model), dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((cfg.head_dim,), dtype)
        p["k_norm"] = jnp.zeros((cfg.head_dim,), dtype)
    return p


def _block_init(key: Array, cfg: ArchConfig, j: int, dtype) -> dict:
    kmix, kmlp, k3 = jax.random.split(key, 3)
    p: dict[str, Any] = {"ln_mix": jnp.zeros((cfg.d_model,), dtype)}
    if cfg.mixer_kind(j) == "attn":
        p["attn"] = _attn_init(kmix, cfg, dtype)
    else:
        p["mamba"] = mamba_init(kmix, cfg, dtype)
    kind = cfg.mlp_kind(j)
    if kind != "none":
        p["ln_mlp"] = jnp.zeros((cfg.d_model,), dtype)
        if kind == "moe":
            p["moe"] = moe_init(kmlp, cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.mlp_act,
                                cfg.shared_expert_ff, dtype)
        else:
            p["mlp"] = mlp_init(kmlp, cfg.d_model, cfg.d_ff, cfg.mlp_act, dtype)
    return p


def init_params(cfg: ArchConfig, key: Array) -> dict:
    dtype = _dtype(cfg)
    vp = padded_vocab(cfg)
    ke, ko, kb = jax.random.split(key, 3)
    params: dict[str, Any] = {"final_norm": jnp.zeros((cfg.d_model,), dtype)}
    if cfg.embed_inputs:
        # 1/sqrt(d) keeps tied-head logits O(1) at init (RMSNorm rescales
        # the residual stream immediately, so forward magnitudes are safe)
        params["embed"] = ninit(ke, (vp, cfg.d_model), scale=cfg.d_model**-0.5,
                                dtype=dtype)
    if not cfg.tie_embeddings or not cfg.embed_inputs:
        params["out_head"] = ninit(ko, (cfg.d_model, vp), dtype=dtype)

    period, groups = cfg.layer_period, cfg.n_groups
    blocks: dict[str, Any] = {}
    for j in range(period):
        keys = jax.random.split(jax.random.fold_in(kb, j), groups)
        per_group = [_block_init(keys[g], cfg, j, dtype) for g in range(groups)]
        blocks[f"blk{j}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per_group)
    params["blocks"] = blocks
    return params


# =============================================================================
# sharding specs (leaf-name rules)
# =============================================================================

_SPEC_RULES: dict[str, tuple[Optional[str], ...]] = {
    # attention
    "wq": ("fsdp", "model"), "wk": ("fsdp", "model"), "wv": ("fsdp", "model"),
    "wo": ("model", "fsdp"),
    # mlp
    "w_gate": ("fsdp", "model"), "w_up": ("fsdp", "model"), "w_down": ("model", "fsdp"),
    # mamba
    "in_proj": ("fsdp", "model"), "out_proj": ("model", "fsdp"),
    "conv_w": (None, "model"),
    # io
    "embed": ("model", "fsdp"), "out_head": ("fsdp", "model"),
    "router": (None, None),
}


def _moe_spec(cfg: ArchConfig, name: str) -> tuple[Optional[str], ...]:
    mode = cfg.moe_mode(TP)
    if name in ("w_gate", "w_up"):
        return {"ep": ("model", "fsdp", None), "tp": (None, "fsdp", "model"),
                "replicate": (None, "fsdp", None)}[mode]
    return {"ep": ("model", None, "fsdp"), "tp": (None, "model", "fsdp"),
            "replicate": (None, None, "fsdp")}[mode]


def param_specs(cfg: ArchConfig, ctx: MeshCtx) -> Any:
    """PartitionSpec pytree mirroring init_params' structure."""
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))

    def spec_of(path, leaf) -> P:
        keys = [p.key for p in path if hasattr(p, "key")]
        name = keys[-1]
        in_moe = "moe" in keys and "shared" not in keys  # shared expert = dense MLP
        stacked = keys and keys[0] == "blocks"
        if in_moe and name in ("w_gate", "w_up", "w_down"):
            logical = _moe_spec(cfg, name)
        elif name in _SPEC_RULES:
            logical = _SPEC_RULES[name]
        else:
            logical = (None,) * (leaf.ndim - (1 if stacked else 0))
        if stacked:
            logical = (None,) + logical
        assert len(logical) == leaf.ndim, (keys, leaf.shape, logical)
        return logical_to_spec(*logical, ctx=ctx)

    return jax.tree_util.tree_map_with_path(spec_of, params)


# =============================================================================
# forward
# =============================================================================


def _attn_mixer(p: dict, cfg: ArchConfig, x: Array, positions: Array,
                mrope_pos: Optional[Array]) -> Array:
    b, s, _ = x.shape
    hp = cfg.padded_heads(TP)
    hd = cfg.head_dim
    q = lowp(x @ p["wq"]).reshape(b, s, hp, hd)
    k = lowp(x @ p["wk"]).reshape(b, s, -1, hd)
    v = lowp(x @ p["wv"]).reshape(b, s, -1, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.pos == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif cfg.pos == "mrope":
        q = apply_mrope(q, mrope_pos, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, mrope_pos, cfg.rope_theta, cfg.mrope_sections)
    if cfg.attention_impl == "bless_nystrom" and s > cfg.nystrom_landmarks:
        out = nystrom_attention(q, k, v, landmarks=cfg.nystrom_landmarks)
    else:
        out = attention(q, k, v, causal=cfg.causal, chunk=cfg.attn_chunk,
                        softcap=cfg.attn_logit_softcap)
    if hp != cfg.n_heads:  # mask padded q-heads: exact, shard-friendly
        mask = (jnp.arange(hp) < cfg.n_heads).astype(out.dtype)
        out = out * mask[None, None, :, None]
    return out.reshape(b, s, hp * hd) @ p["wo"]


def _block_apply(p: dict, cfg: ArchConfig, j: int, x: Array, positions: Array,
                 mrope_pos: Optional[Array]) -> Array:
    # optimization_barrier after each residual update pins the bf16 dtype at
    # the TP psum: without it XLA hoists the next norm's f32 upcast across
    # the all-reduce, doubling fwd collective bytes (EXPERIMENTS.md §Perf)
    h = rms_norm(x, p["ln_mix"], cfg.norm_eps)
    if cfg.mixer_kind(j) == "attn":
        x = x + _attn_mixer(p["attn"], cfg, h, positions, mrope_pos)
    else:
        x = x + mamba_block(p["mamba"], cfg, h)
    x = jax.lax.optimization_barrier(x)
    kind = cfg.mlp_kind(j)
    if kind == "none":
        return x
    h = rms_norm(x, p["ln_mlp"], cfg.norm_eps)
    if kind == "moe":
        x = x + moe_apply(p["moe"], h, top_k=cfg.top_k, n_experts=cfg.n_experts,
                          act=cfg.mlp_act, capacity_factor=cfg.capacity_factor,
                          ep=cfg.moe_ep(TP))
    else:
        x = x + mlp_apply(p["mlp"], h, cfg.mlp_act)
    return jax.lax.optimization_barrier(x)


def _embed_in(params: dict, cfg: ArchConfig, batch: dict) -> Array:
    if not cfg.embed_inputs:  # audio: precomputed frame embeddings (stub frontend)
        x = batch["frames"].astype(_dtype(cfg))
        return x + sinusoidal_pos(x.shape[1], cfg.d_model, x.dtype)[None]
    x = params["embed"][batch["tokens"]]
    if cfg.extra_image_tokens:  # vlm: patch embeds occupy a static prefix
        n = cfg.extra_image_tokens
        x = jnp.concatenate([batch["pixel_embeds"].astype(x.dtype), x[:, n:]], axis=1)
    return x


def forward(params: dict, cfg: ArchConfig, batch: dict) -> Array:
    """Full-sequence forward -> final hidden states (B, S, d)."""
    from ..sharding.rules import shard

    x = _embed_in(params, cfg, batch)
    x = shard(x, "batch", None, None)  # residual stream: batch-sharded
    b, s, _ = x.shape
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    mrope_pos = batch.get("mrope_positions")

    period = cfg.layer_period

    def one_block(x, bparams, j):
        return _block_apply(bparams, cfg, j, x, positions, mrope_pos)

    if cfg.remat:
        # remat per *layer*, not per period-group: a group-level checkpoint
        # would make the backward materialize all `period` layers'
        # intermediates at once (5x live memory for Jamba's 8-layer groups
        # — EXPERIMENTS.md §Perf iteration 10)
        one_block = jax.checkpoint(one_block, static_argnums=(2,),
                                   policy=jax.checkpoint_policies.nothing_saveable)

    def group_body(x, gparams):
        for j in range(period):
            x = one_block(x, gparams[f"blk{j}"], j)
        return x

    def scan_fn(x, gparams):
        return group_body(x, gparams), None

    x, _ = jax.lax.scan(scan_fn, x, params["blocks"])
    return rms_norm(x, params["final_norm"], cfg.norm_eps)


def logits_fn(params: dict, cfg: ArchConfig, h: Array) -> Array:
    w = params["embed"].T if cfg.tie_embeddings else params["out_head"]
    return h @ w


def loss_fn(params: dict, cfg: ArchConfig, batch: dict, *, n_chunks: int = 8) -> Array:
    """Chunked softmax cross-entropy: logits materialize one *sequence*
    chunk at a time ((B, S/n, Vp) per step, batch- and vocab-sharded) —
    never the full (B, S, Vp). Chunking over S keeps the batch axis
    sharding intact through every reshape."""
    from ..sharding.rules import shard

    h = forward(params, cfg, batch)
    b, s, d = h.shape
    w = (params["embed"].T if cfg.tie_embeddings else params["out_head"])
    vp = w.shape[1]
    n_chunks = min(n_chunks, s)
    assert s % n_chunks == 0, (s, n_chunks)
    sc = s // n_chunks
    valid_v = jnp.arange(vp) < cfg.vocab_size

    def per_chunk(args):
        hc, lc = args  # (B, sc, d), (B, sc)
        logits = (hc @ w).astype(jnp.float32)
        logits = shard(logits, "batch", None, "model")
        logits = jnp.where(valid_v[None, None, :], logits, -1e30)  # padded vocab
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lc[..., None], axis=2)[..., 0] - lse
        return -jnp.sum(ll)

    hc = jnp.moveaxis(h.reshape(b, n_chunks, sc, d), 1, 0)
    lc = jnp.moveaxis(batch["labels"].reshape(b, n_chunks, sc), 1, 0)
    losses = jax.lax.map(per_chunk, (hc, lc))
    return jnp.sum(losses) / (b * s)


# =============================================================================
# decode (KV / SSM caches)
# =============================================================================


def init_cache(cfg: ArchConfig, batch_size: int, max_len: int,
               dtype=None) -> dict:
    """Cache pytree: per period-position j, stacked over groups."""
    dtype = dtype or _dtype(cfg)
    g = cfg.n_groups
    kvp = (cfg.padded_heads(TP) if cfg.n_kv_heads == cfg.n_heads else cfg.n_kv_heads)
    cache: dict[str, Any] = {}
    for j in range(cfg.layer_period):
        if cfg.mixer_kind(j) == "attn":
            cache[f"blk{j}"] = {
                "k": jnp.zeros((g, batch_size, max_len, kvp, cfg.head_dim), dtype),
                "v": jnp.zeros((g, batch_size, max_len, kvp, cfg.head_dim), dtype),
            }
        else:
            cache[f"blk{j}"] = {
                "conv": jnp.zeros((g, batch_size, cfg.ssm_conv - 1,
                                   cfg.d_inner + 2 * cfg.ssm_state), dtype),
                "state": jnp.zeros((g, batch_size, cfg.ssm_heads, cfg.ssm_headdim,
                                    cfg.ssm_state), jnp.float32),
            }
    return cache


def cache_specs(cfg: ArchConfig, ctx: MeshCtx, *, seq_logical: str = "none") -> Any:
    """Sharding for the cache. seq_logical: 'none' (replicated seq),
    'seq_shard' (data) or 'seq_shard_wide' (data+model) for long-context."""
    cache = jax.eval_shape(lambda: init_cache(cfg, 1, 8))

    def spec_of(path, leaf) -> P:
        name = path[-1].key
        if name in ("k", "v"):
            return logical_to_spec(None, "batch", seq_logical, None, None, ctx=ctx)
        if name == "conv":
            return logical_to_spec(None, "batch", None, "model", ctx=ctx)
        if name == "state":
            return logical_to_spec(None, "batch", "model", None, None, ctx=ctx)
        return logical_to_spec(*([None] * leaf.ndim), ctx=ctx)

    return jax.tree_util.tree_map_with_path(spec_of, cache)


def _attn_decode(p: dict, cfg: ArchConfig, x: Array, cache: dict, pos: Array,
                 length: Optional[Array], mrope_pos: Optional[Array]) -> tuple[Array, dict]:
    b = x.shape[0]
    hp = cfg.padded_heads(TP)
    hd = cfg.head_dim
    q = (x @ p["wq"]).reshape(b, 1, hp, hd)
    k = (x @ p["wk"]).reshape(b, 1, -1, hd)
    v = (x @ p["wv"]).reshape(b, 1, -1, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    pos_b = jnp.broadcast_to(jnp.asarray(pos).reshape(-1, 1), (b, 1))
    if cfg.pos == "rope":
        q = apply_rope(q, pos_b, cfg.rope_theta)
        k = apply_rope(k, pos_b, cfg.rope_theta)
    elif cfg.pos == "mrope":
        q = apply_mrope(q, mrope_pos, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, mrope_pos, cfg.rope_theta, cfg.mrope_sections)
    s_max = cache["k"].shape[1]
    slot = (pos_b[:, 0] % s_max).astype(jnp.int32)  # per-slot write position
    bidx = jnp.arange(b)
    kc = cache["k"].at[bidx, slot].set(k[:, 0].astype(cache["k"].dtype))
    vc = cache["v"].at[bidx, slot].set(v[:, 0].astype(cache["v"].dtype))
    out = decode_attention(q, kc, vc, softcap=cfg.attn_logit_softcap, length=length)
    if hp != cfg.n_heads:
        mask = (jnp.arange(hp) < cfg.n_heads).astype(out.dtype)
        out = out * mask[None, None, :, None]
    out = out.reshape(b, 1, hp * hd) @ p["wo"]
    return out, {"k": kc, "v": vc}


def decode_step(params: dict, cfg: ArchConfig, cache: dict, token: Array, pos: Array,
                *, length: Optional[Array] = None,
                mrope_pos: Optional[Array] = None) -> tuple[Array, dict]:
    """One decode step. token (B,) int32; pos () int32. Returns
    (logits (B, Vp), new cache)."""
    assert cfg.has_decode, f"{cfg.name} is encoder-only"
    x = params["embed"][token][:, None, :]  # (B, 1, d)

    period = cfg.layer_period
    new_cache: dict[str, Any] = {}

    def group_body(x, slices):
        gparams, gcache = slices
        outc = {}
        for j in range(period):
            p = gparams[f"blk{j}"]
            h = rms_norm(x, p["ln_mix"], cfg.norm_eps)
            if cfg.mixer_kind(j) == "attn":
                out, c = _attn_decode(p["attn"], cfg, h[:, 0], gcache[f"blk{j}"], pos,
                                      length, mrope_pos)
            else:
                out, c = mamba_decode(p["mamba"], cfg, h, gcache[f"blk{j}"])
            x = x + out
            outc[f"blk{j}"] = c
            kind = cfg.mlp_kind(j)
            if kind != "none":
                h = rms_norm(x, p["ln_mlp"], cfg.norm_eps)
                if kind == "moe":
                    x = x + moe_apply(p["moe"], h, top_k=cfg.top_k,
                                      n_experts=cfg.n_experts, act=cfg.mlp_act,
                                      capacity_factor=cfg.capacity_factor,
                                      ep=cfg.moe_ep(TP))
                else:
                    x = x + mlp_apply(p["mlp"], h, cfg.mlp_act)
        return x, outc

    x, new_cache = jax.lax.scan(group_body, x, (params["blocks"], cache))
    h = rms_norm(x[:, 0], params["final_norm"], cfg.norm_eps)
    return logits_fn(params, cfg, h), new_cache
