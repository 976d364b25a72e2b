"""Model forward passes: stage-scanned decoder (and encoder-decoder) stacks.

Each Stage is executed as one `jax.lax.scan` over its stacked block params —
HLO size stays O(block) regardless of depth, which keeps 72-layer/398B
configs compilable.  `jax.checkpoint` wraps the scan body (one block), so a
Stage with a K-sub-layer block natively gives the sqrt-remat pattern: one
saved carry per block, recompute inside.

Entry points:
  forward(...)        — full-sequence logits (training / prefill)
  decode_step(...)    — one token against caches
  init_caches(...)    — stacked per-stage cache pytrees
  lm_loss(...)        — next-token CE (+ MoE aux), vocab-sharding friendly
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import mamba as M
from repro.models.config import LayerSpec, ModelConfig, Stage


# ------------------------------------------------------------- sub-layer ---

def _sublayer(lp: Dict[str, Any], cfg: ModelConfig, spec: LayerSpec,
              x: jnp.ndarray, positions: jnp.ndarray,
              cache: Optional[Dict[str, Any]], enc_out: Optional[jnp.ndarray],
              ctx, impl: str):
    """One residual block: (attn | mamba) [+ cross-attn] + (mlp | moe)."""
    aux = jnp.float32(0.0)
    h = L.norm(lp, cfg, x, "ln1")
    if spec.kind == "attn":
        h, kv_new = L.attention(lp, cfg, spec, h, positions,
                                cache=_get(cache, "kv"), ctx=ctx, impl=impl)
    else:
        h, mcache = M.mamba_block(lp, cfg, h, cache=_get(cache, "ssm_cache"),
                                  ctx=ctx, use_kernel=(impl == "pallas_ssd"))
    if cfg.post_norm:
        h = L.norm(lp, cfg, h, "post1")
    x = x + h

    out_cache: Dict[str, Any] = {}
    if spec.kind == "attn" and cache is not None:
        out_cache["kv_new"] = kv_new  # committed post-scan (commit_kv)
    elif spec.kind == "mamba" and cache is not None:
        out_cache["ssm_cache"] = mcache

    if spec.cross:
        h = L.norm(lp, cfg, x, "ln_cross")
        # Prefill passes enc_out (cross K/V computed and cached); decode
        # passes enc_out=None and reads the cached projections.
        if enc_out is None:
            kv = (cache["cross"]["k"], cache["cross"]["v"])
        else:
            kv = L.encode_cross_kv(lp, cfg, enc_out)
        h = L.cross_attention(lp, cfg, h, kv, ctx=ctx)
        x = x + h
        if cache is not None:
            out_cache["cross"] = {"k": kv[0], "v": kv[1]}

    if spec.moe or cfg.d_ff > 0:  # mamba2-style layers have no MLP block
        h = L.norm(lp, cfg, x, "ln2")
        if spec.moe:
            h, a = L.moe_mlp(lp["moe"], cfg, h, ctx=ctx)
            aux = aux + a
        else:
            h = L.mlp(lp["mlp"], cfg, h, ctx=ctx)
        if cfg.post_norm:
            h = L.norm(lp, cfg, h, "post2")
        x = x + h
    return x, out_cache, aux


def _get(cache, key):
    if cache is None:
        return None
    return cache.get(key)


# ----------------------------------------------------------------- stage ---

def _stage_forward(sp: Dict[str, Any], cfg: ModelConfig, stage: Stage,
                   x: jnp.ndarray, positions: jnp.ndarray,
                   cache: Optional[Dict[str, Any]],
                   enc_out: Optional[jnp.ndarray], ctx, impl: str,
                   remat: bool):
    """Scan the stacked block.  cache leaves carry a leading (repeats,) dim."""

    def body(carry, xs):
        x, aux = carry
        layer_p, layer_cache = xs
        if getattr(ctx, "pin_gathers", False):
            # Pin FSDP weight all-gathers inside the loop: without this XLA
            # hoists loop-invariant gathers out of the (microbatch x layer)
            # scans and materializes EVERY layer's gathered weights at once
            # (~49 GB/device for jamba-398B; see EXPERIMENTS.md §Perf P8).
            layer_p = jax.lax.optimization_barrier(layer_p)
        new_cache: Dict[str, Any] = {}
        for i, spec in enumerate(stage.block):
            sub_cache = (layer_cache.get(f"sub{i}")
                         if isinstance(layer_cache, dict) else None)
            x, c_i, a_i = _sublayer(layer_p[f"sub{i}"], cfg, spec, x,
                                    positions, sub_cache, enc_out, ctx, impl)
            if c_i:
                new_cache[f"sub{i}"] = c_i
            aux = aux + a_i
        x = ctx.constrain(x, ("act_batch", "act_seq", "act_embed")) \
            if ctx is not None else x
        return (x, aux), new_cache

    if remat:
        body = jax.checkpoint(body)

    if stage.repeats == 1:
        # No scan needed; avoids degenerate (1,)-leading stacked ops.
        sp1 = jax.tree.map(lambda a: a[0], sp)
        c1 = None if cache is None else jax.tree.map(lambda a: a[0], cache)
        (x, aux), nc = body((x, jnp.float32(0.0)), (sp1, c1))
        ys = jax.tree.map(lambda a: a[None], nc)
    else:
        (x, aux), ys = jax.lax.scan(
            body, (x, jnp.float32(0.0)), (sp, cache))
    if cache is None:
        return x, None, aux
    new_cache = _commit_stage_cache(cfg, stage, cache, ys, positions, ctx)
    return x, new_cache, aux


def _commit_stage_cache(cfg: ModelConfig, stage: Stage, old_cache, ys,
                        positions, ctx):
    """Apply the deferred KV commits (one in-place write per stage) and pass
    through scan-produced mamba/cross cache entries."""
    aligned = bool(getattr(ctx, "aligned_decode", False))
    new_cache: Dict[str, Any] = {}
    for i, spec in enumerate(stage.block):
        e_old = old_cache.get(f"sub{i}", {})
        e_ys = ys.get(f"sub{i}", {}) if isinstance(ys, dict) else {}
        entry: Dict[str, Any] = {}
        if "kv_new" in e_ys:
            kvn = e_ys["kv_new"]  # k/v: (L, B, H, T, D)
            entry["kv"] = L.commit_kv(e_old["kv"], kvn["k"], kvn["v"],
                                      positions, aligned=aligned)
        if "ssm_cache" in e_ys:
            entry["ssm_cache"] = e_ys["ssm_cache"]
        if "cross" in e_ys:
            entry["cross"] = e_ys["cross"]
        elif "cross" in e_old:
            entry["cross"] = e_old["cross"]
        if entry:
            new_cache[f"sub{i}"] = entry
    return new_cache


# ----------------------------------------------------------------- model ---

def _embed(params, cfg: ModelConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def _head(params, cfg: ModelConfig, x: jnp.ndarray, ctx) -> jnp.ndarray:
    x = L.norm(params, cfg, x, "final")
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ w.astype(x.dtype)).astype(jnp.float32)
    if cfg.final_softcap > 0:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    if cfg.padded_vocab != cfg.vocab_size:  # mask vocab-padding rows
        pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(pad_mask, logits, -2.0e38)
    if ctx is not None:
        logits = ctx.constrain(logits, ("act_batch", "act_seq", "act_vocab"))
    return logits


def encode(params, cfg: ModelConfig, frames: jnp.ndarray, ctx=None,
           impl: str = "xla") -> jnp.ndarray:
    """Encoder stack over stub frame embeddings (B, S, d)."""
    x = frames.astype(jnp.dtype(cfg.dtype))
    if cfg.learned_pos and "enc_pos_embed" in params:
        s = x.shape[1]
        x = x + params["enc_pos_embed"][:s].astype(x.dtype)
    pos = jnp.broadcast_to(jnp.arange(x.shape[1], dtype=jnp.int32)[None],
                           x.shape[:2])
    for i, st in enumerate(cfg.enc_stages):
        x, _, _ = _stage_forward(params["enc_stages"][f"stage{i}"], cfg, st,
                                 x, pos, None, None, ctx, impl, remat=True)
    return L.norm(params, cfg, x, "enc_final")


def forward(params, cfg: ModelConfig, tokens: jnp.ndarray, *,
            frontend: Optional[jnp.ndarray] = None,
            enc_out: Optional[jnp.ndarray] = None,
            positions: Optional[jnp.ndarray] = None,
            caches: Optional[Dict[str, Any]] = None,
            ctx=None, impl: str = "xla", remat: bool = True):
    """Full-sequence forward.  tokens: (B, T) int32.

    frontend: (B, Nf, d) precomputed patch embeddings (VLM) prepended to the
    token embeddings.  enc_out: (B, S, d) encoder output (enc-dec).
    Returns (logits (B, T', V) f32, new_caches, aux) with
    T' = Nf + T for VLM, T otherwise.
    """
    x = _embed(params, cfg, tokens)
    if frontend is not None:
        x = jnp.concatenate([frontend.astype(x.dtype), x], axis=1)
    b, t, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None],
                                     (b, t))
    if cfg.learned_pos:
        x = x + params["pos_embed"][positions].astype(x.dtype)
    if ctx is not None:
        x = ctx.constrain(x, ("act_batch", "act_seq", "act_embed"))

    aux = jnp.float32(0.0)
    new_caches: Dict[str, Any] = {}
    for i, st in enumerate(cfg.stages):
        stage_cache = None if caches is None else caches[f"stage{i}"]
        x, nc, a = _stage_forward(params["stages"][f"stage{i}"], cfg, st, x,
                                  positions, stage_cache, enc_out, ctx, impl,
                                  remat)
        aux = aux + a
        if nc is not None:
            new_caches[f"stage{i}"] = nc
    logits = _head(params, cfg, x, ctx)
    return logits, (new_caches or None), aux


def decode_step(params, cfg: ModelConfig, tokens: jnp.ndarray,
                lengths: jnp.ndarray, caches: Dict[str, Any], *,
                ctx=None) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """One decode step: tokens (B, 1), lengths (B,) current cache lengths.
    Returns (logits (B, 1, V), new_caches)."""
    positions = lengths[:, None].astype(jnp.int32)
    logits, new_caches, _ = forward(params, cfg, tokens, positions=positions,
                                    caches=caches, ctx=ctx, impl="xla",
                                    remat=False)
    return logits, new_caches


# ---------------------------------------------------------------- caches ---

def init_caches(cfg: ModelConfig, batch: int, max_len: int, dtype=None,
                enc_len: int = 0,
                kv_heads: Optional[int] = None) -> Dict[str, Any]:
    """Stacked cache pytree matching the stage structure.  kv_heads overrides
    the stored head count (GQA-expanded caches under TP; see layers)."""
    dt = jnp.dtype(dtype or cfg.dtype)
    caches: Dict[str, Any] = {}
    for i, st in enumerate(cfg.stages):
        sub: Dict[str, Any] = {}
        for j, spec in enumerate(st.block):
            entry: Dict[str, Any] = {}
            if spec.kind == "attn":
                kv = L.init_kv_cache(cfg, spec, batch, max_len, dt,
                                     kv_heads=kv_heads)
                entry["kv"] = _stack_tree(kv, st.repeats)
            else:
                mc = M.init_mamba_cache(cfg, batch, dt)
                entry["ssm_cache"] = _stack_tree(mc, st.repeats)
            if spec.cross:
                s = enc_len or cfg.num_audio_frames
                z = jnp.zeros((st.repeats, batch, cfg.num_kv_heads, s,
                               cfg.head_dim), dt)
                entry["cross"] = {"k": z, "v": z}
            sub[f"sub{j}"] = entry
        caches[f"stage{i}"] = sub
    return caches


def _stack_tree(tree, repeats: int):
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (repeats,) + a.shape), tree)


def abstract_caches(cfg: ModelConfig, batch: int, max_len: int, dtype=None,
                    enc_len: int = 0, kv_heads: Optional[int] = None):
    """ShapeDtypeStruct view of init_caches — dry-run path, no allocation."""
    return jax.eval_shape(
        lambda: init_caches(cfg, batch, max_len, dtype, enc_len, kv_heads))


def cache_axes(cfg: ModelConfig):
    """Logical sharding axes mirroring the init_caches structure."""
    axes: Dict[str, Any] = {}
    for i, st in enumerate(cfg.stages):
        sub: Dict[str, Any] = {}
        for j, spec in enumerate(st.block):
            entry: Dict[str, Any] = {}
            if spec.kind == "attn":
                entry["kv"] = {
                    "k": ("layers", "act_batch", "kv_heads", "act_cache", None),
                    "v": ("layers", "act_batch", "kv_heads", "act_cache", None),
                    "pos": ("layers", "act_batch", "act_cache"),
                }
            else:
                entry["ssm_cache"] = {
                    "ssm": ("layers", "act_batch", "ssm_heads", None, None),
                    "conv": ("layers", "act_batch", None, "ssm_inner"),
                }
            if spec.cross:
                entry["cross"] = {
                    "k": ("layers", "act_batch", "kv_heads", None, None),
                    "v": ("layers", "act_batch", "kv_heads", None, None),
                }
            sub[f"sub{j}"] = entry
        axes[f"stage{i}"] = sub
    return axes


# ------------------------------------------------------------------ loss ---

def lm_loss(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray], *,
            ctx=None, impl: str = "xla", remat: bool = True,
            aux_weight: float = 0.01):
    """Next-token cross-entropy.  batch: tokens (B,T), labels (B,T) with -1
    for ignored positions, optional frontend/frames.

    The label log-prob is taken with a one-hot einsum, which stays sharded
    when the vocab axis is model-sharded (no logits all-gather).
    """
    enc_out = None
    if cfg.is_encdec:
        enc_out = encode(params, cfg, batch["frames"], ctx=ctx, impl=impl)
    logits, _, aux = forward(params, cfg, batch["tokens"],
                             frontend=batch.get("frontend"),
                             enc_out=enc_out, ctx=ctx, impl=impl, remat=remat)
    labels = batch["labels"]
    if cfg.num_frontend_tokens and batch.get("frontend") is not None:
        logits = logits[:, batch["frontend"].shape[1]:]
    valid = (labels >= 0)
    labels_c = jnp.clip(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)                      # (B, T)
    onehot = jax.nn.one_hot(labels_c, cfg.padded_vocab, dtype=logits.dtype)
    ll = jnp.einsum("btv,btv->bt", logits, onehot)
    ce = jnp.where(valid, logz - ll, 0.0)
    ntok = jnp.maximum(jnp.sum(valid), 1)
    loss = jnp.sum(ce) / ntok
    total = loss + aux_weight * aux
    return total, {"ce": loss, "moe_aux": aux, "ntokens": ntok}
