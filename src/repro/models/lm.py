"""Unified LM covering all 10 assigned architectures.

Families:
  dense   — GQA transformer (phi4, gemma3, qwen2, starcoder2)
  moe     — mixtral (every-layer MoE, TP experts), llama4 (alt-layer MoE, EP)
  ssm     — mamba2 (SSD)
  hybrid  — zamba2 (mamba2 backbone + weight-shared attention block)
  encdec  — whisper (stub frame embeddings)
  vlm     — llama-3.2-vision (1 gated cross-attn layer per 5)

Parameters are (params, specs) pytrees; specs leaves are logical-axis tuples
consumed by repro.sharding.  Layer stacks use lax.scan with jax.checkpoint
(large archs) or are unrolled (small/heterogeneous: gemma3, zamba2).

Modes:
  train_loss(cfg, ctx, params, batch) -> scalar loss
  prefill(cfg, ctx, params, batch)    -> (last_logits, cache)
  decode_step(cfg, ctx, params, cache, tokens, pos) -> (logits, cache)
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import ssd
from repro.models.common import (Initializer, apply_rope, gelu,
                                 rms_norm, rope_at, split_tree, swiglu)

# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------


def _attn_params(ini: Initializer, cfg: ModelConfig, *, cross=False):
    d, hd = cfg.d_model, cfg.head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    t = {
        "norm": ini.zeros((d,), ("embed",)),
        "wq": ini.dense((d, h, hd), ("embed", "heads", "head")),
        "wk": ini.dense((d, kv, hd), ("embed", "kv_heads", "head")),
        "wv": ini.dense((d, kv, hd), ("embed", "kv_heads", "head")),
        "wo": ini.dense((h, hd, d), ("heads", "head", "embed"),
                        std=1.0 / np.sqrt(h * hd * 2 * cfg.num_layers)),
    }
    if cfg.qkv_bias:
        t["bq"] = ini.zeros((h, hd), ("heads", "head"))
        t["bk"] = ini.zeros((kv, hd), ("kv_heads", "head"))
        t["bv"] = ini.zeros((kv, hd), ("kv_heads", "head"))
    if cfg.qk_norm:
        t["q_norm"] = ini.zeros((hd,), ("head",))
        t["k_norm"] = ini.zeros((hd,), ("head",))
    if cross:
        t["gate"] = ini.zeros((), None)  # tanh-gated cross-attn (llama3.2)
        t["kv_norm"] = ini.zeros((d,), ("embed",))
    return t


def _mlp_params(ini: Initializer, cfg: ModelConfig):
    d, f = cfg.d_model, cfg.d_ff
    t = {
        "norm": ini.zeros((d,), ("embed",)),
        "wi": ini.dense((d, f), ("embed", "mlp")),
        "wo": ini.dense((f, d), ("mlp", "embed"),
                        std=1.0 / np.sqrt(f * 2 * cfg.num_layers)),
    }
    if cfg.act == "swiglu":
        t["wg"] = ini.dense((d, f), ("embed", "mlp"))
    return t


def _moe_params(ini: Initializer, cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ep = cfg.moe_partition == "expert"
    w_axes = ("expert", "embed", None) if ep else (None, "embed", "mlp")
    o_axes = ("expert", "mlp", "embed") if ep else (None, "mlp", "embed")
    # EP keeps d_ff unsharded; TP shards d_ff over "model".
    if ep:
        o_axes = ("expert", None, "embed")
    t = {
        "norm": ini.zeros((d,), ("embed",)),
        "router": ini.dense((d, e), ("embed", "expert"), std=0.02),
        "wi": ini.dense((e, d, f), w_axes, std=1.0 / np.sqrt(d)),
        "wg": ini.dense((e, d, f), w_axes, std=1.0 / np.sqrt(d)),
        "wo": ini.dense((e, f, d), o_axes,
                        std=1.0 / np.sqrt(f * 2 * cfg.num_layers)),
    }
    return t


def _mamba_params(ini: Initializer, cfg: ModelConfig):
    d = cfg.d_model
    di, gn, h = cfg.d_inner, cfg.ssm_ngroups * cfg.ssm_state, cfg.ssm_nheads
    w = cfg.ssm_conv_width
    t = {
        "norm": ini.zeros((d,), ("embed",)),
        "wz": ini.dense((d, di), ("embed", "mlp")),
        "wx": ini.dense((d, di), ("embed", "mlp")),
        "wB": ini.dense((d, gn), ("embed", None)),
        "wC": ini.dense((d, gn), ("embed", None)),
        "wdt": ini.dense((d, h), ("embed", "ssm_heads")),
        "conv_x_w": ini.dense((w, di), (None, "mlp"), std=0.3),
        "conv_x_b": ini.zeros((di,), ("mlp",)),
        "conv_B_w": ini.dense((w, gn), (None, None), std=0.3),
        "conv_B_b": ini.zeros((gn,), (None,)),
        "conv_C_w": ini.dense((w, gn), (None, None), std=0.3),
        "conv_C_b": ini.zeros((gn,), (None,)),
        "A_log": ini.const(np.log(np.linspace(1.0, 16.0, h)), ("ssm_heads",)),
        "D": ini.ones((h,), ("ssm_heads",)),
        "dt_bias": ini.const(np.log(np.expm1(np.linspace(1e-3, 0.1, h))),
                             ("ssm_heads",)),
        "gnorm": ini.zeros((di,), ("mlp",)),
        "wout": ini.dense((di, d), ("mlp", "embed"),
                          std=1.0 / np.sqrt(di * 2 * cfg.num_layers)),
    }
    return t


def _block_params(ini, cfg, kind: str):
    if kind == "dense":
        return {"attn": _attn_params(ini, cfg), "mlp": _mlp_params(ini, cfg)}
    if kind == "moe":
        return {"attn": _attn_params(ini, cfg), "moe": _moe_params(ini, cfg)}
    if kind == "mamba":
        return {"mamba": _mamba_params(ini, cfg)}
    if kind == "cross":
        return {"attn": _attn_params(ini, cfg, cross=True),
                "mlp": _mlp_params(ini, cfg)}
    if kind == "encoder":
        return {"attn": _attn_params(ini, cfg), "mlp": _mlp_params(ini, cfg)}
    if kind == "decoder":  # whisper decoder layer: self + cross + mlp
        return {"attn": _attn_params(ini, cfg),
                "xattn": _attn_params(ini, cfg, cross=True),
                "mlp": _mlp_params(ini, cfg)}
    raise ValueError(kind)


def _stack(trees):
    """Stack a list of (param,spec) trees along a new leading 'layers' dim."""

    def is_leaf(x):
        return isinstance(x, tuple) and len(x) == 2 and not isinstance(x[0], dict)

    out = {}
    first = trees[0]
    for name in first:
        if isinstance(first[name], dict):
            out[name] = _stack([t[name] for t in trees])
        else:
            arrs = jnp.stack([t[name][0] for t in trees])
            spec = ("layers",) + tuple(first[name][1] or ())
            out[name] = (arrs, spec)
    return out


def init_params(cfg: ModelConfig, key: jax.Array):
    """Returns (params, specs).  Use jax.eval_shape for the full configs."""
    dtype = jnp.dtype(cfg.param_dtype)
    ini = Initializer(key, dtype)
    d = cfg.d_model
    tree: dict[str, Any] = {
        "embed": ini.embed((cfg.vocab_size, d), ("vocab", "embed")),
        "final_norm": ini.zeros((d,), ("embed",)),
    }
    if not cfg.tie_embeddings:
        tree["lm_head"] = ini.dense((d, cfg.vocab_size), ("embed", "vocab"))

    fam = cfg.family
    if fam in ("dense", "moe"):
        plan = layer_plan(cfg)
        if cfg.scan_layers:
            kinds = sorted(set(plan))
            if len(kinds) == 1:
                tree["stack"] = _stack(
                    [_block_params(ini, cfg, plan[0]) for _ in plan])
            else:  # llama4 [dense, moe] alternation: one stack per kind
                n = len(plan) // len(kinds)
                tree["stack_a"] = _stack(
                    [_block_params(ini, cfg, plan[0]) for _ in range(n)])
                tree["stack_b"] = _stack(
                    [_block_params(ini, cfg, plan[1]) for _ in range(n)])
        else:
            tree["layers"] = [
                _block_params(ini, cfg, k) for k in plan]
    elif fam == "ssm":
        tree["stack"] = _stack(
            [_block_params(ini, cfg, "mamba") for _ in range(cfg.num_layers)])
    elif fam == "hybrid":
        tree["layers"] = [
            _block_params(ini, cfg, "mamba") for _ in range(cfg.num_layers)]
        tree["shared"] = {"attn": _attn_params(ini, cfg),
                          "mlp": _mlp_params(ini, cfg)}
    elif fam == "encdec":
        tree["encoder"] = {
            "stack": _stack([_block_params(ini, cfg, "encoder")
                             for _ in range(cfg.encoder_layers)]),
            "norm": ini.zeros((d,), ("embed",)),
        }
        tree["stack"] = _stack([_block_params(ini, cfg, "decoder")
                                for _ in range(cfg.num_layers)])
    elif fam == "vlm":
        n_group = cfg.num_layers // cfg.cross_attn_every
        per = cfg.cross_attn_every - 1
        self_layers = [_block_params(ini, cfg, "dense")
                       for _ in range(n_group * per)]
        stacked = _stack(self_layers)
        stacked = _tree_reshape(stacked, (n_group, per))
        tree["stack_self"] = stacked
        tree["stack_cross"] = _stack(
            [_block_params(ini, cfg, "cross") for _ in range(n_group)])
    else:
        raise ValueError(fam)
    return split_tree(tree)


def _tree_reshape(tree, lead):
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out[k] = _tree_reshape(v, lead)
        else:
            arr, spec = v
            out[k] = (arr.reshape(lead + arr.shape[1:]),
                      ("layers",) + tuple(spec))
    return out


def layer_plan(cfg: ModelConfig) -> list[str]:
    if cfg.family == "moe" and cfg.moe_layer_freq > 1:
        plan = []
        for i in range(cfg.num_layers):
            plan.append("moe" if i % cfg.moe_layer_freq == cfg.moe_layer_freq - 1
                        else "dense")
        return plan
    if cfg.family == "moe":
        return ["moe"] * cfg.num_layers
    return ["dense"] * cfg.num_layers


def layer_window(cfg: ModelConfig, i: int) -> int:
    """Sliding window for layer i (0 = full attention)."""
    if cfg.local_global_ratio > 0:
        # pattern: n local then 1 global, repeating (gemma3: 5:1)
        return 0 if (i % (cfg.local_global_ratio + 1)
                     == cfg.local_global_ratio) else cfg.sliding_window
    return cfg.sliding_window


def layer_theta(cfg: ModelConfig, i: int) -> float:
    if cfg.rope_theta_global and layer_window(cfg, i) == 0:
        return cfg.rope_theta_global
    return cfg.rope_theta


# ---------------------------------------------------------------------------
# Block applies (full sequence: train & prefill)
# ---------------------------------------------------------------------------


def _cast(p, dtype):
    return jax.tree.map(lambda x: x.astype(dtype)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x, p)


def _project_qkv(cfg, p, h, h_kv):
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h_kv, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h_kv, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def _rs_ok(ctx, batch, seq, contracted, d_out) -> bool:
    """rs_epilogue applicability: shard_map specs are strict, so every
    mapped dim must divide exactly (pjit hints would just fall back)."""
    shape = ctx.mesh.shape
    nm = shape.get("model", 1)
    nd = shape.get("data", 1)
    nb = nd * shape.get("pod", 1)
    return (nm > 1 and seq % nm == 0 and contracted % nm == 0
            and batch % nb == 0 and d_out % nd == 0)


def _bd(ctx):
    return tuple(a for a in ("pod", "data") if a in ctx.mesh.shape)


def _mlp_out_rs(ctx, act, w):
    """Down-projection with an explicit bf16 reduce-scatter epilogue.

    pjit's partitioner turns the TP partial-sum into a full all-reduce of
    the f32 dot accumulator (observed in the qwen2 baseline HLO: 2 GiB f32
    per layer per direction).  Writing the epilogue as a shard_map psum_
    scatter keeps the boundary in bf16 and scatters instead of reducing:
    4x less wire (§Perf q3).  act: (B,S,F) F-sharded; w: (F,D) (model,
    data)-sharded; returns (B,S,D) seq-sharded over "model".
    """
    def body(a, w_):
        w_full = jax.lax.all_gather(w_, "data", axis=1, tiled=True)
        y = jnp.einsum("bsf,fd->bsd", a, w_full)     # partial over F shard
        return jax.lax.psum_scatter(y, "model", scatter_dimension=1,
                                    tiled=True)

    bd = _bd(ctx)
    return jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(bd, None, "model"), P("model", "data")),
        out_specs=P(bd, "model", None), check_vma=False)(act, w)


def _attn_out_rs(ctx, o, w):
    """Attention out-projection, same epilogue as _mlp_out_rs.

    o: (B,S,H,hd) H-sharded; w: (H,hd,D) (model, -, data)-sharded."""
    def body(o_, w_):
        w_full = jax.lax.all_gather(w_, "data", axis=2, tiled=True)
        y = jnp.einsum("bshk,hkd->bsd", o_, w_full)  # partial over H shard
        return jax.lax.psum_scatter(y, "model", scatter_dimension=1,
                                    tiled=True)

    bd = _bd(ctx)
    return jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(bd, None, "model", None), P("model", None, "data")),
        out_specs=P(bd, "model", None), check_vma=False)(o, w)


def _enter_block(cfg, ctx, x):
    """Cross the SP boundary into a block: gather the sequence-sharded
    residual, then normalize.

    Hint placement matters (§Perf iteration q1): gathering *before* the
    norm moves the all-gather onto the bf16 residual; hinting after lets
    XLA fuse the gather with rms_norm's f32 upcast and ship 2x the bytes.
    Gated on cfg.prenorm_gather so the recorded baselines stay
    reproducible.
    """
    if cfg.prenorm_gather:
        return ctx.hint(x, "batch", "seq", None)
    return x


def attn_block(cfg, ctx, p, x, *, rope, window=0, causal=True,
               chunked=False, return_kv=False, kv_source=None, gated=False):
    """Self- or cross-attention block with residual.  x: (B, S, d)."""
    h = rms_norm(_enter_block(cfg, ctx, x), p["norm"], cfg.norm_eps)
    h = ctx.hint(h, "batch", "seq", None)
    if cfg.boundary_barrier:
        h = jax.lax.optimization_barrier(h)
    if kv_source is None:
        h_kv = h
    else:
        h_kv = rms_norm(kv_source, p["kv_norm"], cfg.norm_eps)
    q, k, v = _project_qkv(cfg, p, h, h_kv)
    if rope is not None and kv_source is None:
        cos, sin = rope
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    q = ctx.hint(q, "batch", "seq", "heads", "head")
    k = ctx.hint(k, "batch", "seq", "kv_heads", "head")
    v = ctx.hint(v, "batch", "seq", "kv_heads", "head")
    qg = attn.split_gqa(q, cfg.num_kv_heads)
    scale = cfg.head_dim ** -0.5
    sq, skv = q.shape[1], k.shape[1]
    q_pos = jnp.arange(sq)
    kv_pos = jnp.arange(skv)
    if kv_source is not None:
        causal = False
    score_hint = None
    if cfg.tuned_hints:
        # Anchor the (B, KV*G, Sq, Skv) score layout: prefer sharding the
        # merged head product over "model" (KV alone cannot shard a 16-way
        # axis under GQA); when the head count itself does not divide
        # (starcoder2's 36, gemma3's 4), fall back to sharding the *query*
        # dim — softmax reduces over Skv, so a q-shard needs no comm.
        score_hint = lambda t: ctx.hint(  # noqa: E731
            t, "batch", "heads", "sp_seq", None)
    if chunked and skv > 4 * cfg.attn_chunk:
        o = attn.chunked_attention(qg, k, v, q_pos, kv_pos, window=window,
                                   causal=causal, scale=scale,
                                   chunk=cfg.attn_chunk,
                                   score_hint=score_hint)
    else:
        o = attn.full_attention(qg, k, v, q_pos, kv_pos, window=window,
                                causal=causal, scale=scale,
                                score_hint=score_hint)
    o = attn.merge_gqa(o.astype(x.dtype))
    # Pin the pre-projection layout: with_sharding_constraint also fixes the
    # cotangent sharding, which keeps the attention backward head-sharded
    # (without this, SPMD re-shards the (b,kv,g,q,s) score tensor seq-wise in
    # the transpose pass -> involuntary full rematerialization).
    o = ctx.hint(o, "batch", "seq", "heads", "head")
    if cfg.rs_epilogue and not gated and _rs_ok(
            ctx, o.shape[0], o.shape[1], o.shape[2], p["wo"].shape[2]):
        out = _attn_out_rs(ctx, o, p["wo"])
    else:
        out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        if gated:
            out = jnp.tanh(p["gate"].astype(jnp.float32)).astype(x.dtype) \
                * out
        out = ctx.hint(out, "batch", "sp_seq", None)
    res = x + out
    if return_kv:  # (B, KV, S, hd) cache layout
        return res, (jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2))
    return res


def mlp_block(cfg, ctx, p, x):
    h = rms_norm(_enter_block(cfg, ctx, x), p["norm"], cfg.norm_eps)
    h = ctx.hint(h, "batch", "seq", None)
    if cfg.boundary_barrier:
        h = jax.lax.optimization_barrier(h)
    up = jnp.einsum("bsd,df->bsf", h, p["wi"])
    up = ctx.hint(up, "batch", "seq", "mlp")
    if cfg.act == "swiglu":
        gate = jnp.einsum("bsd,df->bsf", h, p["wg"])
        act = swiglu(gate, up)
    else:
        act = gelu(up)
    if cfg.rs_epilogue and _rs_ok(ctx, act.shape[0], act.shape[1],
                                  act.shape[2], p["wo"].shape[1]):
        return x + _mlp_out_rs(ctx, act, p["wo"])
    out = jnp.einsum("bsf,fd->bsd", act, p["wo"])
    out = ctx.hint(out, "batch", "sp_seq", None)
    return x + out


# ---------------------------------------------------------------------------
# MoE block — shard_map interior for deterministic collectives
# ---------------------------------------------------------------------------


def _moe_local(cfg, p, x_flat):
    """Local (per-shard) top-k dispatch via sort + capacity scatter.

    x_flat: (T, d) local tokens.  Returns (T, d) combined expert output and
    the number of locally dropped assignments (diagnostic).
    """
    t, d = x_flat.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    cap = int(np.ceil(cfg.capacity_factor * k * t / e))
    cap = max(4, min(cap, t * k))

    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eid = jax.lax.top_k(probs, k)                  # (T, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    flat_e = eid.reshape(-1)                             # (T*k,)
    flat_g = gate.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    counts = jnp.sum(jax.nn.one_hot(flat_e, e, dtype=jnp.int32), axis=0)
    start = jnp.cumsum(counts) - counts                  # exclusive cumsum
    pos_in_e = jnp.arange(t * k) - start[se]
    keep = pos_in_e < cap
    slot = jnp.where(keep, se * cap + pos_in_e, e * cap)  # overflow row

    buf = jnp.zeros((e * cap + 1, d), x_flat.dtype)
    buf = buf.at[slot].add(x_flat[st] * keep[:, None].astype(x_flat.dtype))
    xe = buf[:-1].reshape(e, cap, d)
    return xe, (slot, st, sg, keep), int(cap)


def _moe_combine(t, d, k, outs_rows, slot, st, sg, keep, dtype):
    """Scatter expert rows back to tokens and weight by gates."""
    picked = outs_rows[slot] * keep[:, None].astype(outs_rows.dtype)  # (T*k,d)
    y = jnp.zeros((t, d), dtype)
    y = y.at[st].add(picked.astype(dtype) * sg[:, None].astype(dtype))
    return y


def moe_block(cfg, ctx, p, x):
    """Mixture block.  TP mode: experts replicated, d_ff sharded over
    "model", psum-scatter epilogue (Megatron-SP style).  EP mode: experts
    sharded over "model", explicit all_to_all dispatch/return."""
    mesh = ctx.mesh
    bd = tuple(a for a in ("pod", "data") if a in mesh.shape)
    # the shard_map bodies below hard-code Megatron-TP weight layouts; use
    # them only when the rules actually put d_ff (or experts) on "model".
    # Under data-parallel-only rules (§Perf fsdp preset) fall through to a
    # plain pjit path and let the partitioner place the expert einsums.
    mlp_on_model = "model" in ctx.rules.mesh_axes("mlp")
    exp_on_model = "model" in ctx.rules.mesh_axes("expert")
    has_model = "model" in mesh.shape and (mlp_on_model or exp_on_model)
    ep = cfg.moe_partition == "expert" and has_model and exp_on_model and (
        cfg.num_experts % mesh.shape["model"] == 0)
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token

    h_in = rms_norm(_enter_block(cfg, ctx, x), p["norm"], cfg.norm_eps)

    if not has_model:
        t = b * s
        hf = h_in.reshape(t, d)
        xe, meta, cap = _moe_local(cfg, {"router": p["router"]}, hf)
        up = jnp.einsum("ecd,edf->ecf", xe, p["wi"])
        gt = jnp.einsum("ecd,edf->ecf", xe, p["wg"])
        act = swiglu(gt, up)
        out = jnp.einsum("ecf,efd->ecd", act, p["wo"])
        rows = jnp.concatenate(
            [out.reshape(e * cap, d), jnp.zeros((1, d), out.dtype)])
        y = _moe_combine(t, d, k, rows, *meta, h_in.dtype).reshape(b, s, d)
        y = ctx.hint(y, "batch", "sp_seq", None)
        return x + y

    def tp_body(h, router, wi, wg, wo):
        # tokens replicated over "model", d_ff sharded: each rank computes a
        # partial over its f-shard; the single reduction is fused with the
        # sequence-parallel re-shard (reduce-scatter epilogue, Megatron-SP).
        t = h.shape[0] * h.shape[1]
        hf = h.reshape(t, d)
        xe, meta, cap = _moe_local(cfg, {"router": router}, hf)
        up = jnp.einsum("ecd,edf->ecf", xe, wi)
        gt = jnp.einsum("ecd,edf->ecf", xe, wg)
        act = swiglu(gt, up)
        out = jnp.einsum("ecf,efd->ecd", act, wo)        # partial over f
        rows = jnp.concatenate(
            [out.reshape(e * cap, d), jnp.zeros((1, d), out.dtype)])
        y = _moe_combine(t, d, k, rows, *meta, h.dtype)  # linear: stays partial
        y = y.reshape(h.shape)
        if has_model:
            if s % mesh.shape["model"] == 0:
                y = jax.lax.psum_scatter(y, "model", scatter_dimension=1,
                                         tiled=True)
            else:
                y = jax.lax.psum(y, "model")
        return y

    def ep_body(h, router, wi, wg, wo):
        t = h.shape[0] * h.shape[1]
        hf = h.reshape(t, d)
        xe, meta, cap = _moe_local(cfg, {"router": router}, hf)  # (E,cap,d)
        # all_to_all: split experts across model ranks, concat token chunks
        xr = jax.lax.all_to_all(xe, "model", split_axis=0, concat_axis=1,
                                tiled=True)              # (E/nm, cap*nm, d)
        up = jnp.einsum("ecd,edf->ecf", xr, wi)
        gt = jnp.einsum("ecd,edf->ecf", xr, wg)
        act = swiglu(gt, up)
        out = jnp.einsum("ecf,efd->ecd", act, wo)        # (E/nm, cap*nm, d)
        back = jax.lax.all_to_all(out, "model", split_axis=1, concat_axis=0,
                                  tiled=True)            # (E, cap, d)
        rows = jnp.concatenate(
            [back.reshape(e * cap, d), jnp.zeros((1, d), back.dtype)])
        y = _moe_combine(t, d, k, rows, *meta, h.dtype)
        return y.reshape(h.shape)

    if ep:
        # tokens sharded over every mesh axis (batch over dp axes, seq over
        # model); experts sharded over model.
        in_specs = (P(bd, "model" if s % mesh.shape.get("model", 1) == 0
                      else None, None),
                    P(None, None), P("model", None, None),
                    P("model", None, None), P("model", None, None))
        out_spec = in_specs[0]
        body = ep_body
    else:
        seq_ok = has_model and s % mesh.shape["model"] == 0
        in_specs = (P(bd, None, None),
                    P(None, None),
                    P(None, None, "model") if has_model else P(None, None, None),
                    P(None, None, "model") if has_model else P(None, None, None),
                    P(None, "model", None) if has_model else P(None, None, None))
        out_spec = P(bd, "model", None) if seq_ok else P(bd, None, None)
        body = tp_body

    y = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_spec,
        check_vma=False,
    )(h_in, p["router"], p["wi"], p["wg"], p["wo"])
    y = ctx.hint(y, "batch", "sp_seq", None)
    return x + y


def moe_block_decode(cfg, ctx, p, x):
    """Gather-based MoE for decode: fetch top-k expert weights per token.

    Keeps FLOPs at k/E of dense and reads only the needed expert weights
    (the true memory cost of MoE decode).  d_ff stays sharded over "model"
    in TP mode; in EP mode weights are E-sharded so we fall back to a dense
    one-hot contraction over the *local* experts then psum (tokens tiny).
    """
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    hf = h.reshape(b * s, d)
    logits = jnp.einsum("td,de->te", hf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eid = jax.lax.top_k(probs, k)                   # (T,k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    wi = jnp.take(p["wi"], eid, axis=0)                   # (T,k,d,f)
    wg = jnp.take(p["wg"], eid, axis=0)
    wo = jnp.take(p["wo"], eid, axis=0)                   # (T,k,f,d)
    up = jnp.einsum("td,tkdf->tkf", hf, wi)
    gt = jnp.einsum("td,tkdf->tkf", hf, wg)
    act = swiglu(gt, up)
    out = jnp.einsum("tkf,tkfd->tkd", act, wo)
    y = jnp.einsum("tkd,tk->td", out, gate.astype(out.dtype))
    return x + y.reshape(b, s, d)


# ---------------------------------------------------------------------------
# Mamba2 block
# ---------------------------------------------------------------------------


def mamba_block(cfg, ctx, p, x, *, return_state=False, chunk=None):
    """Full-sequence Mamba2 block.  x: (B, S, d)."""
    b, s, d = x.shape
    h = rms_norm(_enter_block(cfg, ctx, x), p["norm"], cfg.norm_eps)
    h = ctx.hint(h, "batch", "seq", None)
    z = jnp.einsum("bsd,di->bsi", h, p["wz"])
    xi = jnp.einsum("bsd,di->bsi", h, p["wx"])
    bb = jnp.einsum("bsd,dg->bsg", h, p["wB"])
    cc = jnp.einsum("bsd,dg->bsg", h, p["wC"])
    dt = jnp.einsum("bsd,dh->bsh", h, p["wdt"])
    xi = ssd.causal_conv(xi, p["conv_x_w"], p["conv_x_b"])
    bb = ssd.causal_conv(bb, p["conv_B_w"], p["conv_B_b"])
    cc = ssd.causal_conv(cc, p["conv_C_w"], p["conv_C_b"])
    nh, pd, ns = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state
    xh = xi.reshape(b, s, nh, pd)
    xh = ctx.hint(xh, "batch", "seq", "ssm_heads", None)
    dtp = jax.nn.softplus(dt.astype(jnp.float32)
                          + p["dt_bias"].astype(jnp.float32))
    if cfg.tuned_hints:
        # anchor the decay tensor on heads so the (B,C,H,Q,Q) segsum/score
        # intermediates in ssd_scan shard over "model" instead of
        # replicating (§Perf z-iterations)
        dtp = ctx.hint(dtp, "batch", "seq", "ssm_heads")
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    bg = bb.reshape(b, s, cfg.ssm_ngroups, ns)
    cg = cc.reshape(b, s, cfg.ssm_ngroups, ns)
    y, state = ssd.ssd_scan(xh, dtp, a, bg, cg,
                            chunk=chunk or cfg.ssm_chunk, d_skip=p["D"])
    y = y.reshape(b, s, nh * pd)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                 p["gnorm"], cfg.norm_eps)
    if cfg.rs_epilogue and _rs_ok(ctx, b, s, y.shape[2],
                                  p["wout"].shape[1]):
        out = _mlp_out_rs(ctx, y, p["wout"])
    else:
        out = jnp.einsum("bsi,id->bsd", y, p["wout"])
        out = ctx.hint(out, "batch", "sp_seq", None)
    res = x + out
    if return_state:
        # conv states: last (W-1) *pre-conv* channel inputs, re-projected
        # from the normed-residual tail (cheap: (B, W-1, d) slice).
        w = cfg.ssm_conv_width
        tail = h[:, s - (w - 1):, :]
        pre_x = jnp.einsum("bsd,di->bsi", tail, p["wx"])
        pre_b = jnp.einsum("bsd,dg->bsg", tail, p["wB"])
        pre_c = jnp.einsum("bsd,dg->bsg", tail, p["wC"])
        return res, {"state": state, "conv_x": pre_x, "conv_B": pre_b,
                     "conv_C": pre_c}
    return res


def mamba_block_decode(cfg, ctx, p, x, cache):
    """One-token Mamba2 update.  x: (B, 1, d); cache holds state+conv."""
    b = x.shape[0]
    h = rms_norm(x[:, 0, :], p["norm"], cfg.norm_eps)
    z = jnp.einsum("bd,di->bi", h, p["wz"])
    xi = jnp.einsum("bd,di->bi", h, p["wx"])
    bb = jnp.einsum("bd,dg->bg", h, p["wB"])
    cc = jnp.einsum("bd,dg->bg", h, p["wC"])
    dt = jnp.einsum("bd,dh->bh", h, p["wdt"])
    xi, cx = ssd.causal_conv_decode(cache["conv_x"], xi,
                                    p["conv_x_w"], p["conv_x_b"])
    bb, cb = ssd.causal_conv_decode(cache["conv_B"], bb,
                                    p["conv_B_w"], p["conv_B_b"])
    cc, ccs = ssd.causal_conv_decode(cache["conv_C"], cc,
                                     p["conv_C_w"], p["conv_C_b"])
    nh, pd, ns = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state
    xh = xi.reshape(b, nh, pd)
    dtp = jax.nn.softplus(dt.astype(jnp.float32)
                          + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    y, state = ssd.ssd_decode(cache["state"], xh, dtp, a,
                              bb.reshape(b, cfg.ssm_ngroups, ns),
                              cc.reshape(b, cfg.ssm_ngroups, ns),
                              d_skip=p["D"])
    y = y.reshape(b, nh * pd)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                 p["gnorm"], cfg.norm_eps)
    out = jnp.einsum("bi,id->bd", y, p["wout"])
    new_cache = {"state": state, "conv_x": cx, "conv_B": cb, "conv_C": ccs}
    return x + out[:, None, :], new_cache


# ---------------------------------------------------------------------------
# Decode-mode attention block
# ---------------------------------------------------------------------------


def attn_block_decode(cfg, ctx, p, x, cache, pos, *, window=0, theta=None,
                      cross_cache=None, gated=False, use_rope=True):
    """x: (B, 1, d).  cache: {k, v, slot_pos}; cross_cache: {k, v} fixed."""
    b = x.shape[0]
    h = rms_norm(x[:, 0, :], p["norm"], cfg.norm_eps)
    hs = h[:, None, :]
    if cross_cache is not None:
        q = jnp.einsum("bd,dhk->bhk", h, p["wq"])
        if cfg.qkv_bias:
            q = q + p["bq"]
        qg = q.reshape(b, cfg.num_kv_heads,
                       cfg.num_heads // cfg.num_kv_heads, cfg.head_dim)
        skv = cross_cache["k"].shape[2]
        slot = jnp.arange(skv)
        o = attn.decode_attention(qg, cross_cache["k"], cross_cache["v"],
                                  slot, jnp.asarray(skv, jnp.int32),
                                  window=0, scale=cfg.head_dim ** -0.5)
        o = o.reshape(b, cfg.num_heads, cfg.head_dim)
        out = jnp.einsum("bhk,hkd->bd", o.astype(x.dtype), p["wo"])
        if gated:
            out = jnp.tanh(p["gate"].astype(jnp.float32)).astype(x.dtype) * out
        return x + out[:, None, :], cache
    q, k, v = _project_qkv(cfg, p, hs, hs)
    if use_rope:
        theta = theta if theta is not None else cfg.rope_theta
        cos, sin = rope_at(pos[None], cfg.head_dim, theta)  # (1, hd/2)
        q = apply_rope(q, cos[None], sin[None])
        k = apply_rope(k, cos[None], sin[None])
    q1 = q[:, 0]                                           # (B,H,hd)
    k1, v1 = k[:, 0], v[:, 0]                              # (B,KV,hd)
    size = cache["k"].shape[2]
    slot = jnp.where(jnp.asarray(window, jnp.int32) > 0, pos % size,
                     jnp.minimum(pos, size - 1))
    ck, cv = attn.cache_write(cache["k"], cache["v"], k1, v1, slot)
    slot_pos = cache["slot_pos"]
    slot_pos = jnp.where(jnp.arange(size) == slot, pos, slot_pos)
    qg = q1.reshape(b, cfg.num_kv_heads,
                    cfg.num_heads // cfg.num_kv_heads, cfg.head_dim)
    o = attn.decode_attention(qg, ck, cv, slot_pos, pos, window=window,
                              scale=cfg.head_dim ** -0.5)
    o = o.reshape(b, cfg.num_heads, cfg.head_dim)
    out = jnp.einsum("bhk,hkd->bd", o.astype(x.dtype), p["wo"])
    new_cache = {"k": ck, "v": cv, "slot_pos": slot_pos}
    return x + out[:, None, :], new_cache


def mlp_block_decode(cfg, ctx, p, x):
    return mlp_block(cfg, ctx, p, x)
