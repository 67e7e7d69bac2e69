"""Decoder-only transformer LM: GQA, RoPE, MoE (expert-parallel), KV cache.

Covers the four assigned LM architectures (moonshot-v1-16b-a3b,
llama4-scout-17b-a16e, granite-20b, llama3-8b):

  * GQA with arbitrary kv-head count (MQA = 1) and TP head padding: when
    the mesh's model axis does not divide the head count, q/kv heads are
    padded up to the next multiple (Megatron-style KV duplication). The
    MODEL_FLOPS/HLO ratio in the roofline table surfaces the overhead.
  * MoE FFN with sort-based dispatch under ``shard_map``: experts sharded
    over the model axis (EP), tokens routed with a single all-to-all per
    direction within each data row. Dispatch is gather/scatter (no one-hot
    matmul), so compiled FLOPs ≈ active FLOPs.
  * llama4-style chunked local attention (``chunk_attn``) with a RoPE-less
    global layer every ``global_every`` layers — this is what makes the
    long_500k cell sub-quadratic.
  * Layers run under ``lax.scan`` (stacked params) — compile time and HLO
    size stay flat in depth, which is what makes 40 dry-run cells viable.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.models import common as cm
from repro.models.common import ParamSpec


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str = "lm"
    n_layers: int = 2
    d_model: int = 128
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 32
    d_ff: int = 256
    vocab: int = 512
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    # MoE (0 experts = dense FFN).
    moe_experts: int = 0
    moe_topk: int = 1
    moe_capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    # llama4-style local attention: 0 = full attention everywhere.
    chunk_attn: int = 0
    global_every: int = 4            # every Nth layer is global (RoPE-less)
    # TP head padding (set to the mesh model-axis size by the launcher).
    pad_heads_to: int = 1
    dtype: str = "bfloat16"
    kv_block: int = 1024
    remat: bool = True               # activation checkpointing per layer
    # Perf levers (EXPERIMENTS.md §Perf):
    seq_shard: bool = False          # shard residual stream seq over "model"
    remat_policy: str = "minimal"    # minimal | save_sums (keep post-
    #                                  collective sums; backward skips the
    #                                  recomputed all-reduces)
    reduce_dtype: str = "float32"    # accumulation dtype of the row-parallel
    #                                  (wo / w_down) matmuls — "bfloat16"
    #                                  halves cross-chip all-reduce bytes
    embed_shard: str = "vocab"       # vocab | dm: embedding-table sharding
    #                                  (dm turns the masked-gather all-reduce
    #                                  into a 4x cheaper bf16 all-gather)
    microbatch: int = 1              # gradient-accumulation factor
    decode_seq_shard: bool = False   # long-context decode: shard the KV
    #                                  cache SEQUENCE over "model" and run
    #                                  distributed flash-decoding (partial
    #                                  online softmax + pmax/psum combine);
    #                                  attention weights become replicated

    @property
    def n_heads_padded(self) -> int:
        return -(-self.n_heads // self.pad_heads_to) * self.pad_heads_to

    @property
    def n_kv_padded(self) -> int:
        return -(-self.n_kv_heads // self.pad_heads_to) * self.pad_heads_to

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def param_count(self, padded: bool = False) -> int:
        return cm.param_count(lm_param_table(self)) if padded else \
            _logical_param_count(self)

    def active_param_count(self) -> int:
        """Per-token active params (MoE counts top-k experts only)."""
        c = self
        attn = c.d_model * c.head_dim * (c.n_heads * 2 + c.n_kv_heads * 2)
        ffn = 3 * c.d_model * c.d_ff
        ffn_active = ffn * (c.moe_topk if c.moe_experts else 1)
        router = c.d_model * c.moe_experts if c.moe_experts else 0
        per_layer = attn + ffn_active + router + 2 * c.d_model
        return (c.n_layers * per_layer + 2 * c.vocab * c.d_model
                + c.d_model)


def _logical_param_count(c: LMConfig) -> int:
    attn = c.d_model * c.head_dim * (c.n_heads * 2 + c.n_kv_heads * 2)
    ffn = 3 * c.d_model * c.d_ff * (c.moe_experts if c.moe_experts else 1)
    router = c.d_model * c.moe_experts if c.moe_experts else 0
    per_layer = attn + ffn + router + 2 * c.d_model
    return c.n_layers * per_layer + 2 * c.vocab * c.d_model + c.d_model


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def lm_param_table(c: LMConfig) -> Dict[str, Any]:
    dt = c.jdtype
    L, dm, hd = c.n_layers, c.d_model, c.head_dim
    hp, kp = c.n_heads_padded, c.n_kv_padded
    layer: Dict[str, Any] = {
        "attn_norm": ParamSpec((L, dm), ("layers", "embed"), dt, init="ones"),
        "wq": ParamSpec((L, dm, hp, hd), ("layers", "embed", "heads", "head_dim"), dt),
        "wk": ParamSpec((L, dm, kp, hd), ("layers", "embed", "kv_heads", "head_dim"), dt),
        "wv": ParamSpec((L, dm, kp, hd), ("layers", "embed", "kv_heads", "head_dim"), dt),
        "wo": ParamSpec((L, hp, hd, dm), ("layers", "heads", "head_dim", "embed"), dt),
        "mlp_norm": ParamSpec((L, dm), ("layers", "embed"), dt, init="ones"),
    }
    if c.moe_experts:
        E, dff = c.moe_experts, c.d_ff
        layer.update({
            "router": ParamSpec((L, dm, E), ("layers", "embed", None),
                                jnp.float32),
            "w_gate": ParamSpec((L, E, dm, dff), ("layers", "experts", "embed", None), dt),
            "w_up": ParamSpec((L, E, dm, dff), ("layers", "experts", "embed", None), dt),
            "w_down": ParamSpec((L, E, dff, dm), ("layers", "experts", None, "embed"), dt),
        })
    else:
        dff = c.d_ff
        layer.update({
            "w_gate": ParamSpec((L, dm, dff), ("layers", "embed", "mlp"), dt),
            "w_up": ParamSpec((L, dm, dff), ("layers", "embed", "mlp"), dt),
            "w_down": ParamSpec((L, dff, dm), ("layers", "mlp", "embed"), dt),
        })
    return {
        # Dedicated logical axes: the input-embedding sharding is a perf
        # lever (cfg.embed_shard) independent of the unembed projection.
        "embed": ParamSpec((c.vocab, dm), ("vocab_embed", "dm_embed"), dt),
        "layers": layer,
        "final_norm": ParamSpec((dm,), ("embed",), dt, init="ones"),
        "unembed": ParamSpec((dm, c.vocab), ("embed", "vocab"), dt),
    }


def lm_rules(c: LMConfig) -> Dict[str, Any]:
    """Logical→mesh rule overrides implied by the config's perf levers."""
    if c.embed_shard == "dm":
        return {"vocab_embed": None, "dm_embed": "model"}
    return {"vocab_embed": "model", "dm_embed": None}


# ---------------------------------------------------------------------------
# MoE FFN (expert parallel, sort-based dispatch)
# ---------------------------------------------------------------------------

def _route(x, router, cfg: LMConfig):
    """Top-k routing. Returns (top_w, top_e, probs)."""
    logits = (x.astype(jnp.float32) @ router.astype(jnp.float32))  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = lax.top_k(probs, cfg.moe_topk)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    return top_w, top_e, probs


def _aux_loss(top_e, probs, E: int) -> jnp.ndarray:
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    return E * jnp.sum(frac_tokens * frac_probs)


def _expert_ffn(xs, w_gate, w_up, w_down, dtype):
    g = jnp.einsum("ecd,edf->ecf", xs, w_gate)
    u = jnp.einsum("ecd,edf->ecf", xs, w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _moe_local(x, router, w_gate, w_up, w_down, *, cfg: LMConfig,
               model_axis: Optional[str], n_model: int):
    """Per-device MoE body under shard_map — all-to-all dispatch.

    PRECONDITION: every device holds DISTINCT tokens (the caller shards
    the sequence across the model axis). x: (T_loc, dm); w_*: (E_loc, ...)
    local expert shards. Returns (y: (T_loc, dm), aux scalar).
    """
    E, k = cfg.moe_experts, cfg.moe_topk
    t_loc, dm = x.shape
    e_loc = E // n_model
    cap = max(1, math.ceil(t_loc * k / E * cfg.moe_capacity_factor))

    top_w, top_e, probs = _route(x, router, cfg)

    flat_e = top_e.reshape(-1)                                     # (T*k,)
    flat_t = jnp.repeat(jnp.arange(t_loc), k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    sorted_t = flat_t[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    pos = jnp.arange(t_loc * k) - starts[sorted_e]
    keep = pos < cap
    slot_sorted = jnp.where(keep, sorted_e * cap + pos, E * cap)   # OOB=drop

    # Dispatch: (E*cap, dm) buffers, dropped tokens vanish.
    buf = jnp.zeros((E * cap, dm), x.dtype)
    buf = buf.at[slot_sorted].set(x[sorted_t], mode="drop")

    if model_axis is not None and n_model > 1:
        buf = buf.reshape(n_model, e_loc * cap, dm)
        buf = lax.all_to_all(buf, model_axis, split_axis=0, concat_axis=0,
                             tiled=True)                           # grouped by source
        xs = buf.reshape(n_model, e_loc, cap, dm).transpose(1, 0, 2, 3) \
                .reshape(e_loc, n_model * cap, dm)
    else:
        xs = buf.reshape(e_loc, cap, dm)

    o = _expert_ffn(xs, w_gate, w_up, w_down, x.dtype)

    if model_axis is not None and n_model > 1:
        o = o.reshape(e_loc, n_model, cap, dm).transpose(1, 0, 2, 3) \
             .reshape(n_model * e_loc * cap, dm)
        o = lax.all_to_all(o.reshape(n_model, e_loc * cap, dm), model_axis,
                           split_axis=0, concat_axis=0, tiled=True)
        o = o.reshape(E * cap, dm)
    else:
        o = o.reshape(E * cap, dm)

    # Combine: unsort slots back to (T, k), gather, weight, sum.
    slot_flat = jnp.zeros((t_loc * k,), jnp.int32).at[order].set(
        slot_sorted.astype(jnp.int32))
    picked = o.at[slot_flat.clip(0, E * cap - 1)].get()            # (T*k, dm)
    valid = (slot_flat < E * cap).astype(x.dtype)
    w = (top_w.reshape(-1).astype(x.dtype) * valid)[:, None]
    y = (picked * w).reshape(t_loc, k, dm).sum(axis=1)
    return y, _aux_loss(top_e, probs, E)


def _moe_local_replicated(x, router, w_gate, w_up, w_down, *, cfg: LMConfig,
                          model_axis: Optional[str], n_model: int):
    """MoE body when tokens are REPLICATED across the model axis (decode:
    seq length 1 cannot shard). Each column computes only its local
    experts' contributions for all tokens; a psum over the model axis
    combines them — no all-to-all, no duplicated expert FLOPs."""
    E, k = cfg.moe_experts, cfg.moe_topk
    t_loc, dm = x.shape
    e_loc = E // n_model
    cap = max(1, math.ceil(t_loc * k / E * cfg.moe_capacity_factor))

    top_w, top_e, probs = _route(x, router, cfg)
    col = lax.axis_index(model_axis) if (model_axis and n_model > 1) else 0
    local_e = top_e - col * e_loc                                  # (T, k)
    is_local = (local_e >= 0) & (local_e < e_loc)

    flat_e = jnp.where(is_local, local_e, e_loc).reshape(-1)       # e_loc=drop
    flat_t = jnp.repeat(jnp.arange(t_loc), k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    sorted_t = flat_t[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e_loc + 1), side="left")
    pos = jnp.arange(t_loc * k) - starts[jnp.minimum(sorted_e, e_loc)]
    keep = (pos < cap) & (sorted_e < e_loc)
    slot_sorted = jnp.where(keep, sorted_e * cap + pos, e_loc * cap)

    buf = jnp.zeros((e_loc * cap, dm), x.dtype)
    buf = buf.at[slot_sorted].set(x[sorted_t], mode="drop")
    o = _expert_ffn(buf.reshape(e_loc, cap, dm), w_gate, w_up, w_down,
                    x.dtype).reshape(e_loc * cap, dm)

    slot_flat = jnp.zeros((t_loc * k,), jnp.int32).at[order].set(
        slot_sorted.astype(jnp.int32))
    picked = o.at[slot_flat.clip(0, e_loc * cap - 1)].get()
    valid = (slot_flat < e_loc * cap).astype(x.dtype)
    w = (top_w.reshape(-1).astype(x.dtype) * valid)[:, None]
    y = (picked * w).reshape(t_loc, k, dm).sum(axis=1)
    if model_axis and n_model > 1:
        y = lax.psum(y, model_axis)
    return y, _aux_loss(top_e, probs, E)


def make_moe_ffn(cfg: LMConfig, mesh: Mesh,
                 batch_axes: Optional[Tuple[str, ...]],
                 seq_len: Optional[int] = None):
    """Returns moe_ffn(x (B,S,dm), layer_params) -> (y, aux_loss).

    When the sequence divides the model axis, tokens are sequence-sharded
    across it so every device dispatches DISTINCT tokens (all-to-all EP —
    expert FLOPs are ideal x capacity factor). Otherwise (decode, S=1)
    tokens stay replicated and each column computes only its local
    experts, combined with a psum."""
    model_axis = "model" if "model" in mesh.axis_names else None
    n_model = mesh.shape.get("model", 1)
    seq_sharded = bool(model_axis and n_model > 1 and seq_len
                       and seq_len % n_model == 0)
    x_spec = P(batch_axes, "model" if seq_sharded else None, None) \
        if batch_axes else P(None, "model" if seq_sharded else None, None)
    body = _moe_local if (seq_sharded or n_model == 1 or model_axis is None) \
        else _moe_local_replicated

    def local_fn(x, router, w_gate, w_up, w_down):
        b, s, dm = x.shape
        y, aux = body(x.reshape(b * s, dm), router, w_gate, w_up,
                      w_down, cfg=cfg, model_axis=model_axis,
                      n_model=n_model)
        if model_axis and n_model > 1 and seq_sharded:
            aux = lax.pmean(aux, model_axis)
        if batch_axes:
            aux = lax.pmean(aux, batch_axes)
        return y.reshape(b, s, dm), aux

    e_spec = P("model", None, None) if model_axis else P(None, None, None)
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_spec,
                  P(None, None),        # router replicated
                  e_spec, e_spec, e_spec),
        out_specs=(x_spec, P()),
        check_vma=False)


# ---------------------------------------------------------------------------
# Transformer blocks
# ---------------------------------------------------------------------------

def _attention(x, lp, positions, cfg: LMConfig, is_global,
               kv_cache=None, cache_pos=None):
    """One attention sublayer. Returns (out, (k_new, v_new)).

    Training/prefill: kv_cache None, positions (B, S).
    Decode: kv_cache (k, v) each (B, S_max, Kp, hd), cache_pos scalar.
    """
    b, s, dm = x.shape
    h = cm.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])

    if cfg.chunk_attn == 0:
        # Plain causal arch: RoPE everywhere.
        q = cm.rope(q, positions, cfg.rope_theta)
        k = cm.rope(k, positions, cfg.rope_theta)
    else:
        # llama4-style: chunked layers use RoPE, global layers are NoPE.
        q_r = cm.rope(q, positions, cfg.rope_theta)
        k_r = cm.rope(k, positions, cfg.rope_theta)
        q = jnp.where(is_global, q, q_r)
        k = jnp.where(is_global, k, k_r)

    if kv_cache is None:
        if cfg.chunk_attn and s > cfg.chunk_attn:
            w = cfg.chunk_attn
            nchunk = s // w

            def chunked():
                qc = q.reshape(b * nchunk, w, *q.shape[2:])
                kc = k.reshape(b * nchunk, w, *k.shape[2:])
                vc = v.reshape(b * nchunk, w, *v.shape[2:])
                o = cm.causal_attention(qc, kc, vc, kv_block=cfg.kv_block)
                return o.reshape(b, s, *o.shape[2:])

            def full():
                return cm.causal_attention(q, k, v, kv_block=cfg.kv_block)

            o = lax.cond(is_global, full, chunked)
        else:
            o = cm.causal_attention(q, k, v, kv_block=cfg.kv_block)
        k_out, v_out = k, v
    else:
        ck, cv = kv_cache
        k_out = lax.dynamic_update_slice_in_dim(ck, k, cache_pos, axis=1)
        v_out = lax.dynamic_update_slice_in_dim(cv, v, cache_pos, axis=1)
        s_max = ck.shape[1]
        if cfg.chunk_attn and cfg.chunk_attn < s_max:
            w = cfg.chunk_attn

            def windowed():
                start = jnp.clip(cache_pos + s - w, 0, s_max - w)
                kw = lax.dynamic_slice_in_dim(k_out, start, w, axis=1)
                vw = lax.dynamic_slice_in_dim(v_out, start, w, axis=1)
                return cm.causal_attention(q, kw, vw,
                                           q_offset=cache_pos - start,
                                           kv_block=cfg.kv_block)

            def full():
                return cm.causal_attention(q, k_out, v_out,
                                           q_offset=cache_pos,
                                           kv_block=cfg.kv_block)

            o = lax.cond(is_global, full, windowed)
        else:
            o = cm.causal_attention(q, k_out, v_out, q_offset=cache_pos,
                                    kv_block=cfg.kv_block)
    out = jnp.einsum("bshk,hkd->bsd", o, lp["wo"],
                     preferred_element_type=_accum_dtype(cfg))
    return out.astype(x.dtype), (k_out, v_out)


def _accum_dtype(cfg: LMConfig):
    """Accumulation dtype for the row-parallel matmuls whose partial sums
    cross chips (Megatron 2nd all-reduce): bf16 halves the wire bytes."""
    return jnp.bfloat16 if cfg.reduce_dtype == "bfloat16" else jnp.float32


def _dense_ffn(x, lp, cfg: LMConfig):
    h = cm.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    g = jnp.einsum("bsd,df->bsf", h, lp["w_gate"])
    u = jnp.einsum("bsd,df->bsf", h, lp["w_up"])
    act = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("bsf,fd->bsd", act, lp["w_down"],
                      preferred_element_type=_accum_dtype(cfg)).astype(x.dtype)


def _seq_constraint(cfg: LMConfig, mesh: Optional[Mesh],
                    batch_axes, seq_len: int):
    """Residual-stream sequence sharding (SP): returns a constraint fn for
    (B, S, dm) activations, sharding S over 'model' between layers. GSPMD
    then lowers the Megatron all-reduce pair into all-gather +
    reduce-scatter and — the point — remat-saved layer inputs shrink by
    the TP degree."""
    if (not cfg.seq_shard or mesh is None or batch_axes is None
            or "model" not in mesh.axis_names):
        return lambda x: x
    n_model = mesh.shape["model"]
    if seq_len % n_model != 0 or seq_len < n_model:
        return lambda x: x
    sh = jax.sharding.NamedSharding(mesh, P(batch_axes, "model", None))
    return lambda x: jax.lax.with_sharding_constraint(x, sh)


def _maybe_name(x, name: str, cfg: LMConfig):
    if cfg.remat_policy == "save_sums":
        from jax.ad_checkpoint import checkpoint_name
        return checkpoint_name(x, name)
    return x


def _remat(block, cfg: LMConfig):
    if not cfg.remat:
        return block
    if cfg.remat_policy == "save_sums":
        policy = jax.checkpoint_policies.save_only_these_names(
            "attn_out", "ffn_out")
        return jax.checkpoint(block, policy=policy)
    return jax.checkpoint(block)


def make_seqpar_attention(cfg: LMConfig, mesh: Mesh):
    """Distributed flash-decoding: KV cache sequence-sharded over "model".

    Each device holds an S/16 slice of the 500k-token cache, computes a
    partial online-softmax over its slice, and the partials combine with
    one pmax + two psums of (B, H, 1)-sized scalars/vectors — wire bytes
    are O(B·H·hd), independent of context length. Chunked (windowed)
    layers use the same code with an extra window mask.

    Returns attn(q, k_new, v_new, ck, cv, pos, is_global)
      -> (out (B,1,H,hd), new_ck, new_cv), with ck/cv local slices
      (B, S_loc, Kp, hd) under shard_map.
    """
    n_model = mesh.shape.get("model", 1)

    def local_attn(q, k_new, v_new, ck, cv, pos, is_global):
        b, _, h, d = q.shape
        s_loc = ck.shape[1]
        idx = lax.axis_index("model")
        start = idx * s_loc
        # Scatter the new token's K/V into the owning shard's slice.
        owned = (pos >= start) & (pos < start + s_loc)
        li = jnp.clip(pos - start, 0, s_loc - 1)
        ck_upd = lax.dynamic_update_slice_in_dim(ck, k_new, li, axis=1)
        cv_upd = lax.dynamic_update_slice_in_dim(cv, v_new, li, axis=1)
        ck = jnp.where(owned, ck_upd, ck)
        cv = jnp.where(owned, cv_upd, cv)

        kk = cm._repeat_kv(ck, h // ck.shape[2])
        vv = cm._repeat_kv(cv, h // cv.shape[2])
        scale = 1.0 / math.sqrt(d)
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                       kk.astype(jnp.float32))            # (B,H,1,S_loc)
        abs_pos = start + jnp.arange(s_loc)
        mask = abs_pos <= pos
        if cfg.chunk_attn:
            win = abs_pos > pos - cfg.chunk_attn
            mask = jnp.where(is_global, mask, mask & win)
        s = jnp.where(mask[None, None, None, :], s, -jnp.inf)

        m_loc = jnp.max(s, axis=-1)                       # (B,H,1)
        m_glob = lax.pmax(m_loc, "model")
        safe = jnp.isfinite(m_glob)
        p = jnp.exp(s - jnp.where(safe, m_glob, 0.0)[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        l_loc = jnp.sum(p, axis=-1)
        o_loc = jnp.einsum("bhqk,bkhd->bhqd", p, vv.astype(jnp.float32))
        l_glob = lax.psum(l_loc, "model")
        o_glob = lax.psum(o_loc, "model")
        out = (o_glob / jnp.maximum(l_glob, 1e-30)[..., None]) \
            .transpose(0, 2, 1, 3).astype(q.dtype)        # (B,1,H,hd)
        return out, ck, cv

    kvspec = P(None, "model", None, None)
    rep4 = P(None, None, None, None)
    return jax.shard_map(
        local_attn, mesh=mesh,
        in_specs=(rep4, rep4, rep4, kvspec, kvspec, P(), P()),
        out_specs=(rep4, kvspec, kvspec),
        check_vma=False)


def _layer_flags(cfg: LMConfig) -> jnp.ndarray:
    """(L,) bool — True where the layer uses global (full, RoPE-less) attn."""
    if cfg.chunk_attn == 0:
        return jnp.ones((cfg.n_layers,), bool)     # all global (plain causal)
    idx = jnp.arange(cfg.n_layers)
    return (idx + 1) % cfg.global_every == 0


def make_forward(cfg: LMConfig, mesh: Optional[Mesh] = None,
                 batch_axes: Optional[Tuple[str, ...]] = ("data",)):
    """Returns forward(params, tokens (B,S)) -> (logits, aux_loss)."""
    if mesh is None:
        mesh = Mesh(jax.devices()[:1], ("data",))
        batch_axes = None

    def forward(params, tokens):
        b, s = tokens.shape
        moe_ffn = make_moe_ffn(cfg, mesh, batch_axes, seq_len=s) \
            if cfg.moe_experts else None
        x = params["embed"].at[tokens].get(mode="clip").astype(cfg.jdtype)
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        flags = _layer_flags(cfg)
        constrain = _seq_constraint(cfg, mesh, batch_axes, s)

        def block(x, scanned):
            lp, is_global = scanned
            x = constrain(x)
            attn, _ = _attention(x, lp, positions, cfg, is_global)
            x = x + _maybe_name(attn, "attn_out", cfg)
            if cfg.moe_experts:
                h = cm.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
                y, aux = moe_ffn(h, lp["router"], lp["w_gate"], lp["w_up"],
                                 lp["w_down"])
                x = x + _maybe_name(y, "ffn_out", cfg)
            else:
                aux = jnp.zeros((), jnp.float32)
                x = x + _maybe_name(_dense_ffn(x, lp, cfg), "ffn_out", cfg)
            return constrain(x), aux

        block = _remat(block, cfg)
        x, auxes = lax.scan(block, x, (params["layers"], flags))
        x = cm.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", x, params["unembed"])
        return logits, jnp.sum(auxes) * cfg.aux_loss_coef

    return forward


def make_loss_fn(cfg: LMConfig, mesh: Optional[Mesh] = None,
                 batch_axes: Optional[Tuple[str, ...]] = ("data",)):
    forward = make_forward(cfg, mesh, batch_axes)

    def loss_fn(params, batch):
        logits, aux = forward(params, batch["tokens"])
        logits = logits.astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, batch["labels"][..., None].astype(jnp.int32),
            axis=-1)[..., 0]
        nll = jnp.mean(logz - gold)
        return nll + aux, {"nll": nll, "aux": aux}

    return loss_fn


# ---------------------------------------------------------------------------
# Serving: prefill + decode with KV cache
# ---------------------------------------------------------------------------

def init_cache(cfg: LMConfig, batch: int, max_len: int) -> Dict[str, Any]:
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_padded, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.jdtype),
            "v": jnp.zeros(shape, cfg.jdtype),
            "pos": jnp.zeros((), jnp.int32)}


def cache_shapes(cfg: LMConfig, batch: int, max_len: int) -> Dict[str, Any]:
    """ShapeDtypeStruct view for dry-runs (no allocation)."""
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_padded, cfg.head_dim)
    return {"k": jax.ShapeDtypeStruct(shape, cfg.jdtype),
            "v": jax.ShapeDtypeStruct(shape, cfg.jdtype),
            "pos": jax.ShapeDtypeStruct((), jnp.int32)}


def cache_pspecs(cfg: LMConfig, batch_axes) -> Dict[str, P]:
    """KV cache sharding: batch over data axes, kv heads over model."""
    kv = P(None, batch_axes, None, "model", None)
    return {"k": kv, "v": kv, "pos": P()}


def make_decode_step(cfg: LMConfig, mesh: Optional[Mesh] = None,
                     batch_axes: Optional[Tuple[str, ...]] = ("data",)):
    """Returns decode(params, cache, tokens (B,1)) -> (logits, cache)."""
    if mesh is None:
        mesh = Mesh(jax.devices()[:1], ("data",))
        batch_axes = None
    moe_ffn = make_moe_ffn(cfg, mesh, batch_axes, seq_len=1) \
        if cfg.moe_experts else None
    seqpar = (cfg.decode_seq_shard and "model" in mesh.axis_names
              and mesh.shape["model"] > 1)
    seqpar_attn = make_seqpar_attention(cfg, mesh) if seqpar else None

    def decode(params, cache, tokens):
        b, s = tokens.shape
        pos = cache["pos"]
        x = params["embed"].at[tokens].get(mode="clip").astype(cfg.jdtype)
        positions = jnp.broadcast_to(pos + jnp.arange(s), (b, s))
        flags = _layer_flags(cfg)

        def block(x, scanned):
            lp, is_global, ck, cv = scanned
            if seqpar:
                assert s == 1, "seq-parallel decode is single-token"
                h = cm.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
                q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
                k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
                v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
                if cfg.chunk_attn == 0:
                    q = cm.rope(q, positions, cfg.rope_theta)
                    k = cm.rope(k, positions, cfg.rope_theta)
                else:
                    q = jnp.where(is_global, q,
                                  cm.rope(q, positions, cfg.rope_theta))
                    k = jnp.where(is_global, k,
                                  cm.rope(k, positions, cfg.rope_theta))
                o, k_new, v_new = seqpar_attn(q, k, v, ck, cv, pos, is_global)
                attn = jnp.einsum("bshk,hkd->bsd", o, lp["wo"]).astype(x.dtype)
                k_new, v_new = k_new, v_new
            else:
                attn, (k_new, v_new) = _attention(
                    x, lp, positions, cfg, is_global,
                    kv_cache=(ck, cv), cache_pos=pos)
            x = x + attn
            if cfg.moe_experts:
                h = cm.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
                y, _ = moe_ffn(h, lp["router"], lp["w_gate"], lp["w_up"],
                               lp["w_down"])
                x = x + y
            else:
                x = x + _dense_ffn(x, lp, cfg)
            return x, (k_new, v_new)

        x, (k_all, v_all) = lax.scan(
            block, x, (params["layers"], flags, cache["k"], cache["v"]))
        x = cm.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", x, params["unembed"])
        new_cache = {"k": k_all, "v": v_all, "pos": pos + s}
        return logits, new_cache

    return decode


def make_prefill(cfg: LMConfig, mesh: Optional[Mesh] = None,
                 batch_axes: Optional[Tuple[str, ...]] = ("data",),
                 max_len: Optional[int] = None):
    """Returns prefill(params, tokens (B,S)) -> (last_logits (B,V), cache).

    Uses the forward-path attention (correct block-diagonal semantics for
    chunked layers) while collecting the per-layer K/V into a fresh cache.
    Only the last position's logits are computed — that is what serving
    needs, and it avoids a (B, S, V) logits buffer at 32k context.
    """
    if mesh is None:
        mesh = Mesh(jax.devices()[:1], ("data",))
        batch_axes = None

    def prefill(params, tokens):
        b, s = tokens.shape
        moe_ffn = make_moe_ffn(cfg, mesh, batch_axes, seq_len=s) \
            if cfg.moe_experts else None
        total = max_len or s
        x = params["embed"].at[tokens].get(mode="clip").astype(cfg.jdtype)
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        flags = _layer_flags(cfg)
        constrain = _seq_constraint(cfg, mesh, batch_axes, s)

        def block(x, scanned):
            lp, is_global = scanned
            x = constrain(x)
            attn, (k_new, v_new) = _attention(x, lp, positions, cfg, is_global)
            x = x + attn
            if cfg.moe_experts:
                h = cm.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
                y, _ = moe_ffn(h, lp["router"], lp["w_gate"], lp["w_up"],
                               lp["w_down"])
                x = x + y
            else:
                x = x + _dense_ffn(x, lp, cfg)
            return constrain(x), (k_new, v_new)

        x, (k_all, v_all) = lax.scan(block, x, (params["layers"], flags))
        x = cm.rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", x, params["unembed"])[:, 0]
        if total > s:
            pad = ((0, 0), (0, 0), (0, total - s), (0, 0), (0, 0))
            k_all = jnp.pad(k_all, pad)
            v_all = jnp.pad(v_all, pad)
        cache = {"k": k_all, "v": v_all,
                 "pos": jnp.asarray(s, jnp.int32)}
        return logits, cache

    return prefill
