"""LM-family transformer: GQA / MLA attention, dense / MoE FFN, RoPE.

Design targets the production mesh (pod, data, model):
  * params stored fp32, FSDP-sharded over ``data`` and TP-sharded over
    ``model``; computed in bf16 (cast at use).
  * activations (B, S, D) sharded over batch = (pod, data); attention heads
    and FFN hidden TP-sharded over ``model``; per-layer psum inserted by the
    SPMD partitioner from the contraction shardings (Megatron pattern).
  * vocab-parallel embedding + vocab-sharded chunked cross-entropy — the
    (B, S, V) logits tensor never exists.
  * MoE: replicated-routing expert parallelism — every model rank routes the
    local token shard, computes only its E/M local experts at fixed capacity
    and psums the combine (no all-to-all; see DESIGN.md §6).  Shared experts
    (DeepSeek) run as a dense TP branch.
  * MLA (DeepSeek-V2): full-rank attention for training; absorbed low-rank
    form for decode so the cache is (c_kv, k_rope) = 576 floats/token.
  * scan over layers (+ remat) keeps HLO size O(1) in depth.
  * decode KV caches shard their sequence axis over ``model``
    (flash-decoding split-K: softmax reductions become all-reduces).
"""

from __future__ import annotations

import dataclasses
import functools

from jax import ad_checkpoint
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


from repro.models import common as cm
from repro.models.common import ShardingCtx, NO_SHARDING


# ---------------------------------------------------------------------------
# configs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int                       # per-expert hidden
    n_shared: int = 0               # shared (always-on) experts
    capacity_factor: float = 1.25
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = 1536
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    tie_embeddings: bool = False
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    first_k_dense: int = 0          # leading dense layers in a MoE model
    gather_weights_at_use: bool = False   # ZeRO-3: all-gather FSDP shards
    microbatch: int = 1             # gradient-accumulation µbatches
    remat: bool = True
    remat_policy: str = "nothing"   # nothing | offload_psum
    attn_chunk_q: int = 1024
    attn_chunk_kv: int = 1024
    xent_chunk: int = 256
    dtype: Any = jnp.bfloat16

    @property
    def dh(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def layer_counts(self) -> Tuple[int, int]:
        """(n_dense_layers, n_moe_layers)."""
        if self.moe is None:
            return self.n_layers, 0
        return self.first_k_dense, self.n_layers - self.first_k_dense

    def param_count(self) -> int:
        """Analytic parameter count (matches init; used for 6·N·D)."""
        import numpy as np
        d, v = self.d_model, self.vocab
        total = v * d * (1 if self.tie_embeddings else 2) + d
        n_dense, n_moe = self.layer_counts()
        total += self.n_layers * 2 * d               # norms
        total += self.n_layers * self._attn_params()
        total += n_dense * 3 * d * self.d_ff
        if self.moe is not None:
            m = self.moe
            per_moe = d * m.n_experts \
                + m.n_experts * 3 * d * m.d_ff \
                + (3 * d * (m.d_ff * m.n_shared) if m.n_shared else 0)
            total += n_moe * per_moe
        return int(total)

    def active_param_count(self) -> int:
        """Activated params per token (MoE: top-k + shared only)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        full = self.param_count()
        n_dense, n_moe = self.layer_counts()
        routed_all = n_moe * m.n_experts * 3 * self.d_model * m.d_ff
        routed_act = n_moe * m.top_k * 3 * self.d_model * m.d_ff
        return int(full - routed_all + routed_act)

    def _attn_params(self) -> int:
        d = self.d_model
        if self.mla is not None:
            a = self.mla
            q_in = a.q_lora_rank or d
            n = 0
            if a.q_lora_rank:
                n += d * a.q_lora_rank + a.q_lora_rank
            n += q_in * self.n_heads * a.qk_dim
            n += d * (a.kv_lora_rank + a.qk_rope_dim) + a.kv_lora_rank
            n += a.kv_lora_rank * self.n_heads * (a.qk_nope_dim + a.v_head_dim)
            n += self.n_heads * a.v_head_dim * d
            return n
        dh = self.dh
        n = d * self.n_heads * dh + 2 * d * self.n_kv_heads * dh \
            + self.n_heads * dh * d
        if self.qkv_bias:
            n += (self.n_heads + 2 * self.n_kv_heads) * dh
        if self.qk_norm:
            n += 2 * dh
        return n


# ---------------------------------------------------------------------------
# parameter init + partition specs
# ---------------------------------------------------------------------------

def _attn_init(cfg: TransformerConfig, key):
    d, dh = cfg.d_model, cfg.dh
    ks = jax.random.split(key, 8)
    if cfg.mla is not None:
        a = cfg.mla
        p = {}
        q_in = d
        if a.q_lora_rank:
            p["wq_a"] = cm.dense_init(ks[0], d, a.q_lora_rank)
            p["q_a_norm"] = cm.rmsnorm_init(a.q_lora_rank)
            q_in = a.q_lora_rank
        p["wq_b"] = cm.dense_init(ks[1], q_in, cfg.n_heads * a.qk_dim)
        p["wkv_a"] = cm.dense_init(ks[2], d, a.kv_lora_rank + a.qk_rope_dim)
        p["kv_a_norm"] = cm.rmsnorm_init(a.kv_lora_rank)
        p["wkv_b"] = cm.dense_init(
            ks[3], a.kv_lora_rank, cfg.n_heads * (a.qk_nope_dim + a.v_head_dim))
        p["wo"] = cm.dense_init(ks[4], cfg.n_heads * a.v_head_dim, d)
        return p
    p = {
        "wq": cm.dense_init(ks[0], d, cfg.n_heads * dh, bias=cfg.qkv_bias),
        "wk": cm.dense_init(ks[1], d, cfg.n_kv_heads * dh, bias=cfg.qkv_bias),
        "wv": cm.dense_init(ks[2], d, cfg.n_kv_heads * dh, bias=cfg.qkv_bias),
        "wo": cm.dense_init(ks[3], cfg.n_heads * dh, d),
    }
    if cfg.qk_norm:
        p["q_norm"] = cm.rmsnorm_init(dh)
        p["k_norm"] = cm.rmsnorm_init(dh)
    return p


def _attn_specs(cfg: TransformerConfig):
    if cfg.mla is not None:
        a = cfg.mla
        p = {}
        if a.q_lora_rank:
            p["wq_a"] = {"w": P("data", None)}
            p["q_a_norm"] = {"scale": P(None)}
        p["wq_b"] = {"w": P("data", "model")}
        p["wkv_a"] = {"w": P("data", None)}
        p["kv_a_norm"] = {"scale": P(None)}
        p["wkv_b"] = {"w": P("data", "model")}
        p["wo"] = {"w": P("model", "data")}
        return p
    kv_shardable = cfg.n_kv_heads % 16 == 0      # heads divide model axis
    kv_spec = P("data", "model") if kv_shardable else P("data", None)
    p = {
        "wq": cm.dense_specs(bias=cfg.qkv_bias, w_spec=P("data", "model")),
        "wk": cm.dense_specs(bias=cfg.qkv_bias, w_spec=kv_spec),
        "wv": cm.dense_specs(bias=cfg.qkv_bias, w_spec=kv_spec),
        "wo": cm.dense_specs(w_spec=P("model", "data")),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": P(None)}
        p["k_norm"] = {"scale": P(None)}
    return p


def _dense_ffn_init(cfg: TransformerConfig, key, d_ff: int):
    ks = jax.random.split(key, 3)
    d = cfg.d_model
    return {"w_gate": cm.dense_init(ks[0], d, d_ff),
            "w_up": cm.dense_init(ks[1], d, d_ff),
            "w_down": cm.dense_init(ks[2], d_ff, d)}


def _dense_ffn_specs():
    return {"w_gate": {"w": P("data", "model")},
            "w_up": {"w": P("data", "model")},
            "w_down": {"w": P("model", "data")}}


def _moe_ffn_init(cfg: TransformerConfig, key):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    std = 1.0 / jnp.sqrt(d)
    p = {
        "router": {"w": jax.random.normal(ks[0], (d, m.n_experts),
                                          jnp.float32) * std},
        "w_gate": jax.random.normal(ks[1], (m.n_experts, d, m.d_ff),
                                    jnp.float32) * std,
        "w_up": jax.random.normal(ks[2], (m.n_experts, d, m.d_ff),
                                  jnp.float32) * std,
        "w_down": jax.random.normal(ks[3], (m.n_experts, m.d_ff, d),
                                    jnp.float32) / jnp.sqrt(m.d_ff),
    }
    if m.n_shared:
        p["shared"] = _dense_ffn_init(cfg, ks[4], m.d_ff * m.n_shared)
    return p


def _moe_ffn_specs(cfg: TransformerConfig):
    p = {
        "router": {"w": P(None, None)},
        "w_gate": P("model", None, "data"),
        "w_up": P("model", None, "data"),
        "w_down": P("model", "data", None),
    }
    if cfg.moe.n_shared:
        p["shared"] = _dense_ffn_specs()
    return p


def _layer_init(cfg: TransformerConfig, key, kind: str):
    k1, k2 = jax.random.split(key)
    p = {"ln1": cm.rmsnorm_init(cfg.d_model),
         "ln2": cm.rmsnorm_init(cfg.d_model),
         "attn": _attn_init(cfg, k1)}
    if kind == "moe":
        p["ffn"] = _moe_ffn_init(cfg, k2)
    else:
        p["ffn"] = _dense_ffn_init(cfg, k2, cfg.d_ff)
    return p


def _layer_specs(cfg: TransformerConfig, kind: str):
    p = {"ln1": {"scale": P(None)}, "ln2": {"scale": P(None)},
         "attn": _attn_specs(cfg)}
    p["ffn"] = _moe_ffn_specs(cfg) if kind == "moe" else _dense_ffn_specs()
    return p


def _stack(leaves):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *leaves)


def init_params(cfg: TransformerConfig, key) -> Dict:
    ke, ko, kl = jax.random.split(key, 3)
    n_dense, n_moe = cfg.layer_counts()
    params: Dict[str, Any] = {
        "embed": jax.random.normal(ke, (cfg.vocab, cfg.d_model),
                                   jnp.float32) * 0.02,
        "final_norm": cm.rmsnorm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["w_out"] = jax.random.normal(
            ko, (cfg.d_model, cfg.vocab), jnp.float32) / jnp.sqrt(cfg.d_model)
    keys = jax.random.split(kl, cfg.n_layers)
    if n_dense:
        params["dense_layers"] = _stack(
            [_layer_init(cfg, keys[i], "dense") for i in range(n_dense)])
    if n_moe:
        params["moe_layers"] = _stack(
            [_layer_init(cfg, keys[n_dense + i], "moe")
             for i in range(n_moe)])
    return params


def param_specs(cfg: TransformerConfig) -> Dict:
    n_dense, n_moe = cfg.layer_counts()
    specs: Dict[str, Any] = {
        "embed": P("model", "data"),
        "final_norm": {"scale": P(None)},
    }
    if not cfg.tie_embeddings:
        specs["w_out"] = P("data", "model")

    def add_layer_dim(spec):
        return P(*((None,) + tuple(spec)))

    if n_dense:
        specs["dense_layers"] = jax.tree_util.tree_map(
            add_layer_dim, _layer_specs(cfg, "dense"),
            is_leaf=lambda x: isinstance(x, P))
    if n_moe:
        specs["moe_layers"] = jax.tree_util.tree_map(
            add_layer_dim, _layer_specs(cfg, "moe"),
            is_leaf=lambda x: isinstance(x, P))
    return specs


# ---------------------------------------------------------------------------
# forward pieces
# ---------------------------------------------------------------------------

def _bf16(t, dtype):
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), t)


def _gw(cfg: TransformerConfig, sc: ShardingCtx, p, out_tp: bool,
        transpose_tp: bool = False):
    """ZeRO-3 weight use: drop the FSDP ('data') sharding at the use site.

    Without this, weights whose *contraction* dim is data-sharded make the
    SPMD partitioner all-reduce the (much larger) activations over the data
    axis; gathering the weight shard instead trades a (B,S,·) psum for a
    (d_in,d_out)/16 all-gather — the ZeRO-3 schedule.  Baseline keeps the
    raw sharding so EXPERIMENTS.md §Perf can show the delta.
    """
    if not (cfg.gather_weights_at_use and sc.enabled):
        return p
    w = p["w"]
    if transpose_tp:
        spec = (sc.model,) + (None,) * (w.ndim - 1)
    elif out_tp:
        spec = (None,) * (w.ndim - 1) + (sc.model,)
    else:
        spec = (None,) * w.ndim
    q = dict(p)
    q["w"] = sc.constrain(w, *spec)
    return q


def _gqa_attention(cfg: TransformerConfig, p, x, sc: ShardingCtx,
                   positions) -> Tuple[jnp.ndarray, Dict]:
    """Training/prefill attention.  Returns (out, kv) with kv for caching."""
    b, s, d = x.shape
    dh = cfg.dh
    kv_tp = cfg.n_kv_heads % 16 == 0
    q = cm.dense(_gw(cfg, sc, p["wq"], True), x).reshape(
        b, s, cfg.n_heads, dh)
    k = cm.dense(_gw(cfg, sc, p["wk"], kv_tp), x).reshape(
        b, s, cfg.n_kv_heads, dh)
    v = cm.dense(_gw(cfg, sc, p["wv"], kv_tp), x).reshape(
        b, s, cfg.n_kv_heads, dh)
    q = sc.constrain(q, sc.batch, None, sc.model, None)
    if cfg.qk_norm:
        q = cm.rmsnorm(p["q_norm"], q)
        k = cm.rmsnorm(p["k_norm"], k)
    q = cm.apply_rope(q.swapaxes(1, 2), positions[:, None, :],
                      cfg.rope_theta)                       # (B, Hq, S, dh)
    k = cm.apply_rope(k.swapaxes(1, 2), positions[:, None, :],
                      cfg.rope_theta)                       # (B, Hkv, S, dh)
    v = v.swapaxes(1, 2)
    out = cm.chunked_attention(q, k, v, causal=True,
                               chunk_q=min(cfg.attn_chunk_q, s),
                               chunk_kv=min(cfg.attn_chunk_kv, s))
    out = out.swapaxes(1, 2).reshape(b, s, cfg.n_heads * dh)
    out = cm.dense(_gw(cfg, sc, p["wo"], False, transpose_tp=True), out)
    return out, {"k": k, "v": v}


def _mla_attention(cfg: TransformerConfig, p, x, sc: ShardingCtx,
                   positions) -> Tuple[jnp.ndarray, Dict]:
    """MLA training/prefill attention (full-rank form)."""
    a = cfg.mla
    b, s, d = x.shape
    h = cfg.n_heads
    if a.q_lora_rank:
        q_in = cm.rmsnorm(p["q_a_norm"],
                          cm.dense(_gw(cfg, sc, p["wq_a"], False), x))
    else:
        q_in = x
    q = cm.dense(_gw(cfg, sc, p["wq_b"], True), q_in).reshape(
        b, s, h, a.qk_dim)
    q = sc.constrain(q, sc.batch, None, sc.model, None)
    q_nope, q_rope = jnp.split(q, [a.qk_nope_dim], axis=-1)
    q_rope = cm.apply_rope(q_rope.swapaxes(1, 2), positions[:, None, :],
                           cfg.rope_theta).swapaxes(1, 2)

    kv_a = cm.dense(_gw(cfg, sc, p["wkv_a"], False), x)     # (B,S,rank+rope)
    c_kv, k_rope = jnp.split(kv_a, [a.kv_lora_rank], axis=-1)
    c_kv = cm.rmsnorm(p["kv_a_norm"], c_kv)
    k_rope = cm.apply_rope(k_rope[:, None], positions[:, None, :],
                           cfg.rope_theta)                  # (B,1,S,rope)
    kv = cm.dense(_gw(cfg, sc, p["wkv_b"], True), c_kv).reshape(
        b, s, h, a.qk_nope_dim + a.v_head_dim)
    kv = sc.constrain(kv, sc.batch, None, sc.model, None)
    k_nope, v = jnp.split(kv, [a.qk_nope_dim], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope.swapaxes(1, 2),
                                  (b, s, h, a.qk_rope_dim))], axis=-1)

    qh = jnp.concatenate([q_nope, q_rope], -1).swapaxes(1, 2)  # (B,H,S,qk)
    kh = k.swapaxes(1, 2)
    vh = v.swapaxes(1, 2)                                      # (B,H,S,v)
    out = cm.chunked_attention(qh, kh, vh, causal=True,
                               scale=1.0 / (a.qk_dim ** 0.5),
                               chunk_q=min(cfg.attn_chunk_q, s),
                               chunk_kv=min(cfg.attn_chunk_kv, s))
    out = out.swapaxes(1, 2).reshape(b, s, h * a.v_head_dim)
    out = cm.dense(_gw(cfg, sc, p["wo"], False, transpose_tp=True), out)
    return out, {"c_kv": c_kv, "k_rope": k_rope[:, 0]}


def _dense_ffn(p, x, sc: ShardingCtx, cfg: TransformerConfig = None):
    if cfg is not None:
        p = {"w_gate": _gw(cfg, sc, p["w_gate"], True),
             "w_up": _gw(cfg, sc, p["w_up"], True),
             "w_down": _gw(cfg, sc, p["w_down"], False, transpose_tp=True)}
    h = cm.swiglu(cm.dense(p["w_gate"], x), cm.dense(p["w_up"], x))
    h = sc.constrain(h, sc.batch, None, sc.model)
    return cm.dense(p["w_down"], h)


def _moe_ffn(cfg: TransformerConfig, p, x, sc: ShardingCtx,
             capacity_factor: float | None = None):
    """Replicated-routing expert parallelism over the ``model`` axis.

    Every model rank routes the full local token shard; rank m computes only
    its E/M local experts at fixed capacity; combine is a psum (the same
    collective the dense-TP FFN needs, so the MoE adds no new comm pattern).
    Runs under shard_map over the whole mesh; token batch stays sharded over
    (pod, data) and is replicated over model — exactly the activation layout
    of the surrounding attention layers.
    """
    m = cfg.moe
    b, s, d = x.shape
    cf = capacity_factor or m.capacity_factor

    def local_moe(xl, router_w, w_gate, w_up, w_down):
        # xl: (b_loc, s, d) local token shard; expert weights: local E/M
        # shard, FSDP-gathered over 'data' (tiled all_gather on the ff dim).
        if sc.enabled:
            w_gate = jax.lax.all_gather(w_gate, "data", axis=2, tiled=True)
            w_up = jax.lax.all_gather(w_up, "data", axis=2, tiled=True)
            w_down = jax.lax.all_gather(w_down, "data", axis=1, tiled=True)
            m_rank = jax.lax.axis_index("model")
            n_model = jax.lax.axis_size("model")
        else:
            m_rank, n_model = 0, 1
        e_loc = w_gate.shape[0]
        t = xl.shape[0] * xl.shape[1]
        xt = xl.reshape(t, d)

        logits = (xt @ router_w.astype(xt.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)               # (T, E)
        gate_vals, exp_idx = jax.lax.top_k(probs, m.top_k)    # (T, K)
        if m.norm_topk_prob:
            gate_vals = gate_vals / jnp.maximum(
                jnp.sum(gate_vals, -1, keepdims=True), 1e-9)
        gate_vals = gate_vals * m.routed_scaling_factor

        # flatten assignments; keep only experts local to this model rank
        flat_e = exp_idx.reshape(-1)                          # (T*K,)
        flat_g = gate_vals.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(t), m.top_k)
        local = (flat_e // e_loc) == m_rank
        loc_e = jnp.where(local, flat_e % e_loc, e_loc)       # e_loc = drop
        # position of each assignment within its expert (capacity slotting)
        onehot = jax.nn.one_hot(loc_e, e_loc + 1, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1
        capacity = max(int(t * m.top_k / m.n_experts * cf), 4)
        keep = local & (pos < capacity)
        slot_e = jnp.where(keep, loc_e, e_loc)                # drop → pad row
        slot_p = jnp.where(keep, pos, 0)

        # dispatch: gather token features into (E_loc+1, C, D); pad row last
        buf = jnp.zeros((e_loc + 1, capacity, d), xt.dtype)
        buf = buf.at[slot_e, slot_p].set(xt[flat_t], mode="drop")
        buf = buf[:e_loc]

        hh = cm.swiglu(jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(xt.dtype)),
                       jnp.einsum("ecd,edf->ecf", buf, w_up.astype(xt.dtype)))
        out = jnp.einsum("ecf,efd->ecd", hh, w_down.astype(xt.dtype))

        # combine: weighted scatter-add back to token rows
        contrib = out[slot_e.clip(0, e_loc - 1), slot_p] * \
            flat_g[:, None].astype(out.dtype)
        contrib = jnp.where(keep[:, None], contrib, 0.0)
        y = jnp.zeros((t, d), out.dtype).at[flat_t].add(contrib)
        if sc.enabled:
            y = jax.lax.psum(y, "model")
        return y.reshape(xl.shape)

    if not sc.enabled:
        y = local_moe(x, p["router"]["w"], p["w_gate"], p["w_up"],
                      p["w_down"])
    else:
        mesh = sc.mesh
        if mesh is None:
            raise ValueError("sharded MoE needs ShardingCtx.mesh")
        y = jax.shard_map(
            local_moe, mesh=mesh,
            in_specs=(P(sc.batch, None, None), P(None, None),
                      P("model", None, "data"), P("model", None, "data"),
                      P("model", "data", None)),
            out_specs=P(sc.batch, None, None),
            check_vma=False,
        )(x, p["router"]["w"], p["w_gate"], p["w_up"], p["w_down"])

    if m.n_shared:
        y = y + _dense_ffn(p["shared"], x, sc, cfg)
    return y


def _layer_fwd(cfg: TransformerConfig, kind: str, p, x, sc: ShardingCtx,
               positions):
    attn_fn = _mla_attention if cfg.mla is not None else _gqa_attention
    h, kv = attn_fn(cfg, p["attn"], cm.rmsnorm(p["ln1"], x), sc, positions)
    if cfg.remat_policy == "offload_psum":
        # name the psum'd tensors so the remat policy can offload them to
        # host instead of re-running their collectives in the backward pass
        h = ad_checkpoint.checkpoint_name(h, "attn_out")
    x = sc.constrain(x + h, sc.batch, None, None)
    ffn_in = cm.rmsnorm(p["ln2"], x)
    if kind == "moe":
        f = _moe_ffn(cfg, p["ffn"], ffn_in, sc)
    else:
        f = _dense_ffn(p["ffn"], ffn_in, sc, cfg)
    if cfg.remat_policy == "offload_psum":
        f = ad_checkpoint.checkpoint_name(f, "ffn_out")
    x = sc.constrain(x + f, sc.batch, None, None)
    return x, kv


def _run_stack(cfg: TransformerConfig, kind: str, stacked, x, sc,
               positions, collect_kv: bool):
    def body(layer_p, h, pos):
        return _layer_fwd(cfg, kind, layer_p, h, sc, pos)

    if cfg.remat:
        if cfg.remat_policy == "offload_psum":
            policy = jax.checkpoint_policies.save_and_offload_only_these_names(
                names_which_can_be_saved=[],
                names_which_can_be_offloaded=["attn_out", "ffn_out"],
                offload_src="device", offload_dst="pinned_host")
        else:
            policy = jax.checkpoint_policies.nothing_saveable
        body = jax.checkpoint(body, policy=policy)

    def scan_fn(h, layer_p):
        h, kv = body(layer_p, h, positions)
        return h, (kv if collect_kv else None)

    x, kvs = jax.lax.scan(scan_fn, x, stacked)
    return x, kvs


def forward(cfg: TransformerConfig, params, tokens, sc: ShardingCtx = NO_SHARDING,
            collect_kv: bool = False):
    """tokens (B, S) → final hidden (B, S, D) [+ per-layer kv for caching]."""
    b, s = tokens.shape
    dt = cfg.dtype
    embed = params["embed"].astype(dt)
    embed = sc.constrain(embed, sc.model, None)
    x = jnp.take(embed, tokens, axis=0)
    x = sc.constrain(x, sc.batch, None, None)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    kv_all = {}
    n_dense, n_moe = cfg.layer_counts()
    if n_dense:
        x, kv = _run_stack(cfg, "dense", _bf16(params["dense_layers"], dt),
                           x, sc, positions, collect_kv)
        kv_all["dense"] = kv
    if n_moe:
        x, kv = _run_stack(cfg, "moe", _bf16(params["moe_layers"], dt),
                           x, sc, positions, collect_kv)
        kv_all["moe"] = kv
    x = cm.rmsnorm(params["final_norm"], x)
    if collect_kv:
        return x, kv_all
    return x


def output_weights(cfg: TransformerConfig, params, sc: ShardingCtx):
    if cfg.tie_embeddings:
        w = params["embed"].T
    else:
        w = params["w_out"]
    w = w.astype(cfg.dtype)
    return sc.constrain(w, None, sc.model)


def loss_fn(cfg: TransformerConfig, params, batch,
            sc: ShardingCtx = NO_SHARDING) -> jnp.ndarray:
    """batch: {"tokens": (B, S), "labels": (B, S) with -1 ignore}."""
    h = forward(cfg, params, batch["tokens"], sc)
    w_out = output_weights(cfg, params, sc)
    spec = P(sc.batch, None, sc.model) if sc.enabled else None
    return cm.chunked_softmax_xent(h, w_out, batch["labels"],
                                   chunk=cfg.xent_chunk, spec=spec)


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------

def init_cache(cfg: TransformerConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict:
    """Allocate the decode cache pytree (layer-major for lax.scan)."""
    L = cfg.n_layers
    if cfg.mla is not None:
        a = cfg.mla
        return {
            "c_kv": jnp.zeros((L, batch, max_len, a.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((L, batch, max_len, a.qk_rope_dim), dtype),
            "len": jnp.zeros((batch,), jnp.int32),
        }
    return {
        "k": jnp.zeros((L, batch, cfg.n_kv_heads, max_len, cfg.dh), dtype),
        "v": jnp.zeros((L, batch, cfg.n_kv_heads, max_len, cfg.dh), dtype),
        "len": jnp.zeros((batch,), jnp.int32),
    }


def cache_specs(cfg: TransformerConfig,
                batch_axes=("pod", "data")) -> Dict:
    """Decode caches: sequence axis sharded over model (flash-decoding)."""
    if cfg.mla is not None:
        return {"c_kv": P(None, batch_axes, "model", None),
                "k_rope": P(None, batch_axes, "model", None),
                "len": P(batch_axes)}
    return {"k": P(None, batch_axes, None, "model", None),
            "v": P(None, batch_axes, None, "model", None),
            "len": P(batch_axes)}


def prefill(cfg: TransformerConfig, params, tokens,
            sc: ShardingCtx = NO_SHARDING, max_len: int | None = None):
    """Run the prompt, return (last-position logits, populated cache)."""
    b, s = tokens.shape
    max_len = max_len or s
    h, kvs = forward(cfg, params, tokens, sc, collect_kv=True)
    w_out = output_weights(cfg, params, sc)
    last = h[:, -1]
    logits = last.astype(jnp.float32) @ w_out.astype(jnp.float32)

    cache = init_cache(cfg, b, max_len, cfg.dtype)
    parts = []
    if "dense" in kvs and kvs["dense"] is not None:
        parts.append(kvs["dense"])
    if "moe" in kvs and kvs["moe"] is not None:
        parts.append(kvs["moe"])
    merged = jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, 0), *parts) if len(parts) > 1 \
        else parts[0]
    if cfg.mla is not None:
        # merged: c_kv (L,B,S,rank), k_rope (L,B,S,rope)
        cache["c_kv"] = jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], merged["c_kv"].astype(cfg.dtype), 0, axis=2)
        cache["k_rope"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], merged["k_rope"].astype(cfg.dtype), 0, axis=2)
    else:
        # merged k/v: (L, B, Hkv, S, dh)
        cache["k"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], merged["k"].astype(cfg.dtype), 0, axis=3)
        cache["v"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], merged["v"].astype(cfg.dtype), 0, axis=3)
    cache["len"] = jnp.full((b,), s, jnp.int32)
    return logits, cache


def _gqa_decode_layer(cfg, p, x, layer_cache, cache_len, sc):
    b = x.shape[0]
    dh = cfg.dh
    pos = cache_len[:, None]                                   # (B, 1)
    q = cm.dense(p["wq"], x).reshape(b, 1, cfg.n_heads, dh)
    k = cm.dense(p["wk"], x).reshape(b, 1, cfg.n_kv_heads, dh)
    v = cm.dense(p["wv"], x).reshape(b, 1, cfg.n_kv_heads, dh)
    if cfg.qk_norm:
        q = cm.rmsnorm(p["q_norm"], q)
        k = cm.rmsnorm(p["k_norm"], k)
    q = cm.apply_rope(q.swapaxes(1, 2), pos[:, None, :], cfg.rope_theta)
    k = cm.apply_rope(k.swapaxes(1, 2), pos[:, None, :], cfg.rope_theta)
    v = v.swapaxes(1, 2)
    kc = _cache_insert(layer_cache["k"], k.astype(layer_cache["k"].dtype),
                       cache_len)
    vc = _cache_insert(layer_cache["v"], v.astype(layer_cache["v"].dtype),
                       cache_len)
    out = cm.decode_attention(q, kc, vc, cache_len + 1)
    out = out.reshape(b, 1, cfg.n_heads * dh)
    return cm.dense(p["wo"], out), {"k": kc, "v": vc}


def _mla_decode_layer(cfg, p, x, layer_cache, cache_len, sc):
    """Absorbed-matmul MLA decode: cache stays in the 576-dim latent space."""
    a = cfg.mla
    b = x.shape[0]
    h = cfg.n_heads
    pos = cache_len[:, None]
    if a.q_lora_rank:
        q_in = cm.rmsnorm(p["q_a_norm"], cm.dense(p["wq_a"], x))
    else:
        q_in = x
    q = cm.dense(p["wq_b"], q_in).reshape(b, h, a.qk_dim)
    q_nope, q_rope = jnp.split(q, [a.qk_nope_dim], axis=-1)
    q_rope = cm.apply_rope(q_rope[:, :, None, :],
                           pos[:, None, :], cfg.rope_theta)[:, :, 0]

    kv_a = cm.dense(p["wkv_a"], x)[:, 0]                      # (B, rank+rope)
    c_kv_new, k_rope_new = jnp.split(kv_a, [a.kv_lora_rank], axis=-1)
    c_kv_new = cm.rmsnorm(p["kv_a_norm"], c_kv_new)
    k_rope_new = cm.apply_rope(k_rope_new[:, None], pos, cfg.rope_theta)[:, 0]

    ckv = _cache_insert_2d(layer_cache["c_kv"],
                           c_kv_new.astype(layer_cache["c_kv"].dtype),
                           cache_len)
    krope = _cache_insert_2d(layer_cache["k_rope"],
                             k_rope_new.astype(layer_cache["k_rope"].dtype),
                             cache_len)

    # absorb W_kv_b's key half into the query
    wkv_b = p["wkv_b"]["w"].reshape(a.kv_lora_rank, h,
                                    a.qk_nope_dim + a.v_head_dim)
    wk_b, wv_b = wkv_b[..., :a.qk_nope_dim], wkv_b[..., a.qk_nope_dim:]
    q_lat = jnp.einsum("bhn,lhn->bhl", q_nope.astype(jnp.float32),
                       wk_b.astype(jnp.float32))              # (B,H,rank)
    scores = jnp.einsum("bhl,bsl->bhs", q_lat, ckv.astype(jnp.float32)) \
        + jnp.einsum("bhr,bsr->bhs", q_rope.astype(jnp.float32),
                     krope.astype(jnp.float32))
    scores = scores / (a.qk_dim ** 0.5)
    mask = jnp.arange(ckv.shape[1])[None] < (cache_len + 1)[:, None]
    scores = jnp.where(mask[:, None], scores, cm.NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bhs,bsl->bhl", w, ckv.astype(jnp.float32))
    out = jnp.einsum("bhl,lhv->bhv", o_lat, wv_b.astype(jnp.float32))
    out = out.reshape(b, 1, h * a.v_head_dim).astype(x.dtype)
    return cm.dense(p["wo"], out), {"c_kv": ckv, "k_rope": krope}


def _cache_insert(cache, new, cache_len):
    """cache (B, H, S, D), new (B, H, 1, D), per-batch position."""
    s = cache.shape[2]
    onehot = jax.nn.one_hot(cache_len, s, dtype=cache.dtype)  # (B, S)
    return cache * (1 - onehot[:, None, :, None]) + \
        new * onehot[:, None, :, None]


def _cache_insert_2d(cache, new, cache_len):
    """cache (B, S, D), new (B, D)."""
    s = cache.shape[1]
    onehot = jax.nn.one_hot(cache_len, s, dtype=cache.dtype)
    return cache * (1 - onehot[..., None]) + new[:, None] * onehot[..., None]


def decode_step(cfg: TransformerConfig, params, tokens, cache,
                sc: ShardingCtx = NO_SHARDING):
    """One token for every sequence.  tokens (B, 1) → (logits, new cache)."""
    b = tokens.shape[0]
    dt = cfg.dtype
    cache_len = cache["len"]
    embed = params["embed"].astype(dt)
    embed = sc.constrain(embed, sc.model, None)
    x = jnp.take(embed, tokens, axis=0)
    x = sc.constrain(x, sc.batch, None, None)

    n_dense, n_moe = cfg.layer_counts()
    decode_layer = _mla_decode_layer if cfg.mla is not None \
        else _gqa_decode_layer
    cache_keys = [k for k in cache if k != "len"]

    def make_scan(kind):
        def scan_fn(h, xs):
            layer_p, layer_cache = xs
            ffn_in_attn = cm.rmsnorm(layer_p["ln1"], h)
            att, new_c = decode_layer(cfg, layer_p["attn"], ffn_in_attn,
                                      layer_cache, cache_len, sc)
            h = h + att
            ffn_in = cm.rmsnorm(layer_p["ln2"], h)
            if kind == "moe":
                f = _moe_ffn(cfg, layer_p["ffn"], ffn_in, sc)
            else:
                f = _dense_ffn(layer_p["ffn"], ffn_in, sc)
            h = h + f
            return h, new_c
        return scan_fn

    new_cache = dict(cache)
    off = 0
    for kind, field in (("dense", "dense_layers"), ("moe", "moe_layers")):
        if field not in params:
            continue
        n = (n_dense if kind == "dense" else n_moe)
        layer_caches = {k: jax.lax.dynamic_slice_in_dim(cache[k], off, n, 0)
                        for k in cache_keys}
        x, upd = jax.lax.scan(make_scan(kind),
                              x, (_bf16(params[field], dt), layer_caches))
        for k in cache_keys:
            new_cache[k] = jax.lax.dynamic_update_slice_in_dim(
                new_cache[k], upd[k], off, axis=0)
        off += n

    x = cm.rmsnorm(params["final_norm"], x)
    w_out = output_weights(cfg, params, sc)
    logits = x[:, 0].astype(jnp.float32) @ w_out.astype(jnp.float32)
    new_cache["len"] = cache_len + 1
    return logits, new_cache
