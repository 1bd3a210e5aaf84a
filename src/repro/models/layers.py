"""Model-layer primitives shared by every assigned architecture.

Pure JAX (no flax).  Parameters are plain pytrees of jnp arrays; every layer is a
function ``(params, x, ...) -> y``.  Sharding is expressed two ways:

* GSPMD ``with_sharding_constraint`` hints on activations (no-ops off-mesh), and
* an explicit ``shard_map`` expert-parallel path for MoE (the only layer whose
  collective pattern GSPMD cannot be trusted to infer at 480B scale).

All attention variants route through :func:`attention_core` /
:func:`chunked_attention` so the 32k-prefill cells never materialise an
``[B, H, S, S]`` score tensor.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# Sharding helpers
# ---------------------------------------------------------------------------

def shard(x: jax.Array, spec: Optional[P]) -> jax.Array:
    """Apply a sharding constraint if we are tracing under a mesh."""
    if spec is None:
        return x
    try:
        return lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        # Not under a mesh (unit tests / pure-CPU smoke) — constraint is a hint only.
        return x


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Logical→mesh axis mapping used by every layer.

    ``batch`` may span several mesh axes (("pod", "data")), ``tensor`` is the
    Megatron tensor-parallel axis, ``fsdp`` the parameter-sharding axis.  Any
    field may be None to disable that form of parallelism (single-host smoke).
    """

    batch: Any = None          # e.g. ("pod", "data") or "data"
    tensor: Any = None         # e.g. "model"
    fsdp: Any = None           # e.g. "data"
    # When the global batch is too small to occupy the batch axes (long_500k has
    # batch=1) the runner sets ``seq_shards`` so long sequence/state dims are
    # sharded over every axis instead.
    seq: Any = None            # axes for long sequence dims in decode
    # Sequence parallelism (train/prefill): residual-stream activations at
    # layer boundaries are sharded over this axis so the remat-saved stack is
    # 1/TP the size; GSPMD turns the row-parallel psum into a reduce-scatter
    # and inserts the SP all-gather at the next matmul.
    act_seq: Any = None
    # MoE weight handling: True gathers FSDP-sharded expert weights on use
    # (right for training, where every token batch reuses them); False keeps
    # weights 2-D sharded (E over tensor, D/F over fsdp) and gathers TOKENS
    # over the batch axes instead, psumming the tiny expert activations —
    # the decode regime, where weights are read once per token and the
    # per-step gather of multi-GB expert tensors is pure waste (§Perf).
    moe_gather_weights: bool = True
    # Sequence-parallel attention: keep Q (and the residual) seq-sharded
    # through the attention block and all-gather only the K/V heads —
    # n_kv·hd bytes instead of d_model per token.  Wins when
    # n_kv·hd ≪ d_model (GQA at large d_model: llama-90b gathers 8×128
    # instead of 8192 per token, ~8× less attention-path gather traffic);
    # the attention weights are gathered over the tensor axis instead
    # (≈MBs — amortised over the whole batch).
    seq_parallel_attn: bool = False

    def act(self, *rest) -> Optional[P]:
        """Spec for an activation whose leading dim is batch."""
        if self.batch is None and all(r is None for r in rest):
            return None
        return P(self.batch, *rest)

    def residual(self) -> Optional[P]:
        """Spec for the [B, S, D] residual stream at layer boundaries."""
        return self.act(self.act_seq, None)


NO_SHARD = ShardingRules()


# ---------------------------------------------------------------------------
# Basic blocks
# ---------------------------------------------------------------------------

def rms_norm(scale: jax.Array, x: jax.Array, eps: float = 1e-6,
             plus_one: bool = False) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * lax.rsqrt(var + eps)
    w = scale.astype(jnp.float32)
    if plus_one:                      # gemma stores scale as (1 + w)
        w = 1.0 + w
    return (y * w).astype(x.dtype)


def layer_norm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + eps)
    return (y * params["scale"] + params["bias"]).astype(x.dtype)


def apply_norm(params, x, *, kind: str, eps: float, plus_one: bool = False):
    if kind == "layernorm":
        return layer_norm(params, x, eps)
    return rms_norm(params["scale"], x, eps, plus_one=plus_one)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding.  x: [..., S, H, D_head]; positions: [S] or [B, S]."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq          # [..., S, half]
    # broadcast over head dim: [..., S, 1, half]
    ang = ang[..., None, :]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _act(name: str, x: jax.Array) -> jax.Array:
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(f"unknown activation {name}")


def mlp(params: dict, x: jax.Array, *, activation: str, glu: bool,
        rules: ShardingRules = NO_SHARD) -> jax.Array:
    """(Gated) MLP.  Column-parallel up/gate, row-parallel down."""
    h = x @ params["up"]
    if glu:
        g = x @ params["gate"]
        h = _act(activation, g) * h
    else:
        h = _act(activation, h)
    h = shard(h, rules.act(None, rules.tensor))
    out = h @ params["down"]
    return shard(out, rules.residual())


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def _qkv(params: dict, x: jax.Array, *, n_heads: int, n_kv: int, head_dim: int,
         qkv_bias: bool):
    B, S, _ = x.shape
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(B, S, n_heads, head_dim)
    k = k.reshape(B, S, n_kv, head_dim)
    v = v.reshape(B, S, n_kv, head_dim)
    return q, k, v


def _repeat_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """[B, S, KV, D] -> [B, S, H, D] by repeating each kv head."""
    n_kv = k.shape[-2]
    if n_kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // n_kv, axis=-2)


def attention_core(q, k, v, *, causal: bool, window: int = 0,
                   q_offset: int | jax.Array = 0,
                   kv_valid: Optional[jax.Array] = None) -> jax.Array:
    """Dense attention.  q: [B, Sq, H, D]; k, v: [B, Sk, H, D].

    ``q_offset`` is the absolute position of q[0] (decode: current pos).
    ``kv_valid`` optionally masks cache slots ([B, Sk] or [Sk]).
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    qpos = jnp.arange(Sq) + q_offset                     # [Sq]
    kpos = jnp.arange(Sk)                                # [Sk]
    mask = jnp.ones((Sq, Sk), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > qpos[:, None] - window
    mask = mask[None, None]
    if kv_valid is not None:
        kvm = kv_valid if kv_valid.ndim == 2 else kv_valid[None]
        mask = mask & kvm[:, None, None, :]
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def chunked_attention(q, k, v, *, causal: bool, window: int = 0,
                      chunk: int = 1024) -> jax.Array:
    """Online-softmax attention, scanning over KV chunks.

    Never materialises [B, H, Sq, Sk]; peak transient is [B, H, Sq, chunk].
    Used for the 32k-prefill cells; also the jnp oracle shape for a future
    Pallas flash kernel.
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    if Sk <= chunk:
        return attention_core(q, k, v, causal=causal, window=window)
    n_chunks = -(-Sk // chunk)
    pad = n_chunks * chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, n_chunks, chunk, H, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, H, D).transpose(1, 0, 2, 3, 4)
    scale = 1.0 / math.sqrt(D)
    qpos = jnp.arange(Sq)

    # flash-style: recompute chunk probabilities in the backward pass instead
    # of letting scan stack [n_chunks, B, H, Sq, chunk] f32 residuals.
    @jax.checkpoint
    def body(carry, xs):
        m, l, acc = carry                     # [B,H,Sq], [B,H,Sq], [B,H,Sq,D]
        ci, (kb, vb) = xs
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kb,
                            preferred_element_type=jnp.float32) * scale
        kpos = ci * chunk + jnp.arange(chunk)
        mask = kpos[None, :] < Sk
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        logits = jnp.where(mask[None, None], logits, -1e30)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(q.dtype), vb).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, D), jnp.float32)
    (m, l, acc), _ = lax.scan(body, (m0, l0, a0),
                              (jnp.arange(n_chunks), (kc, vc)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)      # [B, Sq, H, D]


def self_attention(params: dict, x: jax.Array, *, n_heads: int, n_kv: int,
                   head_dim: int, qkv_bias: bool, rope_theta: float,
                   causal: bool, window: int, positions: jax.Array,
                   use_rope: bool = True, chunk_threshold: int = 2048,
                   rules: ShardingRules = NO_SHARD) -> jax.Array:
    """Full-sequence self-attention (train / prefill path).

    Default sharding: q is head-sharded over the tensor axis; k/v are
    explicitly *replicated* over it (GQA kv-head counts rarely divide the
    16-way axis, and letting GSPMD split 2 kv heads over 16 devices
    triggers involuntary full rematerialisation — one small all-gather of
    k/v is far cheaper).

    ``rules.seq_parallel_attn``: q and the residual stay seq-sharded over
    the tensor axis and only K/V are gathered — n_kv·hd per token instead
    of d_model (8× less on llama-90b's GQA).
    """
    q, k, v = _qkv(params, x, n_heads=n_heads, n_kv=n_kv, head_dim=head_dim,
                   qkv_bias=qkv_bias)
    if use_rope:
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)
    sp = rules.seq_parallel_attn and rules.act_seq is not None
    if sp:
        q = shard(q, rules.act(rules.act_seq, None, None))
        k = shard(k, rules.act(None, None, None))
        v = shard(v, rules.act(None, None, None))
    else:
        q = shard(q, rules.act(None, rules.tensor, None))
        k = shard(k, rules.act(None, None, None))
        v = shard(v, rules.act(None, None, None))
    kf = _repeat_kv(k, n_heads)
    vf = _repeat_kv(v, n_heads)
    if x.shape[1] > chunk_threshold:
        o = chunked_attention(q, kf, vf, causal=causal, window=window)
    else:
        o = attention_core(q, kf, vf, causal=causal, window=window)
    o = o.reshape(x.shape[0], x.shape[1], n_heads * head_dim)
    o = shard(o, rules.act(rules.act_seq, None) if sp
              else rules.act(None, rules.tensor))
    out = o @ params["wo"]
    return shard(out, rules.residual())


def cross_attention(params: dict, x: jax.Array, kv_src: jax.Array | tuple,
                    *, n_heads: int, n_kv: int, head_dim: int, qkv_bias: bool,
                    rules: ShardingRules = NO_SHARD) -> jax.Array:
    """Cross-attention.  ``kv_src`` is either the encoder/patch sequence
    [B, Se, D] (keys projected here) or a precomputed (k, v) tuple (decode)."""
    B, Sq, _ = x.shape
    q = x @ params["wq"]
    if qkv_bias:
        q = q + params["bq"]
    q = q.reshape(B, Sq, n_heads, head_dim)
    if isinstance(kv_src, tuple):
        k, v = kv_src
    else:
        k, v = project_cross_kv(params, kv_src, n_kv=n_kv, head_dim=head_dim,
                                qkv_bias=qkv_bias)
    kf = _repeat_kv(k, n_heads)
    vf = _repeat_kv(v, n_heads)
    o = attention_core(q, kf, vf, causal=False)
    o = o.reshape(B, Sq, n_heads * head_dim)
    return shard(o @ params["wo"], rules.residual())


def project_cross_kv(params: dict, kv_src: jax.Array, *, n_kv: int,
                     head_dim: int, qkv_bias: bool):
    B, Se, _ = kv_src.shape
    k = kv_src @ params["wk"]
    v = kv_src @ params["wv"]
    if qkv_bias:
        k = k + params["bk"]
        v = v + params["bv"]
    return (k.reshape(B, Se, n_kv, head_dim), v.reshape(B, Se, n_kv, head_dim))


# ---------------------------------------------------------------------------
# Decode-path attention (KV cache, ring buffers for windows)
# ---------------------------------------------------------------------------

def decode_self_attention(params: dict, x: jax.Array, cache_k: jax.Array,
                          cache_v: jax.Array, pos: jax.Array, *, n_heads: int,
                          n_kv: int, head_dim: int, qkv_bias: bool,
                          rope_theta: float, window: int,
                          use_rope: bool = True,
                          rules: ShardingRules = NO_SHARD):
    """One-token decode.  x: [B, 1, D]; cache_k/v: [B, S_cache, KV, D_head].

    For windowed layers the cache is a ring buffer of size ``window``; for
    global layers S_cache is the full max context.  Returns (out, ck, cv).
    """
    B = x.shape[0]
    S_cache = cache_k.shape[1]
    q, k, v = _qkv(params, x, n_heads=n_heads, n_kv=n_kv, head_dim=head_dim,
                   qkv_bias=qkv_bias)
    if use_rope:
        posv = jnp.full((1,), pos)
        q = rope(q, posv, rope_theta)
        k = rope(k, posv, rope_theta)
    slot = jnp.where(window > 0, pos % S_cache, pos) if window else pos
    cache_k = lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype),
                                       (0, slot, 0, 0))
    cache_v = lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype),
                                       (0, slot, 0, 0))
    cache_k = shard(cache_k, rules.act(rules.seq, None, None))
    cache_v = shard(cache_v, rules.act(rules.seq, None, None))
    # validity: slot i holds position (for ring: the newest S_cache positions)
    idx = jnp.arange(S_cache)
    valid = idx <= pos if not window else (
        (idx <= pos) & (idx > pos - S_cache) | (pos >= S_cache))
    kf = _repeat_kv(cache_k.astype(q.dtype), n_heads)
    vf = _repeat_kv(cache_v.astype(q.dtype), n_heads)
    scale = 1.0 / math.sqrt(head_dim)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kf,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(valid[None, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, vf)
    o = o.reshape(B, 1, n_heads * head_dim)
    out = o @ params["wo"]
    return shard(out, rules.residual()), cache_k, cache_v


# ---------------------------------------------------------------------------
# Mixture of Experts (expert-parallel over the tensor axis via shard_map)
# ---------------------------------------------------------------------------

def moe_router(wg: jax.Array, x: jax.Array, top_k: int):
    """x: [T, D] -> (gates [T,k] fp32 normalised, idx [T,k] int32)."""
    logits = (x @ wg).astype(jnp.float32)
    gate_logits, idx = lax.top_k(logits, top_k)
    gates = jax.nn.softmax(gate_logits, axis=-1)
    return gates, idx


def _moe_local_compute(x, gates, idx, w_up, w_gate, w_down, *,
                       n_experts: int, top_k: int, capacity: int,
                       activation: str, e_start: int):
    """Dense grouped compute for the experts this shard owns.

    x: [T, D]; w_*: [E_loc, ...]; returns partial output [T, D] containing the
    contribution of experts [e_start, e_start + E_loc).
    """
    T, D = x.shape
    E_loc = w_up.shape[0]
    flat_e = idx.reshape(-1)                                  # [T*k]
    flat_g = gates.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), top_k)
    local = (flat_e >= e_start) & (flat_e < e_start + E_loc)
    loc_e = jnp.where(local, flat_e - e_start, E_loc)         # E_loc = drop bin
    # position of each assignment within its expert, via sorted ranking
    order = jnp.argsort(loc_e, stable=True)                   # [T*k]
    sorted_e = loc_e[order]
    seg_first = jnp.searchsorted(sorted_e, jnp.arange(E_loc + 1))
    pos_sorted = jnp.arange(T * top_k) - seg_first[sorted_e]
    keep = (pos_sorted < capacity) & (sorted_e < E_loc)
    keep_f = keep.astype(x.dtype)                # multiply, never jnp.where:
    buf_slot = jnp.where(keep, sorted_e * capacity + pos_sorted,
                         E_loc * capacity)       # (a [T*k, D] bool broadcast
    tok_sorted = flat_t[order]                   #  would be saved for the
    gate_sorted = flat_g[order]                  #  backward of select)
    # scatter token rows into the expert buffer [E_loc*capacity + 1, D]
    x_buf = jnp.zeros((E_loc * capacity + 1, D), x.dtype)
    x_buf = x_buf.at[buf_slot].set(x[tok_sorted] * keep_f[:, None])
    xb = x_buf[:-1].reshape(E_loc, capacity, D)
    h = jnp.einsum("ecd,edf->ecf", xb, w_up)
    if w_gate is not None:
        g = jnp.einsum("ecd,edf->ecf", xb, w_gate)
        h = _act(activation, g) * h
    else:
        h = _act(activation, h)
    y = jnp.einsum("ecf,efd->ecd", h, w_down)                 # [E_loc, C, D]
    y_flat = y.reshape(E_loc * capacity, D)
    y_tok = y_flat[jnp.minimum(buf_slot, E_loc * capacity - 1)] * \
        keep_f[:, None]
    out = jnp.zeros((T, D), x.dtype)
    out = out.at[tok_sorted].add(y_tok * gate_sorted[:, None].astype(x.dtype))
    return out


def _moe_local_compute_2d(xg, xg_d, gates, idx, w_up, w_gate, w_down, *,
                          fsdp_ax, n_experts: int, top_k: int,
                          capacity: int, activation: str, e_start: int):
    """2-D-sharded expert compute (decode): weights keep their (E × tensor,
    D/F × fsdp) sharding; the D-contraction partials of the up/gate
    projections are psummed over fsdp *before* the nonlinearity, and the
    down projection contracts this shard's F-slice (partial, psummed by the
    caller).  Collective payloads are expert activations — [E_loc, C, F] —
    not weights.

    xg: [T, D] gathered tokens (for dtype/shape only); xg_d: [T, D_loc]
    this shard's D-slice.  Returns partial output [T, D].
    """
    T = xg.shape[0]
    D = xg.shape[1]
    E_loc = w_up.shape[0]
    flat_e = idx.reshape(-1)
    flat_g = gates.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), top_k)
    local = (flat_e >= e_start) & (flat_e < e_start + E_loc)
    loc_e = jnp.where(local, flat_e - e_start, E_loc)
    order = jnp.argsort(loc_e, stable=True)
    sorted_e = loc_e[order]
    seg_first = jnp.searchsorted(sorted_e, jnp.arange(E_loc + 1))
    pos_sorted = jnp.arange(T * top_k) - seg_first[sorted_e]
    keep = (pos_sorted < capacity) & (sorted_e < E_loc)
    keep_f = keep.astype(xg.dtype)
    buf_slot = jnp.where(keep, sorted_e * capacity + pos_sorted,
                         E_loc * capacity)
    tok_sorted = flat_t[order]
    gate_sorted = flat_g[order]

    xd_buf = jnp.zeros((E_loc * capacity + 1, xg_d.shape[1]), xg.dtype)
    xd_buf = xd_buf.at[buf_slot].set(xg_d[tok_sorted] * keep_f[:, None])
    xb = xd_buf[:-1].reshape(E_loc, capacity, xg_d.shape[1])

    h = jnp.einsum("ecd,edf->ecf", xb, w_up)          # partial over D
    if w_gate is not None:
        g = jnp.einsum("ecd,edf->ecf", xb, w_gate)
        h, g = lax.psum((h, g), fsdp_ax)              # tiny activations
        h = _act(activation, g) * h
    else:
        h = lax.psum(h, fsdp_ax)
        h = _act(activation, h)
    f_loc = w_down.shape[1]
    f0 = lax.axis_index(fsdp_ax) * f_loc
    h_f = lax.dynamic_slice_in_dim(h, f0, f_loc, axis=2)
    y = jnp.einsum("ecf,efd->ecd", h_f, w_down)       # partial over F
    y_flat = y.reshape(E_loc * capacity, D)
    y_tok = y_flat[jnp.minimum(buf_slot, E_loc * capacity - 1)] * \
        keep_f[:, None]
    out = jnp.zeros((T, D), xg.dtype)
    out = out.at[tok_sorted].add(y_tok * gate_sorted[:, None].astype(
        xg.dtype))
    return out


def moe_block(params: dict, x: jax.Array, *, n_experts: int, top_k: int,
              capacity_factor: float, activation: str, glu: bool,
              mesh: Optional[jax.sharding.Mesh],
              rules: ShardingRules = NO_SHARD) -> jax.Array:
    """MoE FFN.  x: [B, S, D] (replicated over tensor axis, sharded over batch).

    Expert parallelism: experts sharded over the tensor axis; each shard
    routes every local token, computes its experts' contributions densely at
    fixed capacity, and psums partial outputs over the tensor axis.  Expert
    weights are additionally FSDP-sharded over the batch/fsdp axis and
    all-gathered on use.
    """
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    gates, idx = moe_router(params["router"], xf, top_k)

    if mesh is None or rules.tensor is None:
        T = B * S
        capacity = max(int(T * top_k * capacity_factor / n_experts), top_k)
        out = _moe_local_compute(
            xf, gates, idx, params["up"],
            params.get("gate") if glu else None, params["down"],
            n_experts=n_experts, top_k=top_k, capacity=capacity,
            activation=activation, e_start=0)
        return out.reshape(B, S, D)

    tensor_ax = rules.tensor
    fsdp_ax = rules.fsdp
    n_shards = mesh.shape[tensor_ax]
    batch_axes = rules.batch if isinstance(rules.batch, tuple) else (rules.batch,)
    batch_axes = tuple(a for a in batch_axes if a is not None)
    batch_size = max(math.prod(mesh.shape[a] for a in batch_axes), 1)
    T_loc = (B * S) // batch_size
    E_loc = n_experts // n_shards
    gather_w = rules.moe_gather_weights or fsdp_ax is None
    capacity = max(int((T_loc if gather_w else T_loc * batch_size)
                       * top_k * capacity_factor / n_experts), top_k)

    wspec = P(tensor_ax, fsdp_ax, None)
    tspec = P(batch_axes if batch_axes else None, None)

    # checkpoint: the dispatch gather/scatter chain would otherwise stack
    # O(T*k*D) broadcast residuals for its backward; recompute it instead
    # (this also re-gathers FSDP weights in the backward — ZeRO-3 semantics).
    @jax.checkpoint
    def local_fn(xf, gates, idx, *weights):
        if glu:
            w_up, w_gate, w_down = weights
        else:
            (w_up, w_down), w_gate = weights, None
        e_start = lax.axis_index(tensor_ax) * E_loc
        if gather_w:
            # training path: gather FSDP-sharded expert weights on use
            if fsdp_ax is not None:
                w_up = lax.all_gather(w_up, fsdp_ax, axis=1, tiled=True)
                w_down = lax.all_gather(w_down, fsdp_ax, axis=1, tiled=True)
                if w_gate is not None:
                    w_gate = lax.all_gather(w_gate, fsdp_ax, axis=1,
                                            tiled=True)
            out = _moe_local_compute(
                xf, gates, idx, w_up, w_gate, w_down,
                n_experts=n_experts, top_k=top_k, capacity=capacity,
                activation=activation, e_start=e_start)
            return lax.psum(out, tensor_ax)

        # decode path: weights stay 2-D sharded (E x tensor, D/F x fsdp);
        # gather the (tiny) token batch over the batch axes, contract
        # against the local D-shard of w_up / F-shard of w_down, and psum
        # the partial expert activations — a few MB of collectives instead
        # of multi-GB weight gathers.
        T_all = xf.shape[0] * batch_size
        xg = lax.all_gather(xf, batch_axes, axis=0, tiled=True)
        gg = lax.all_gather(gates, batch_axes, axis=0, tiled=True)
        ig = lax.all_gather(idx, batch_axes, axis=0, tiled=True)
        d_loc = w_up.shape[1]
        d0 = lax.axis_index(fsdp_ax) * d_loc
        xg_d = lax.dynamic_slice_in_dim(xg, d0, d_loc, axis=1)

        out = _moe_local_compute_2d(
            xg, xg_d, gg, ig, w_up, w_gate, w_down, fsdp_ax=fsdp_ax,
            n_experts=n_experts, top_k=top_k, capacity=capacity,
            activation=activation, e_start=e_start)
        # partial over the expert partition (tensor) and the D/F
        # contraction shards (fsdp); pod replicas computed identical work
        out = lax.psum(out, (tensor_ax, fsdp_ax))
        # slice this shard's tokens back out
        flat = jnp.zeros((), jnp.int32)
        for a in batch_axes:
            flat = flat * mesh.shape[a] + lax.axis_index(a)
        return lax.dynamic_slice_in_dim(out, flat * xf.shape[0],
                                        xf.shape[0], axis=0)

    weights = ((params["up"], params["gate"], params["down"]) if glu
               else (params["up"], params["down"]))
    in_specs = (tspec, tspec, tspec) + (wspec,) * len(weights)
    # Under sequence parallelism the residual stream arrives seq-sharded over
    # the tensor axis; every expert shard needs all of its tokens, so gather
    # tokens over the tensor axis here (the SP all-gather).
    xf = shard(xf, tspec)
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=tspec, check_vma=False)
    out = fn(xf, gates, idx, *weights)
    out = out.reshape(B, S, D)
    return shard(out, rules.residual())


# ---------------------------------------------------------------------------
# Mamba-1 selective SSM
# ---------------------------------------------------------------------------

def _pin(x, spec):
    return shard(x, spec) if spec is not None else x


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def linear_scan(a, b, h0, spec):
    """h[t] = a[t]⊙h[t-1] + b[t] along axis 1, h[-1] = h0.

    Custom VJP: the adjoint of a linear recurrence is the *reversed*
    recurrence g[t] = a[t+1]⊙g[t+1] + ḣ[t], so the backward pass is
    another associative scan with the same explicit sharding pins —
    autodiff through ``lax.associative_scan`` leaves GSPMD free to
    replicate the transposed scan's [B, c, d_inner, N] transients
    (measured: ~400 GB/step of full-d_inner all-gathers on hymba
    train_4k), which this eliminates.  ``spec`` pins every transient.
    """
    return _linear_scan_fwd(a, b, h0, spec)[0]


def _scan_core(a, b, spec):
    def op(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, a2 * b1 + b2

    aa, bb = lax.associative_scan(op, (a, b), axis=1)
    return _pin(aa, spec), _pin(bb, spec)


def _linear_scan_fwd(a, b, h0, spec):
    aa, bb = _scan_core(a, b, spec)
    h = _pin(aa * h0[:, None] + bb, spec)
    return h, (a, h, h0)


def _linear_scan_bwd(spec, res, gh):
    a, h, h0 = res
    gh = _pin(gh, spec)
    ones = jnp.ones_like(a[:, :1])
    a_next = _pin(jnp.concatenate([a[:, 1:], ones], axis=1), spec)
    ar = jnp.flip(a_next, axis=1)
    gr = jnp.flip(gh, axis=1)
    _, gg = _scan_core(ar, gr, spec)
    g = _pin(jnp.flip(gg, axis=1), spec)
    h_prev = _pin(jnp.concatenate([h0[:, None], h[:, :-1]], axis=1), spec)
    da = _pin(g * h_prev, spec)
    db = g
    dh0 = a[:, 0] * g[:, 0]
    return da, db, dh0


linear_scan.defvjp(_linear_scan_fwd, _linear_scan_bwd)

def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv via shifted adds.  x: [B, S, C]; w: [K, C]."""
    K = w.shape[0]
    out = x * w[K - 1]
    for i in range(1, K):
        shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, :-i]
        out = out + shifted * w[K - 1 - i]
    return out + b


def _ssm_params(params: dict, xc: jax.Array, *, d_state: int):
    """Input-dependent Δ, B, C.  xc: [B, S, d_inner]."""
    proj = xc @ params["x_proj"]                 # [B, S, dt_rank + 2N]
    dt_rank = params["dt_proj"].shape[0]
    dt, Bc, Cc = jnp.split(proj, [dt_rank, dt_rank + d_state], axis=-1)
    dt = jax.nn.softplus(dt @ params["dt_proj"] + params["dt_bias"])  # [B,S,di]
    return dt, Bc, Cc


def selective_scan(xc, dt, Bc, Cc, A_log, D_skip, *, chunk: int = 512,
                   rules: ShardingRules = NO_SHARD):
    """Selective state-space scan (Mamba-1), chunked to bound transients.

    xc, dt: [B, S, di]; Bc, Cc: [B, S, N]; A_log: [di, N].
    Sequential scan over chunks, associative scan within a chunk; peak
    transient is [B, chunk, di, N].  Returns (y [B, S, di], h_last [B, di, N]).

    Sharding: d_inner is tensor-parallel, and the [B, c, di, N] transients
    MUST be pinned to that sharding — without the explicit constraints
    GSPMD replicates the associative scan's operands, all-gathering the
    full-d_inner f32 transients every layer (measured: +400 GB/step of
    gathers on hymba train_4k).  y is cast to the residual dtype *inside*
    the chunk body so the stacked scan output is a pure bf16
    dynamic-update-slice (in place), not an f32 buffer converted at the
    root (which XLA cannot update in place).
    """
    B, S, di = xc.shape
    N = Bc.shape[-1]
    A = -jnp.exp(A_log.astype(jnp.float32))                     # [di, N]
    n_chunks = -(-S // chunk)
    pad = n_chunks * chunk - S
    if pad:
        xc_p = jnp.pad(xc, ((0, 0), (0, pad), (0, 0)))
        dt_p = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bc_p = jnp.pad(Bc, ((0, 0), (0, pad), (0, 0)))
        Cc_p = jnp.pad(Cc, ((0, 0), (0, pad), (0, 0)))
    else:
        xc_p, dt_p, Bc_p, Cc_p = xc, dt, Bc, Cc

    chunk_spec = (P(rules.batch, None, rules.tensor, None)
                  if rules.tensor is not None else None)

    # checkpointed: the scan's VJP would otherwise stack every chunk's
    # [B, c, di, N] f32 intermediates (dA, dBx, assoc-scan levels) —
    # measured as the dominant HBM term on hymba/falcon train.  With the
    # checkpoint, backward re-derives them from the (bf16) chunk inputs
    # and the tiny [B, di, N] carry; linear_scan's custom VJP keeps the
    # reverse scan's transients pinned to the same sharding.
    @jax.checkpoint
    def chunk_body(h0, xs):
        xcb, dtb, Bcb, Ccb = xs                                 # [B, chunk, ...]
        dA = jnp.exp(dtb.astype(jnp.float32)[..., None] * A)    # [B,c,di,N]
        dBx = (dtb * xcb).astype(jnp.float32)[..., None] * \
            Bcb.astype(jnp.float32)[..., None, :]               # [B,c,di,N]
        dA = _pin(dA, chunk_spec)
        dBx = _pin(dBx, chunk_spec)
        h = linear_scan(dA, dBx, h0, chunk_spec)                # [B,c,di,N]
        y = jnp.einsum("bcdn,bcn->bcd", h, Ccb.astype(jnp.float32))
        h_last = h[:, -1]
        return h_last, y.astype(xc.dtype)

    h0 = jnp.zeros((B, di, N), jnp.float32)
    xs = tuple(t.reshape(B, n_chunks, chunk, -1).swapaxes(0, 1)
               for t in (xc_p, dt_p, Bc_p, Cc_p))
    h_last, ys = lax.scan(chunk_body, h0, xs)
    y = ys.swapaxes(0, 1).reshape(B, n_chunks * chunk, di)[:, :S]
    return (y + (xc * D_skip).astype(xc.dtype)), h_last


def mamba_mixer(params: dict, x: jax.Array, *, d_state: int,
                rules: ShardingRules = NO_SHARD) -> jax.Array:
    """Full-sequence Mamba-1 mixer.  x: [B, S, D] -> [B, S, D]."""
    xz = x @ params["in_proj"]                                  # [B,S,2*di]
    xz = shard(xz, rules.act(None, rules.tensor))
    xc, z = jnp.split(xz, 2, axis=-1)
    xc = jax.nn.silu(_causal_conv(xc, params["conv_w"], params["conv_b"]))
    dt, Bc, Cc = _ssm_params(params, xc, d_state=d_state)
    y, _ = selective_scan(xc, dt, Bc, Cc, params["A_log"], params["D"],
                          rules=rules)
    y = y * jax.nn.silu(z)
    out = y @ params["out_proj"]
    return shard(out, rules.residual())


def mamba_decode(params: dict, x: jax.Array, conv_state: jax.Array,
                 ssm_state: jax.Array, *, d_state: int,
                 rules: ShardingRules = NO_SHARD):
    """Single-token Mamba step.

    x: [B, 1, D]; conv_state: [B, K-1, di]; ssm_state: [B, di, N] fp32.
    Returns (out [B,1,D], conv_state, ssm_state).
    """
    B = x.shape[0]
    xz = x[:, 0] @ params["in_proj"]                            # [B, 2*di]
    xc, z = jnp.split(xz, 2, axis=-1)
    w = params["conv_w"]                                        # [K, di]
    K = w.shape[0]
    hist = jnp.concatenate([conv_state, xc[:, None]], axis=1)   # [B, K, di]
    conv = jnp.einsum("bkd,kd->bd", hist, w) + params["conv_b"]
    new_conv_state = hist[:, 1:]
    xc = jax.nn.silu(conv)
    proj = xc @ params["x_proj"]
    dt_rank = params["dt_proj"].shape[0]
    dt, Bc, Cc = jnp.split(proj, [dt_rank, dt_rank + d_state], axis=-1)
    dt = jax.nn.softplus(dt @ params["dt_proj"] + params["dt_bias"])
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    dA = jnp.exp(dt.astype(jnp.float32)[..., None] * A)         # [B, di, N]
    dBx = (dt * xc).astype(jnp.float32)[..., None] * \
        Bc.astype(jnp.float32)[:, None, :]
    h = dA * ssm_state + dBx
    y = jnp.einsum("bdn,bn->bd", h, Cc.astype(jnp.float32))
    y = (y + xc.astype(jnp.float32) * params["D"]).astype(x.dtype)
    y = y * jax.nn.silu(z)
    out = (y @ params["out_proj"])[:, None]
    return shard(out, rules.residual()), new_conv_state, h


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embed(table: jax.Array, tokens: jax.Array, *, scale: bool) -> jax.Array:
    x = table[tokens]
    if scale:
        x = x * math.sqrt(table.shape[1])
    return x.astype(table.dtype)


def lm_logits(params: dict, x: jax.Array, *, tied: bool) -> jax.Array:
    w = params["embed"].T if tied else params["lm_head"]
    return (x @ w).astype(jnp.float32)
