"""Model building blocks: norms, RoPE, attention, MLP, MoE, Mamba2 SSD.

Pure functions over explicit parameter dicts (no framework dependency).
Initializers return real arrays for small configs; the dry-run never
calls them (``jax.eval_shape`` turns them into ShapeDtypeStructs).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.config import ModelConfig
from repro.models.sharding import ShardingPlan


def perf_opts_enabled() -> bool:
    """SPerf beyond-paper optimizations (EXPERIMENTS.md): flash chunk
    4096 + bf16 PV product, decode layer-loop unroll. Gated so the
    baseline columns of the roofline table stay reproducible."""
    import os
    return os.environ.get("REPRO_PERF_OPTS", "1") == "1"

# ---------------------------------------------------------------------------
# norms / rope
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: [..., S, H, head_dim], positions: [..., S]."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freq  # [..., S, half]
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def softcap(x: jax.Array, cap: float | None) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    """Projections stored FLAT ([d, h*hd]) so the TP-sharded dim is the
    product h*hd, which is 16-divisible for every assigned arch even when
    the head count (56, 40, 6, 2...) is not. Head structure is recovered
    by reshape under an (uneven-tolerant) internal sharding constraint.
    """
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    p = {
        "wq": (jax.random.normal(k1, (d, hq * hd), dtype) * s).astype(dtype),
        "wk": (jax.random.normal(k2, (d, hkv * hd), dtype) * s).astype(dtype),
        "wv": (jax.random.normal(k3, (d, hkv * hd), dtype) * s).astype(dtype),
        "wo": (jax.random.normal(k4, (hq * hd, d), dtype)
               * (1.0 / math.sqrt(hq * hd))).astype(dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), dtype)
        p["bk"] = jnp.zeros((hkv * hd,), dtype)
        p["bv"] = jnp.zeros((hkv * hd,), dtype)
    return p


def _proj_heads(x, w, b, n_heads: int, hd: int):
    b_, s_, _ = x.shape
    y = jnp.einsum("bsd,de->bse", x, w)
    if b is not None:
        y = y + b
    return y.reshape(b_, s_, n_heads, hd)


def _qkv(p: dict, x: jax.Array, cfg: ModelConfig, positions: jax.Array,
         plan: ShardingPlan):
    hd = cfg.head_dim
    q = _proj_heads(x, p["wq"], p.get("bq"), cfg.n_heads, hd)
    k = _proj_heads(x, p["wk"], p.get("bk"), cfg.n_kv_heads, hd)
    v = _proj_heads(x, p["wv"], p.get("bv"), cfg.n_kv_heads, hd)
    q = plan.constrain(q, plan.act_heads())
    if not plan.activation_tp and plan.shard_seq:
        # Ulysses-style: Q stays seq-sharded; K/V replicate over seq so
        # local Q shards attend to the full context without per-chunk
        # resharding inside the flash scan.
        k = plan.constrain(k, plan.kv_full())
        v = plan.constrain(v, plan.kv_full())
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def flash_attention(q, k, v, *, causal: bool, window: int | None,
                    logit_cap: float | None, q_offset, kv_len=None,
                    chunk: int | None = None):
    if chunk is None:
        chunk = 4096 if perf_opts_enabled() else 1024
    """Chunked (flash-style) GQA attention, O(S * chunk) memory.

    q: [B, Sq, Hq, hd]; k, v: [B, Skv, Hkv, hd]. q_offset: scalar position
    of q[0] within the kv sequence (for decode/prefill continuation).
    kv_len: optional scalar — valid kv prefix length (decode cache).
    """
    b, sq, hq, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qr = q.reshape(b, sq, hkv, g, hd)
    scale = 1.0 / math.sqrt(hd)
    nchunks = -(-skv // chunk)
    pad = nchunks * chunk - skv
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = kp.reshape(b, nchunks, chunk, hkv, hd).transpose(1, 0, 2, 3, 4)
    vc = vp.reshape(b, nchunks, chunk, hkv, hd).transpose(1, 0, 2, 3, 4)
    qpos = q_offset + jnp.arange(sq)

    def body(carry, inp):
        m, l, acc, cidx = carry
        kci, vci = inp
        kvpos = cidx * chunk + jnp.arange(chunk)
        logits = jnp.einsum("bskgd,bckd->bskgc", qr, kci) * scale
        logits = softcap(logits, logit_cap)
        # padded keys (skv -> nchunks*chunk) must NEVER enter the
        # softmax — caught by the fused-kernel oracle sweep
        mask = (kvpos[None, :] < skv)
        if causal:
            mask = mask & (kvpos[None, :] <= qpos[:, None])
        if window is not None:
            mask = mask & (qpos[:, None] - kvpos[None, :] < window)
        if kv_len is not None:
            mask = mask & (kvpos[None, :] < kv_len)
        logits = jnp.where(mask[None, :, None, None, :], logits, -1e30)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        probs = jnp.exp(logits - m_new[..., None])
        l_new = l * alpha + probs.sum(axis=-1)
        if perf_opts_enabled():
            # probs in bf16 for the PV product: halves the dominant HBM
            # traffic; accumulator stays f32 (SPerf iteration 2)
            pv = jnp.einsum("bskgc,bckd->bskgd",
                            probs.astype(jnp.bfloat16),
                            vci.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
        else:
            pv = jnp.einsum("bskgc,bckd->bskgd", probs, vci)
        acc_new = acc * alpha[..., None] + pv
        return (m_new, l_new, acc_new, cidx + 1), None

    m0 = jnp.full((b, sq, hkv, g), -1e30, jnp.float32)
    l0 = jnp.zeros((b, sq, hkv, g), jnp.float32)
    acc0 = jnp.zeros((b, sq, hkv, g, hd), jnp.float32)
    (m, l, acc, _), _ = lax.scan(
        body, (m0, l0, acc0, jnp.int32(0)),
        (kc.astype(jnp.float32), vc.astype(jnp.float32)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, sq, hq, hd).astype(q.dtype)


def decode_attention_sharded(q, k_cache, v_cache, *, cache_pos,
                             window: int | None, logit_cap: float | None,
                             plan: ShardingPlan):
    """Decode attention with the KV cache sequence-sharded over the
    model axis — flash-decoding style: each shard computes a partial
    softmax over its local KV slab; partials merge with a log-sum-exp
    psum. Avoids GSPMD's replication fallback when scanning a sharded
    chunk axis (involuntary full remat of the fp32 cache copy).

    q: [B, 1, Hq, hd]; caches: [B, S, Hkv, hd] with S over ``model``.
    Returns [B, 1, Hq*hd].
    """
    from jax.sharding import PartitionSpec as P

    mesh, tp = plan.mesh, plan.tp
    dp = plan.dp
    scale = 1.0 / math.sqrt(q.shape[-1])

    def fn(qb, kc, vc, pos):
        b, _, hq, hd = qb.shape
        s_loc, hkv = kc.shape[1], kc.shape[2]
        g = hq // hkv
        tpi = lax.axis_index(tp)
        kvpos = tpi * s_loc + jnp.arange(s_loc)
        # bf16 operands + f32 accumulation (MXU-style): avoids
        # materializing an f32 copy of the whole cache slab
        qr = qb.reshape(b, hkv, g, hd)
        logits = jnp.einsum("bkgd,bskd->bkgs", qr, kc,
                            preferred_element_type=jnp.float32) * scale
        logits = softcap(logits, logit_cap)
        mask = kvpos <= pos
        if window is not None:
            mask = mask & (pos - kvpos < window)
        logits = jnp.where(mask[None, None, None, :], logits, -1e30)
        m = logits.max(axis=-1)
        mg = lax.pmax(m, tp)
        probs = jnp.exp(logits - mg[..., None])
        l = lax.psum(probs.sum(axis=-1), tp)
        acc = lax.psum(jnp.einsum("bkgs,bskd->bkgd",
                                  probs.astype(jnp.bfloat16), vc,
                                  preferred_element_type=jnp.float32), tp)
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.reshape(b, 1, hq * hd).astype(qb.dtype)

    return jax.shard_map(
        fn, mesh=mesh, check_vma=False,
        in_specs=(P(dp, None, None, None), P(dp, tp, None, None),
                  P(dp, tp, None, None), P()),
        out_specs=P(dp, None, None),
    )(q, k_cache, v_cache, cache_pos)


def attention(p: dict, x: jax.Array, cfg: ModelConfig, positions, plan,
              *, local: bool, cache: tuple | None = None,
              cache_pos=None, xattn_kv: jax.Array | None = None,
              causal: bool = True):
    """Full attention sub-layer.

    Modes:
      train/prefill: cache None -> causal flash attention over x itself.
        Returns (out, (k, v)) so prefill can build the cache.
      decode: cache=(k_cache, v_cache) [B, S_max, Hkv, hd], cache_pos =
        scalar write position. x is [B, 1, d].
      cross-attention (enc-dec): xattn_kv = encoder activations; no
        causal mask, no cache.
    """
    window = cfg.window if local else None
    hd = cfg.head_dim
    if xattn_kv is not None:
        q = _proj_heads(x, p["wq"], p.get("bq"), cfg.n_heads, hd)
        k = _proj_heads(xattn_kv, p["wk"], p.get("bk"), cfg.n_kv_heads, hd)
        v = _proj_heads(xattn_kv, p["wv"], p.get("bv"), cfg.n_kv_heads, hd)
        out = flash_attention(q, k, v, causal=False, window=None,
                              logit_cap=cfg.attn_logit_softcap, q_offset=0)
        out = out.reshape(*out.shape[:2], -1)
        return jnp.einsum("bse,ed->bsd", out, p["wo"]), None

    q, k, v = _qkv(p, x, cfg, positions, plan)
    if cache is None:
        out = flash_attention(q, k, v, causal=causal, window=window,
                              logit_cap=cfg.attn_logit_softcap, q_offset=0)
        # constrain so prefill's stacked cache ys accumulate SHARDED
        # (unconstrained ys replicate: 61 layers x 32k seq = fleet-OOM)
        new_cache = (plan.constrain(k, plan.kv_cache()),
                     plan.constrain(v, plan.kv_cache()))
    else:
        k_cache, v_cache = cache
        k_cache = lax.dynamic_update_slice_in_dim(k_cache, k, cache_pos, 1)
        v_cache = lax.dynamic_update_slice_in_dim(v_cache, v, cache_pos, 1)
        k_cache = plan.constrain(k_cache, plan.kv_cache())
        v_cache = plan.constrain(v_cache, plan.kv_cache())
        if plan.mesh is not None and q.shape[1] == 1:
            out = decode_attention_sharded(
                q, k_cache, v_cache, cache_pos=cache_pos, window=window,
                logit_cap=cfg.attn_logit_softcap, plan=plan)
            new_cache = (k_cache, v_cache)
            out = jnp.einsum("bse,ed->bsd", out, p["wo"])
            return plan.constrain(out, plan.act()), new_cache
        out = flash_attention(q, k_cache, v_cache, causal=False,
                              window=window,
                              logit_cap=cfg.attn_logit_softcap,
                              q_offset=cache_pos, kv_len=cache_pos + 1)
        new_cache = (k_cache, v_cache)
    out = out.reshape(*out.shape[:2], -1)
    out = jnp.einsum("bse,ed->bsd", out, p["wo"])
    return plan.constrain(out, plan.act()), new_cache


# ---------------------------------------------------------------------------
# MLP (SwiGLU) and MoE
# ---------------------------------------------------------------------------


def init_mlp(key, d: int, f: int, dtype=jnp.bfloat16) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi": (jax.random.normal(k1, (d, f), dtype) / math.sqrt(d)).astype(dtype),
        "wg": (jax.random.normal(k2, (d, f), dtype) / math.sqrt(d)).astype(dtype),
        "wo": (jax.random.normal(k3, (f, d), dtype) / math.sqrt(f)).astype(dtype),
    }


def mlp(p: dict, x: jax.Array, plan: ShardingPlan) -> jax.Array:
    h = jnp.einsum("bsd,df->bsf", x, p["wi"])
    g = jnp.einsum("bsd,df->bsf", x, p["wg"])
    h = plan.constrain(jax.nn.silu(g) * h, plan.act_ff())
    out = jnp.einsum("bsf,fd->bsd", h, p["wo"])
    return plan.constrain(out, plan.act())


def init_moe(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.num_experts
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "router": (jax.random.normal(k1, (d, e), jnp.float32)
                   / math.sqrt(d)).astype(jnp.float32),
        "wi": (jax.random.normal(k2, (e, d, f), dtype) / math.sqrt(d)).astype(dtype),
        "wg": (jax.random.normal(k3, (e, d, f), dtype) / math.sqrt(d)).astype(dtype),
        "wo": (jax.random.normal(k4, (e, f, d), dtype) / math.sqrt(f)).astype(dtype),
    }


def moe(p: dict, x: jax.Array, cfg: ModelConfig, plan: ShardingPlan):
    """Top-k MoE. With a mesh: explicitly-partitioned GShard dispatch
    (see moe_sharded.py — GSPMD auto-partitioning of the dispatch scatter
    replicates [N*k, d]); without a mesh: dense sort-based dispatch.
    """
    if plan.mesh is not None:
        from repro.models.moe_sharded import moe_sharded
        return moe_sharded(p, x, cfg, plan)
    return _moe_dense(p, x, cfg, plan)


def _moe_dense(p: dict, x: jax.Array, cfg: ModelConfig, plan: ShardingPlan):
    """Sort-based top-k MoE with capacity dropping (single-device path).

    The dispatch is the same group-by-destination primitive as TAM's
    request bucketing: tokens sorted by expert id, positions within each
    expert computed from prefix sums, overflow dropped. Returns
    (out, aux_loss).
    """
    m = cfg.moe
    b, s, d = x.shape
    e, k = m.num_experts, m.top_k
    n = b * s
    xt = plan.constrain(x.reshape(n, d), plan.flat_tokens())
    logits = plan.constrain(xt.astype(jnp.float32) @ p["router"],
                            plan.flat_tokens())            # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, eids = lax.top_k(probs, k)                  # [N, k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    # load-balancing aux loss (Switch-style)
    me = probs.mean(axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[eids.reshape(-1)].add(
        1.0 / (n * k))
    aux = e * jnp.sum(me * ce)

    cap = int(math.ceil(n * k / e * m.capacity_factor))
    cap = max(8, -(-cap // 8) * 8)
    flat_e = eids.reshape(-1)                              # [N*k]
    order = jnp.argsort(flat_e, stable=True)
    ranked = flat_e[order]
    # position within expert group (prefix over sorted layout)
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    pos = jnp.arange(n * k, dtype=jnp.int32) - starts[ranked]
    ok = pos < cap
    slot = jnp.where(ok, ranked * cap + pos, e * cap)      # OOB => dropped
    token_of = order // k
    rows = plan.constrain(xt[token_of], plan.flat_tokens())  # [N*k, d]
    disp = jnp.zeros((e * cap, d), x.dtype).at[slot].set(rows, mode="drop")
    disp = plan.constrain(disp.reshape(e, cap, d), plan.moe_dispatch())
    h = jnp.einsum("ecd,edf->ecf", disp, p["wi"])
    g = jnp.einsum("ecd,edf->ecf", disp, p["wg"])
    h = plan.constrain(jax.nn.silu(g) * h,
                       plan.moe_dispatch())  # [E, cap, f]
    eo = jnp.einsum("ecf,efd->ecd", h, p["wo"])
    eo = plan.constrain(eo, plan.moe_dispatch()).reshape(e * cap, d)
    # combine: gather each token's k expert outputs, weight by gates
    inv_slot = jnp.full((n * k,), e * cap, jnp.int32).at[order].set(
        jnp.where(ok, slot, e * cap), mode="drop")
    eo_pad = jnp.concatenate([eo, jnp.zeros((1, d), eo.dtype)], axis=0)
    per_tok = plan.constrain(
        eo_pad[jnp.minimum(inv_slot, e * cap)],
        plan.flat_tokens()).reshape(n, k, d)
    out = (per_tok * gate_vals[..., None].astype(per_tok.dtype)).sum(axis=1)
    out = plan.constrain(out.reshape(b, s, d), plan.act())
    return out, aux


# ---------------------------------------------------------------------------
# Mamba2 (SSD)
# ---------------------------------------------------------------------------


def init_mamba(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    """Split projections (wx/wz TP-sharded on d_inner; B/C/dt tiny and
    replicated) so TP shard boundaries align with the semantic segments —
    a fused in_proj would smear z/x/B/C/dt across shards and force
    reshards after every split.
    """
    mc = cfg.mamba
    d = cfg.d_model
    di, ds, nh = mc.d_inner(d), mc.d_state, mc.n_heads(d)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    sc = 1.0 / math.sqrt(d)
    return {
        "wx": (jax.random.normal(k1, (d, di), dtype) * sc).astype(dtype),
        "wz": (jax.random.normal(k4, (d, di), dtype) * sc).astype(dtype),
        "wbcdt": (jax.random.normal(k5, (d, 2 * ds + nh), dtype)
                  * sc).astype(dtype),
        "conv": (jax.random.normal(k2, (mc.d_conv, di + 2 * ds), dtype)
                 * 0.1).astype(dtype),
        "A_log": jnp.zeros((nh,), jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "norm": jnp.zeros((di,), jnp.float32),
        "out_proj": (jax.random.normal(k3, (di, d), dtype)
                     / math.sqrt(di)).astype(dtype),
    }


def _ssd_chunked(xh, dt, A, B_, C_, chunk: int):
    """SSD (state-space duality) forward, chunked.

    xh: [B, S, nh, hd]; dt: [B, S, nh]; A: [nh] (negative);
    B_, C_: [B, S, ds]. Returns y [B, S, nh, hd].
    """
    b, s, nh, hd = xh.shape
    ds = B_.shape[-1]
    nc = s // chunk
    xc = xh.reshape(b, nc, chunk, nh, hd)
    dtc = dt.reshape(b, nc, chunk, nh)
    Bc = B_.reshape(b, nc, chunk, ds)
    Cc = C_.reshape(b, nc, chunk, ds)
    a = dtc * A[None, None, None, :]                     # [b,nc,L,nh] (<=0)
    cum = jnp.cumsum(a, axis=2)                          # within-chunk

    # intra-chunk (masked "attention" in log space)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # [b,nc,Li,Lj,nh]
    il = jnp.arange(chunk)
    causal = (il[:, None] >= il[None, :])[None, None, :, :, None]
    decay = jnp.where(causal, jnp.exp(seg), 0.0)
    cb = jnp.einsum("bnis,bnjs->bnij", Cc, Bc)           # [b,nc,Li,Lj]
    m = decay * cb[..., None] * dtc[:, :, None, :, :]    # [b,nc,Li,Lj,nh]
    y_intra = jnp.einsum("bnijh,bnjhd->bnihd", m, xc.astype(jnp.float32))

    # chunk states: S_n = sum_j exp(cum_last - cum_j) dt_j B_j x_j
    last = cum[:, :, -1:, :]                             # [b,nc,1,nh]
    w = jnp.exp(last - cum) * dtc                        # [b,nc,L,nh]
    states = jnp.einsum("bnlh,bnls,bnlhd->bnhsd", w, Bc,
                        xc.astype(jnp.float32))          # [b,nc,nh,ds,hd]
    chunk_decay = jnp.exp(last[:, :, 0, :])              # [b,nc,nh]

    def scan_body(st, inp):
        s_n, dec = inp                                   # [b,nh,ds,hd],[b,nh]
        new = st * dec[..., None, None] + s_n
        return new, st                                   # emit PREVIOUS state

    init = jnp.zeros((b, nh, ds, hd), jnp.float32)
    final_state, prev_states = lax.scan(
        scan_body, init,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)   # [b,nc,nh,ds,hd]

    # inter-chunk: y_i += C_i . (exp(cum_i) * prev_state)
    y_inter = jnp.einsum("bnls,bnlh,bnhsd->bnlhd", Cc, jnp.exp(cum),
                         prev_states)
    y = (y_intra + y_inter).reshape(b, s, nh, hd)
    return y, final_state


def mamba_block(p: dict, x: jax.Array, cfg: ModelConfig, plan: ShardingPlan,
                state: tuple | None = None):
    """Mamba2 SSD block. state=(ssm_state [B,nh,ds,hd], conv_state
    [B, d_conv-1, di+2ds]) enables single-token decode; None = full seq.
    Returns (out, new_state) — new_state is None in full-seq mode.
    """
    mc = cfg.mamba
    b, s, d = x.shape
    di, ds, nh = mc.d_inner(d), mc.d_state, mc.n_heads(d)
    hd = mc.head_dim
    xin = jnp.einsum("bsd,de->bse", x, p["wx"])
    z = jnp.einsum("bsd,de->bse", x, p["wz"])
    bcdt = jnp.einsum("bsd,de->bse", x, p["wbcdt"])
    B_, C_, dt = jnp.split(bcdt, [ds, 2 * ds], axis=-1)
    conv_in = jnp.concatenate([xin, B_, C_], axis=-1)    # [b,s,di+2ds]

    if state is None:
        # causal depthwise conv over seq
        pad = jnp.pad(conv_in, ((0, 0), (mc.d_conv - 1, 0), (0, 0)))
        conv = sum(pad[:, i:i + s] * p["conv"][i][None, None, :]
                   for i in range(mc.d_conv))
        conv = jax.nn.silu(conv)
        xin, B_, C_ = jnp.split(conv, [di, di + ds], axis=-1)
        dt_s = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
        A = -jnp.exp(p["A_log"])
        xh = xin.reshape(b, s, nh, hd)
        xh = plan.constrain(xh, plan.act_heads())
        assert s % min(mc.chunk, s) == 0, "seq must divide into SSD chunks"
        y, final_ssm = _ssd_chunked(xh, dt_s, A, B_.astype(jnp.float32),
                                    C_.astype(jnp.float32), min(mc.chunk, s))
        y = y + p["D"][None, None, :, None] * xh.astype(jnp.float32)
        # state handoff for prefill -> decode continuation
        tail = conv_in[:, s - (mc.d_conv - 1):, :] if s >= mc.d_conv - 1 \
            else jnp.pad(conv_in, ((0, 0), (mc.d_conv - 1 - s, 0), (0, 0)))
        new_state = (final_ssm, tail)
    else:
        ssm_state, conv_state = state                    # decode: s == 1
        window = jnp.concatenate([conv_state, conv_in], axis=1)
        conv = sum(window[:, i:i + 1] * p["conv"][i][None, None, :]
                   for i in range(mc.d_conv))
        conv = jax.nn.silu(conv)
        xin, B_, C_ = jnp.split(conv, [di, di + ds], axis=-1)
        dt_s = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
        A = -jnp.exp(p["A_log"])
        xh = xin.reshape(b, 1, nh, hd).astype(jnp.float32)
        dec = jnp.exp(dt_s[:, 0, :] * A[None, :])        # [b,nh]
        upd = jnp.einsum("bh,bs,bhd->bhsd", dt_s[:, 0, :],
                         B_[:, 0].astype(jnp.float32), xh[:, 0])
        ssm_state = ssm_state * dec[..., None, None] + upd
        y = jnp.einsum("bs,bhsd->bhd", C_[:, 0].astype(jnp.float32),
                       ssm_state)[:, None]
        y = y + p["D"][None, None, :, None] * xh
        new_state = (ssm_state, window[:, 1:])
    y = y.reshape(b, s, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    return plan.constrain(out, plan.act()), new_state
