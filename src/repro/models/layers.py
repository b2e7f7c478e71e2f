"""Shared neural layers: RMSNorm, RoPE, GQA attention (windowed / cached),
gated MLP, embeddings.  Pure jnp.

All attention here is XLA: `attention(impl="auto")` picks the direct path
below `_FLASH_MIN_Q` query positions and the blocked online-softmax path
above.  The models call no Pallas kernel; `repro.kernels.flash_attention`
is a separate kernel of the same semantics, tested against
`attention(impl="direct")`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MASK_VALUE = -1e30


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """Rotary embedding. x: [B, T, H, hd]; positions: [B, T] or [T]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B, T, half]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def _soft_cap(logits: jax.Array, cap: float) -> jax.Array:
    if cap <= 0:
        return logits
    return cap * jnp.tanh(logits / cap)


_FLASH_MIN_Q = 2048   # direct path below this many query positions


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: jax.Array | int = 0,
    softcap: float = 0.0,
    q_offset: jax.Array | int = 0,
    kv_len: jax.Array | None = None,
    impl: str = "auto",
) -> jax.Array:
    """GQA scaled-dot-product attention.

    q: [B, Tq, H, hd]; k, v: [B, Tk, KV, hd] with H % KV == 0.
    `window` > 0 masks keys further than `window` behind the query (SWA); it
    may be a traced scalar so scanned layers can mix local/global. `q_offset`
    is the absolute position of q[0] (decode). `kv_len` masks the valid
    prefix of the KV buffer (cache decode).

    impl: "auto" uses the online-softmax blocked path for long query
    sequences (O(block) memory — the XLA twin of kernels/flash_attention)
    and the direct path otherwise (decode, short train).
    """
    tq = q.shape[1]
    if impl == "direct" or (impl == "auto" and tq < _FLASH_MIN_Q):
        return _attention_direct(
            q, k, v, causal=causal, window=window, softcap=softcap,
            q_offset=q_offset, kv_len=kv_len,
        )
    return _attention_flash(
        q, k, v, causal=causal, window=window, softcap=softcap,
        q_offset=q_offset, kv_len=kv_len,
    )


def _attention_direct(q, k, v, *, causal, window, softcap, q_offset, kv_len):
    b, tq, h, hd = q.shape
    tk, kv = k.shape[1], k.shape[2]
    groups = h // kv
    qf = q.astype(jnp.float32) / np.sqrt(hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # [B, KV, G, Tq, hd] x [B, S, KV, hd] -> [B, KV, G, Tq, S]
    qf = qf.reshape(b, tq, kv, groups, hd).transpose(0, 2, 3, 1, 4)
    logits = jnp.einsum("bkgqh,bskh->bkgqs", qf, kf)
    logits = _soft_cap(logits, softcap)

    qpos = jnp.arange(tq) + q_offset  # [Tq]
    kpos = jnp.arange(tk)             # [Tk]
    mask = jnp.ones((tq, tk), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    w = jnp.asarray(window)
    mask &= jnp.where(w > 0, kpos[None, :] > qpos[:, None] - w, True)
    if kv_len is not None:
        mask &= kpos[None, :] < jnp.asarray(kv_len).reshape(-1)[0]
    logits = jnp.where(mask[None, None, None], logits, MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bkgqh", probs, vf)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, tq, h, hd)
    return out.astype(q.dtype)


def _attention_flash(
    q, k, v, *, causal, window, softcap, q_offset, kv_len,
    q_block: int = 1024, kv_block: int = 1024,
):
    """Online-softmax blocked attention (memory O(q_block x kv_block)).

    Each query block is `jax.checkpoint`ed so the backward pass recomputes
    the KV scan instead of saving per-step carries — this is what keeps the
    32k prefill cells inside HBM.  Same semantics as `_attention_direct`
    (tested equal); the Pallas kernel in kernels/flash_attention mirrors
    this block structure with VMEM tiling.
    """
    b, tq, h, hd = q.shape
    tk, kv = k.shape[1], k.shape[2]
    g = h // kv
    q_block = min(q_block, tq)
    kv_block = min(kv_block, tk)
    if tq % q_block or tk % kv_block:
        return _attention_direct(
            q, k, v, causal=causal, window=window, softcap=softcap,
            q_offset=q_offset, kv_len=kv_len,
        )
    nq, nk = tq // q_block, tk // kv_block
    # dots stay in the input dtype (bf16 on TPU) with f32 accumulation —
    # halves the blocked buffers and any collectives they ride (G2)
    qf = (q / np.sqrt(hd).astype(q.dtype)).reshape(b, tq, kv, g, hd)
    qf = qf.transpose(0, 2, 3, 1, 4)                     # [B,KV,G,Tq,hd]
    kf = k.transpose(0, 2, 1, 3)                         # [B,KV,S,hd]
    vf = v.transpose(0, 2, 1, 3)
    w = jnp.asarray(window)
    kv_limit = None if kv_len is None else jnp.asarray(kv_len).reshape(-1)[0]

    def q_block_fn(qi):
        q_blk = jax.lax.dynamic_slice_in_dim(qf, qi * q_block, q_block, axis=3)
        qpos = jnp.arange(q_block) + qi * q_block + q_offset

        def kv_step(carry, ki):
            m, l, acc = carry
            k_blk = jax.lax.dynamic_slice_in_dim(kf, ki * kv_block, kv_block, 2)
            v_blk = jax.lax.dynamic_slice_in_dim(vf, ki * kv_block, kv_block, 2)
            logits = jnp.einsum(
                "bkgqh,bksh->bkgqs", q_blk, k_blk,
                preferred_element_type=jnp.float32,
            )
            logits = _soft_cap(logits, softcap)
            kpos = jnp.arange(kv_block) + ki * kv_block
            mask = jnp.ones((q_block, kv_block), bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            mask &= jnp.where(w > 0, kpos[None, :] > qpos[:, None] - w, True)
            if kv_limit is not None:
                mask &= kpos[None, :] < kv_limit
            logits = jnp.where(mask[None, None, None], logits, MASK_VALUE)
            m_new = jnp.maximum(m, logits.max(-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bksh->bkgqh", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kv, g, q_block), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, kv, g, q_block), jnp.float32)
        a0 = jnp.zeros((b, kv, g, q_block, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    q_block_fn = jax.checkpoint(q_block_fn, policy=jax.checkpoint_policies.nothing_saveable)
    out = jax.lax.map(q_block_fn, jnp.arange(nq))        # [nq,B,KV,G,qb,hd]
    out = out.transpose(1, 2, 3, 0, 4, 5).reshape(b, kv, g, tq, hd)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, tq, h, hd)
    return out.astype(q.dtype)


def attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    ctx,
    *,
    causal: bool = True,
    window: jax.Array | int = 0,
    softcap: float = 0.0,
    q_offset: jax.Array | int = 0,
    kv_len: jax.Array | None = None,
) -> jax.Array:
    """Attention as a shard_map island — zero collectives inside the softmax
    loops (hillclimb G3/K4).

    Under plain SPMD the flash scan's carries (f32 accumulators) have no
    dimension divisible by the 16-way 'model' axis when H or KV < 16, so XLA
    all-gathers them EVERY kv step (measured: 7.3 TB/device/step on
    kimi-k2).  Here the parallelism is explicit instead:

    - H % tp == 0: head-split (k/v expanded to H heads, one gather/layer)
    - else:        context-parallel — q sequence-split, k/v replicated,
                   absolute positions offset by the rank's shard start

    Either way each device runs a fully local flash; the only collectives
    are the one-shot in_specs gathers.
    """
    from jax.sharding import PartitionSpec as P

    mesh = ctx.mesh
    tp = ctx.tp_axis
    # 'pod' stays an automatic axis: manual 3-axis islands trip an XLA SPMD
    # partitioner check-failure (hlo_instruction.cc "Invalid binary
    # instruction opcode copy"); partial-manual handles it transparently.
    dp_all = ctx.dp_axes if ctx.dp_axes else ()
    dp_manual = tuple(a for a in dp_all if a != "pod")
    dp = dp_manual if len(dp_manual) > 1 else (dp_manual[0] if dp_manual else None)
    sizes = dict(mesh.shape)
    tps = sizes.get(tp, 1)
    dps = 1
    for a in dp_manual:
        dps *= sizes[a]
    b, t, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    head_split = h % tps == 0 and h >= tps
    seq_split = (not head_split) and t % tps == 0 and (t // tps) >= 256
    h_local = h // tps if head_split else h
    # head-split GQA needs each rank's q heads to map to a contiguous kv
    # subset; holds when h_local divides or is divided by the group size
    if head_split and not (h_local % g == 0 or g % h_local == 0):
        head_split = False
        seq_split = t % tps == 0 and (t // tps) >= 256
    if mesh is None or tps == 1 or b % dps or not (head_split or seq_split):
        return attention(
            q, k, v, causal=causal, window=window, softcap=softcap,
            q_offset=q_offset, kv_len=kv_len,
        )

    qspec = P(dp, None, tp, None) if head_split else P(dp, tp, None, None)
    kvspec = P(dp, None, None, None)   # k/v replicated over 'model' (small)
    t_local = t // tps

    def island(q_l, k_l, v_l):
        off = q_offset
        if seq_split:
            off = off + jax.lax.axis_index(tp) * t_local
        if head_split:
            # select this rank's kv heads (no expansion: dk/dv stay [.,.,KV,.])
            r = jax.lax.axis_index(tp)
            idx = (r * h_local + jnp.arange(h_local)) // g
            k_l = jnp.take(k_l, idx, axis=2)
            v_l = jnp.take(v_l, idx, axis=2)
        return attention(
            q_l, k_l, v_l, causal=causal, window=window, softcap=softcap,
            q_offset=off, kv_len=kv_len,
        )

    manual = set((dp if isinstance(dp, tuple) else (dp,) if dp else ())) | {tp}
    return jax.shard_map(
        island, mesh=mesh, in_specs=(qspec, kvspec, kvspec), out_specs=qspec,
        axis_names=frozenset(manual), check_vma=False,
    )(q, k, v)


def gated_mlp(x: jax.Array, wi: jax.Array, wo: jax.Array, act: str = "silu") -> jax.Array:
    """wi: [d, 2*ff] (gate||up fused); wo: [ff, d]."""
    ff = wo.shape[0]
    gu = x @ wi
    gate, up = gu[..., :ff], gu[..., ff:]
    a = jax.nn.silu(gate) if act == "silu" else jax.nn.gelu(gate)
    return (a * up) @ wo


def embed(tokens: jax.Array, table: jax.Array, scale: bool = False) -> jax.Array:
    x = jnp.take(table, tokens, axis=0)
    if scale:
        x = x * np.sqrt(table.shape[-1])
    return x


def init_linear(key, shape, scale=None) -> jax.Array:
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    s = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * s).astype(jnp.float32)


def cross_entropy(
    logits: jax.Array, labels: jax.Array, mask: jax.Array | None = None, z_coef: float = 1e-4
) -> jax.Array:
    """Token-mean CE + z-loss; logits [.., V] f32-upcast internally."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    zloss = z_coef * jnp.square(lse)
    per_tok = nll + zloss
    if mask is not None:
        per_tok = per_tok * mask
        denom = jnp.maximum(mask.sum(), 1)
    else:
        denom = np.prod(labels.shape)
    return per_tok.sum() / denom
