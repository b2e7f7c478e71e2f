"""Public flash-attention op: kernel on TPU, oracle elsewhere.

Accepts model-layout tensors ([B, T, H, hd] / [B, S, KV, hd]) and folds the
GQA grouping into the kernel's head-major layout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import kernel, ref


def flash_attention(
    q: jax.Array,          # [B, Tq, H, hd]
    k: jax.Array,          # [B, Tk, KV, hd]
    v: jax.Array,
    *,
    kv_len: jax.Array | int | None = None,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_block: int = 512,
    kv_block: int = 512,
    force_kernel: bool = False,
) -> jax.Array:
    b, tq, h, hd = q.shape
    _, tk, kvh, _ = k.shape
    g = h // kvh
    kvl = jnp.asarray(tk if kv_len is None else kv_len, jnp.int32)

    qh = q.transpose(0, 2, 1, 3).reshape(b * h, tq, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(b * kvh, tk, hd)
    vh = v.transpose(0, 2, 1, 3).reshape(b * kvh, tk, hd)

    on_tpu = jax.default_backend() == "tpu"
    if force_kernel or on_tpu:
        out = kernel.flash_attention(
            qh, kh, vh, kvl,
            groups=g, causal=causal, window=window, softcap=softcap,
            q_block=q_block, kv_block=kv_block, interpret=not on_tpu,
        )
    else:
        out = ref.attention_ref(
            qh, kh, vh, kvl,
            groups=g, causal=causal, window=window, softcap=softcap,
        )
    return out.reshape(b, h, tq, hd).transpose(0, 2, 1, 3)
