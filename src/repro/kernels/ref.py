"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

These are the ground truth for the interpret-mode kernel tests and the
small-shape CPU fallbacks.  Naive O(S^2) attention / O(S) sequential SSM —
clarity over efficiency.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    return jnp.tanh(x / cap) * cap if cap > 0 else x


def matmul_ref(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """FC-layer oracle: x [N, C] @ w [C, K] -> [N, K] (f32 accumulation)."""
    return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST).astype(x.dtype)


def conv2d_ref(x: jnp.ndarray, w: jnp.ndarray, stride: int = 1) -> jnp.ndarray:
    """Conv-layer oracle: x [N, C, XI, YI], w [K, C, R, S] -> [N, K, XO, YO]
    with VALID padding (the solver's layer specs bake the halo into the
    input extent, so no implicit padding exists)."""
    return jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32),
        window_strides=(stride, stride), padding="VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=jax.lax.Precision.HIGHEST).astype(x.dtype)


def pool2d_ref(x: jnp.ndarray, r: int, s: int, stride: int = 2) -> jnp.ndarray:
    """Max-pool oracle: x [N, C, XI, YI] -> [N, C, XO, YO], VALID padding
    (pool layer specs bake the window extent into the input, like conv)."""
    return jax.lax.reduce_window(
        x.astype(jnp.float32), -jnp.inf, jax.lax.max,
        window_dimensions=(1, 1, r, s),
        window_strides=(1, 1, stride, stride),
        padding="VALID").astype(x.dtype)


def eltwise_ref(*xs: jnp.ndarray) -> jnp.ndarray:
    """N-ary element-wise sum oracle (residual adds, gate merges; channel
    concatenation is a sum of channel-embedded operands, see
    ``lower.netexec``).  All operands must share one shape."""
    out = xs[0].astype(jnp.float32)
    for x in xs[1:]:
        out = out + x.astype(jnp.float32)
    return out.astype(xs[0].dtype)


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  causal: bool = True, window: int = 0,
                  logit_softcap: float = 0.0,
                  scale: float | None = None) -> jnp.ndarray:
    """Naive attention oracle.

    q: [B, H, Sq, D]; k, v: [B, KV, Sk, D] with H a multiple of KV (GQA).
    window > 0: local (sliding-window) attention of that width.
    """
    B, H, Sq, D = q.shape
    KV = k.shape[1]
    qpk = H // KV
    k = jnp.repeat(k, qpk, axis=1)
    v = jnp.repeat(v, qpk, axis=1)
    scale = scale if scale is not None else D ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = softcap(logits, logit_softcap)
    Sk = k.shape[2]
    qpos = jnp.arange(Sq)[:, None] + (Sk - Sq)     # right-aligned (decode)
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)


def rope_ref(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary position embedding of x [..., S, D] at positions 0..S-1:
    pairs (i, i + D/2) rotate by pos * theta ** (-2i / D).  Angles and
    their cos/sin are taken in float64 on the host, then rounded to
    float32."""
    S, D = x.shape[-2], x.shape[-1]
    inv = theta ** (-np.arange(0, D, 2, dtype=np.float64) / D)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)
    sin = jnp.asarray(np.sin(ang), jnp.float32)
    x = x.astype(jnp.float32)
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def rmsnorm_ref(x: jnp.ndarray, g: jnp.ndarray, eps: float) -> jnp.ndarray:
    """RMSNorm oracle over the last axis: x / sqrt(mean(x^2) + eps) * g."""
    x = x.astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * g.astype(jnp.float32)


def glu_ref(x: jnp.ndarray) -> jnp.ndarray:
    """SwiGLU product oracle: x [N, 2F] holds gate then up;
    silu(gate) * up -> [N, F]."""
    gate, up = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return gate * jax.nn.sigmoid(gate) * up


def looplm_ref(x: jnp.ndarray, weights: Dict[str, jnp.ndarray], *,
               batch: int, seq: int, heads: int, kv_heads: int,
               head_dim: int, layers: int, steps: int, eps: float,
               rope_theta: float) -> Dict[str, jnp.ndarray]:
    """Model-level oracle of a looped transformer's prefill (Ouro's
    LoopLM): ``steps`` passes of one stack of ``layers`` pre- and
    post-normed (sandwich) blocks with the same weights, each pass ended
    by the final RMSNorm, then the head on each sequence's last position.

    x: the embedded tokens [batch * seq, hidden]; ``weights`` holds the
    first pass's ``s0.*.W`` arrays only (fc [C, K], norm gains [C]) and
    ``head.W``.  Returns every layer's output under the layer names of
    ``workloads.nets.looplm``."""
    out: Dict[str, jnp.ndarray] = {"embed": x}
    H, KV, D = heads, kv_heads, head_dim

    def heads_of(a, n):                      # [B*S, n*D] -> [B, n, S, D]
        return a.reshape(batch, seq, n, D).transpose(0, 2, 1, 3)

    h = x
    for t in range(steps):
        for i in range(layers):
            p, w = f"s{t}.l{i}.", f"s0.l{i}."
            res = h
            a = out[p + "in_norm"] = rmsnorm_ref(h, weights[w + "in_norm.W"],
                                                 eps)
            qkv = out[p + "qkv"] = matmul_ref(a, weights[w + "qkv.W"])
            q = rope_ref(heads_of(qkv[:, : H * D], H), rope_theta)
            k = rope_ref(heads_of(qkv[:, H * D:(H + KV) * D], KV),
                         rope_theta)
            v = heads_of(qkv[:, (H + KV) * D:], KV)
            att = attention_ref(q, k, v, causal=True)
            att = out[p + "attn"] = att.transpose(0, 2, 1, 3).reshape(
                batch * seq, H * D)
            o = out[p + "o"] = matmul_ref(att, weights[w + "o.W"])
            o = out[p + "attn_post_norm"] = rmsnorm_ref(
                o, weights[w + "attn_post_norm.W"], eps)
            h = out[p + "add1"] = res + o
            res = h
            f = out[p + "ffn_norm"] = rmsnorm_ref(
                h, weights[w + "ffn_norm.W"], eps)
            gu = out[p + "gate_up"] = matmul_ref(f, weights[w + "gate_up.W"])
            g = out[p + "glu"] = glu_ref(gu)
            dn = out[p + "down"] = matmul_ref(g, weights[w + "down.W"])
            dn = out[p + "ffn_post_norm"] = rmsnorm_ref(
                dn, weights[w + "ffn_post_norm.W"], eps)
            h = out[p + "add2"] = res + dn
        h = out[f"s{t}.norm"] = rmsnorm_ref(h, weights["s0.norm.W"], eps)
    last = h.reshape(batch, seq, -1)[:, -1]
    out["head"] = matmul_ref(last, weights["head.W"])
    return out


def ssd_ref(x: jnp.ndarray, dt: jnp.ndarray, a_log: jnp.ndarray,
            b: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """Sequential state-space-duality (Mamba2) oracle.

    x:  [B, S, H, P]   per-head inputs
    dt: [B, S, H]      softplus'd step sizes (positive)
    a_log: [H]         per-head decay (A = -exp(a_log) < 0)
    b, c: [B, S, N]    shared-across-heads (G=1) input/output projections
    returns y: [B, S, H, P]
    """
    Bsz, S, H, P = x.shape
    N = b.shape[-1]
    a = -jnp.exp(a_log.astype(jnp.float32))                 # [H]
    dt = dt.astype(jnp.float32)
    decay = jnp.exp(dt * a[None, None, :])                  # [B,S,H]

    def step(h, inputs):
        xt, dtt, dect, bt, ct = inputs
        # h: [B,H,P,N]
        h = h * dect[..., None, None] + \
            (dtt[..., None] * xt)[..., None] * bt[:, None, None, :]
        y = jnp.einsum("bhpn,bn->bhp", h, ct)
        return h, y

    h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    xs = (jnp.moveaxis(x.astype(jnp.float32), 1, 0),
          jnp.moveaxis(dt, 1, 0), jnp.moveaxis(decay, 1, 0),
          jnp.moveaxis(b.astype(jnp.float32), 1, 0),
          jnp.moveaxis(c.astype(jnp.float32), 1, 0))
    _, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype)


def ssd_decode_ref(h: jnp.ndarray, x: jnp.ndarray, dt: jnp.ndarray,
                   a_log: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray):
    """One SSD decode step.  h: [B,H,P,N]; x: [B,H,P]; dt: [B,H];
    b, c: [B,N].  Returns (h', y [B,H,P])."""
    a = -jnp.exp(a_log.astype(jnp.float32))
    decay = jnp.exp(dt.astype(jnp.float32) * a[None, :])
    h = h * decay[..., None, None] + \
        (dt[..., None] * x.astype(jnp.float32))[..., None] \
        * b[:, None, None, :].astype(jnp.float32)
    y = jnp.einsum("bhpn,bn->bhp", h, c.astype(jnp.float32))
    return h, y.astype(x.dtype)
