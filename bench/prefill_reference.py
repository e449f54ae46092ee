"""The plain reference of a looped transformer's prefill (Ouro's LoopLM)
and the comparison that decides a ``prefill`` cell's ``correct``.

The forward follows the published description in straightforward
``jax.numpy``, float32, with every product at ``Precision.HIGHEST``, and
imports nothing of the program.  One stack of ``num_hidden_layers``
decoder layers runs ``total_ut_steps`` times as a Python loop over steps
that reuses one weight dict; each layer is

    h = x + post_norm(o(attention(rope(q), rope(k), v)))   q, k, v = qkv(in_norm(x))
    y = h + ffn_post_norm(down(silu(gate) * up))          gate, up = gate_up(ffn_norm(h))

with causal multi-head attention (K/V heads repeated over their query
heads) and RMSNorm x / sqrt(mean(x^2) + eps) * g.  The stack's final
RMSNorm ends every step; the head reads each sequence's last position
after the last step.  RoPE rotates the pairs (i, i + D/2) by
pos * theta ** (-2i / D), angles taken in float64 on the host.

``precision="high"`` is a control: the same forward with every product,
the projections, QK^T and PV, taken in three bf16 passes
(``reference._product``), the step below what the configuration
states.  The comparison has to fail it.
"""
from __future__ import annotations

import functools
from typing import Dict, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import reference

HIGHEST = jax.lax.Precision.HIGHEST
#: the fc and the RMSNorm layers of one decoder layer, by part name
FC_PARTS = ("qkv", "o", "gate_up", "down")
NORM_PARTS = ("in_norm", "attn_post_norm", "ffn_norm", "ffn_post_norm")


def _widths(cfg: Mapping) -> Dict[str, Tuple[int, int]]:
    hidden, ffn = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    return {"qkv": (hidden, (h + 2 * kv) * d), "o": (h * d, hidden),
            "gate_up": (hidden, 2 * ffn), "down": (ffn, hidden)}


def feeds(cfg: Mapping, batch: int, seq: int
          ) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """Every array a forward takes, as ``name -> (shape, draw)``: the
    embedded sequence ``embed.I`` (a normal draw), and the weights of the
    first step's layers only, which every step reads: fc weights [C, K]
    (``fan_in`` draws, scaled by C ** -0.5) and RMSNorm gains [hidden]
    (``gain`` draws, 1 + 0.1 x normal)."""
    hidden = cfg["hidden_size"]
    out = {"embed.I": ((batch * seq, hidden, 1, 1), "normal")}
    for i in range(cfg["num_hidden_layers"]):
        for part, shape in _widths(cfg).items():
            out[f"s0.l{i}.{part}.W"] = (shape, "fan_in")
        for part in NORM_PARTS:
            out[f"s0.l{i}.{part}.W"] = ((hidden,), "gain")
    out["s0.norm.W"] = ((hidden,), "gain")
    out["head.W"] = ((hidden, cfg["vocab_size"]), "fan_in")
    return out


def rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x [..., S, D] rotated by position."""
    s, d = x.shape[-2], x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)
    sin = jnp.asarray(np.sin(ang), jnp.float32)
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def rmsnorm(x: jnp.ndarray, g: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _einsum(spec: str):
    return lambda a, b: jnp.einsum(spec, a, b, precision=HIGHEST)


def attention(qkv: jnp.ndarray, batch: int, seq: int, heads: int, kv: int,
              d: int, theta: float, precision: str) -> jnp.ndarray:
    """Causal attention of one layer: [batch * seq, (heads + 2kv) * d]
    -> [batch * seq, heads * d]."""
    t = qkv.reshape(batch, seq, heads + 2 * kv, d).transpose(0, 2, 1, 3)
    q = rope(t[:, :heads], theta)
    k = rope(t[:, heads:heads + kv], theta)
    v = t[:, heads + kv:]
    k = jnp.repeat(k, heads // kv, axis=1)
    v = jnp.repeat(v, heads // kv, axis=1)
    s = reference._product(_einsum("bhqd,bhkd->bhqk"), q, k, precision) \
        * d ** -0.5
    pos = np.arange(seq)
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    o = reference._product(_einsum("bhqk,bhkd->bhqd"), p, v, precision)
    return o.transpose(0, 2, 1, 3).reshape(batch * seq, heads * d)


@functools.partial(jax.jit, static_argnames=(
    "batch", "seq", "heads", "kv", "d", "eps", "theta", "precision"))
def decoder_layer(x, w, batch: int, seq: int, heads: int, kv: int, d: int,
                  eps: float, theta: float, precision: str):
    """One decoder layer (module docstring) over [batch * seq, hidden];
    ``w`` holds its weights by part name."""
    def fc(a, part):
        return reference.fc(a, w[part], precision)

    qkv = fc(rmsnorm(x, w["in_norm"], eps), "qkv")
    a = attention(qkv, batch, seq, heads, kv, d, theta, precision)
    h = x + rmsnorm(fc(a, "o"), w["attn_post_norm"], eps)
    gu = fc(rmsnorm(h, w["ffn_norm"], eps), "gate_up")
    gate, up = jnp.split(gu, 2, axis=-1)
    f = fc(gate * jax.nn.sigmoid(gate) * up, "down")
    return h + rmsnorm(f, w["ffn_post_norm"], eps)


def forward(cfg: Mapping, arrays: Mapping, batch: int, seq: int,
            precision: str = "highest") -> jnp.ndarray:
    """The last position's logits [batch, vocab] of every sequence."""
    if precision not in reference.PRECISIONS:
        raise ValueError(f"precision must be one of {reference.PRECISIONS}")
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    static = dict(batch=batch, seq=seq, heads=cfg["num_attention_heads"],
                  kv=cfg["num_key_value_heads"], d=cfg["head_dim"], eps=eps,
                  theta=theta, precision=precision)
    weights = [{p: arrays[f"s0.l{i}.{p}.W"] for p in FC_PARTS + NORM_PARTS}
               for i in range(cfg["num_hidden_layers"])]
    h = arrays["embed.I"].reshape(batch * seq, cfg["hidden_size"])
    for _ in range(cfg["total_ut_steps"]):       # the same weights each step
        for w in weights:
            h = decoder_layer(h, w, **static)
        h = rmsnorm(h, arrays["s0.norm.W"], eps)
    last = h.reshape(batch, seq, -1)[:, -1]
    return reference.fc(last, arrays["head.W"], precision)


def compare(cfg: Mapping, arrays: Mapping, logits: jnp.ndarray, batch: int,
            seq: int, precision: str = "highest") -> float:
    """Largest gap of ``logits`` to the reference's over the reference's
    largest magnitude (``inf`` for a non-finite output or another
    shape)."""
    want = forward(cfg, arrays, batch, seq, precision)
    if tuple(logits.shape) != tuple(want.shape):
        return float("inf")
    d, m, finite = (float(v) for v in reference.gap(logits, want))
    return d / m if finite and m > 0 else float("inf")
