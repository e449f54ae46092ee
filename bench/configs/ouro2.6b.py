"""Ouro-2.6B's prefill as published (ByteDance Seed, "Scaling Latent
Reasoning via Looped Language Models"; config.json of ByteDance/Ouro-2.6B),
written out as the layer list the harness checks the program's graph
against.

One stack of ``num_hidden_layers`` decoder layers runs ``total_ut_steps``
times with the same weights.  Each layer has sandwich normalisation: an
RMSNorm before and after the attention and before and after the SwiGLU
FFN, each sub-layer inside a residual add.  Attention is causal
multi-head attention with RoPE on q and k; q, k and v come out of one
fused ``qkv`` projection, gate and up out of one ``gate_up`` projection.
The stack's final RMSNorm ends every step and feeds the next; the head
reads each sequence's last position after the last step.

Tokens are rows: N is batch * seq for every layer but the head (N =
batch).  ``tied`` names the step-0 layer whose weights a later step's
layer reads.  ``embed`` is the identity that takes in the embedded
sequence.  Departures, as the configuration file lists under ``assumed``:
the embedding gather and the early-exit gate are not computed.
"""
from __future__ import annotations

from typing import Dict, List

PARTS = ("in_norm", "qkv", "attn", "o", "attn_post_norm", "add1",
         "ffn_norm", "gate_up", "glu", "down", "ffn_post_norm", "add2")


def _l(name, kind, n, c, k=1, src=(), **extra):
    d = {"name": name, "kind": kind, "N": n, "C": c, "K": k,
         "src": list(src)}
    d.update(extra)
    return d


def layers(cfg: Dict, batch: int, seq: int) -> List[Dict]:
    hidden, ffn = cfg["hidden_size"], cfg["intermediate_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    n = batch * seq
    out = [_l("embed", "eltwise", n, hidden)]
    prev = "embed"
    for t in range(cfg["total_ut_steps"]):
        for i in range(cfg["num_hidden_layers"]):
            p = f"s{t}.l{i}."
            step = [
                _l(p + "in_norm", "norm", n, hidden, src=[prev], eps=eps),
                _l(p + "qkv", "fc", n, hidden, (heads + 2 * kv) * hd,
                   src=[p + "in_norm"]),
                _l(p + "attn", "attention", batch * heads, seq, hd,
                   src=[p + "qkv"], X=seq, batch=batch, seq=seq,
                   heads=heads,
                   kv_heads=kv, causal=True,
                   rope_theta=float(cfg["rope_theta"])),
                _l(p + "o", "fc", n, heads * hd, hidden, src=[p + "attn"]),
                _l(p + "attn_post_norm", "norm", n, hidden, src=[p + "o"],
                   eps=eps),
                _l(p + "add1", "eltwise", n, hidden,
                   src=[p + "attn_post_norm", prev]),
                _l(p + "ffn_norm", "norm", n, hidden, src=[p + "add1"],
                   eps=eps),
                _l(p + "gate_up", "fc", n, hidden, 2 * ffn,
                   src=[p + "ffn_norm"]),
                _l(p + "glu", "glu", n, ffn, src=[p + "gate_up"]),
                _l(p + "down", "fc", n, ffn, hidden, src=[p + "glu"]),
                _l(p + "ffn_post_norm", "norm", n, hidden,
                   src=[p + "down"], eps=eps),
                _l(p + "add2", "eltwise", n, hidden,
                   src=[p + "ffn_post_norm", p + "add1"]),
            ]
            out += step
            prev = p + "add2"
        out.append(_l(f"s{t}.norm", "norm", n, hidden, src=[prev], eps=eps))
        prev = f"s{t}.norm"
    for layer in out:
        if layer["name"].startswith("s") and not layer["name"].startswith(
                "s0."):
            layer["tied"] = "s0." + layer["name"].split(".", 1)[1]
    out.append(_l("head", "fc", batch, hidden, cfg["vocab_size"],
                  src=[prev], last_position=seq))
    return out
