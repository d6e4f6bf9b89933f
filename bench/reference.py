"""The plain reference decoder, and the readings that decide
``correct``.

A straightforward float32 forward pass over whole sequences, written
from the published architecture (pre-norm decoder, RMSNorm with gain
1 + g, rotary embedding on the first ``rotary_dim`` dims of each head
in interleaved pairs, grouped-query attention, SwiGLU). It imports
nothing of the program and reads only the benchmark's weights. Every
product runs at ``precision="highest"`` so that float32 means float32 on
a TPU.

``quant`` gives the controls, the reference computed in the precisions
below the configuration's bfloat16 that a later change could serve in:
both operands of every weight product rounded, with per-output-channel
weight scales and per-row activation scales, to ``"int8"`` (symmetric,
the largest magnitude to 127) or to ``"fp8"`` (float8 e4m3, the largest
magnitude to 448).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _fp8(x, axis):
    """Round to float8 e4m3: 3 mantissa bits, steps of 2**-9 below the
    smallest normal 2**-6; the scale maps the largest |x| to 448."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-30) / 448.0
    y = x / s
    _, e = jnp.frexp(y)
    step = jnp.exp2((jnp.maximum(e, -5) - 4).astype(jnp.float32))
    return jnp.round(y / step) * step * s


def _int8(x, axis):
    """Round to the symmetric int8 grid whose scale maps the largest |x|
    to 127."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-30) / 127.0
    return jnp.round(x / s) * s


QUANT = {"int8": _int8, "fp8": _fp8}


def _mm(x, w, quant):
    if quant is not None:
        x, w = QUANT[quant](x, -1), QUANT[quant](w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


def _rope(x, pos, rot, theta):
    """x: (B, S, h, hd); rotate dims [0, rot) in pairs (2i, 2i+1)."""
    freqs = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos[:, None].astype(jnp.float32) * freqs          # (S, rot/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., 0:rot:2], x[..., 1:rot:2]
    r = jnp.stack([a * cos - b * sin, b * cos + a * sin], -1)
    return jnp.concatenate([r.reshape(a.shape[:-1] + (rot,)),
                            x[..., rot:]], -1)


def logits(w, d: dict, tokens, *, quant: Optional[str] = None):
    """(B, S) token ids -> (B, S, vocab) float32 logits."""
    B, S = tokens.shape
    H, KV, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    pos = jnp.arange(S)
    mask = pos[None, :] <= pos[:, None]
    x = w["embed"][tokens]

    def layer(x, p):
        h = _norm(x, p["ln1"], d["norm_eps"])
        q = _mm(h, p["mix"]["wq"], quant).reshape(B, S, H, hd)
        k = _mm(h, p["mix"]["wk"], quant).reshape(B, S, KV, hd)
        v = _mm(h, p["mix"]["wv"], quant).reshape(B, S, KV, hd)
        q = _rope(q, pos, d["rotary_dim"], d["rope_theta"])
        k = _rope(k, pos, d["rotary_dim"], d["rope_theta"])
        k = jnp.repeat(k, H // KV, axis=2)       # query head h reads kv h//g
        v = jnp.repeat(v, H // KV, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
            / np.sqrt(hd)
        s = jnp.where(mask, s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                       precision=HIGHEST).reshape(B, S, H * hd)
        x = x + _mm(o, p["mix"]["wo"], quant)
        h = _norm(x, p["ln2"], d["norm_eps"])
        f = p["ffn"]
        u = jax.nn.silu(_mm(h, f["w_gate"], quant)) * _mm(h, f["w_up"],
                                                          quant)
        return x + _mm(u, f["w_down"], quant), None

    x, _ = jax.lax.scan(layer, x, w["stack"]["scan"]["p0"])
    x = _norm(x, w["final_ln"], d["norm_eps"])
    return _mm(x, w["lm_head"], quant)


@partial(jax.jit, static_argnames=("d_items", "quant"))
def _gaps(w, tokens, targets, *, d_items, quant):
    d = dict(d_items)
    ref = logits(w, d, tokens)
    if quant is not None:
        targets = jnp.argmax(logits(w, d, tokens, quant=quant), -1)
    picked = jnp.take_along_axis(ref, targets[..., None], -1)[..., 0]
    return ref.max(-1) - picked


def _frozen(d: dict):
    return tuple(sorted((k, v) for k, v in d.items()
                        if isinstance(v, (int, float, bool, str))))


def pack(seqs, pad_to: int):
    """Sequences of (prompt, served tokens) -> token and target arrays of
    width ``pad_to``. Position ``len(prompt) - 1 + i`` predicts served
    token ``i``; other positions are masked out by ``sel``."""
    B = len(seqs)
    toks = np.zeros((B, pad_to), np.int32)
    tgt = np.zeros((B, pad_to), np.int32)
    sel = np.zeros((B, pad_to), bool)
    for b, (prompt, out) in enumerate(seqs):
        seq = list(prompt) + list(out[:-1])
        if len(seq) > pad_to:
            raise ValueError(f"sequence of {len(seq)} over {pad_to}")
        toks[b, :len(seq)] = seq
        lo = len(prompt) - 1
        tgt[b, lo:lo + len(out)] = out
        sel[b, lo:lo + len(out)] = True
    return toks, tgt, sel


def gaps(w, d: dict, seqs, *, pad_to: int, rows: int,
         quant: Optional[str] = None) -> np.ndarray:
    """At every compared position of ``seqs`` (padded to ``pad_to`` and
    run ``rows`` sequences at a time; padding lies after every compared
    position, so causal attention never reads it), the gap by which the
    served token's reference logit lies below the reference's best. With
    ``quant``, the control stands in the program's place: the token the
    control puts first at that position, on the same served tokens,
    takes the served token's."""
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(seqs), rows):
            chunk = list(seqs[i:i + rows])
            n = len(chunk)
            chunk += [chunk[-1]] * (rows - n)
            toks, tgt, sel = pack(chunk, pad_to)
            g = np.asarray(_gaps(w, jnp.asarray(toks), jnp.asarray(tgt),
                                 d_items=_frozen(d), quant=quant))
            out.append(g[:n][sel[:n]])
    return np.concatenate(out) if out else np.zeros(0, np.float32)
