"""Full language models assembled from the block zoo.

Supports every assigned architecture family:
  dense / moe decoder LMs (GQA attention + [Swi/Ge]GLU or MoE FFN),
  hybrid stacks (RG-LRU + local attention, RecurrentGemma-style),
  ssm stacks (mLSTM/sLSTM, xLSTM-style),
  encoder-decoder (Seamless-style; frame-embedding frontend stub),
  vlm (Pixtral-style; patch-embedding frontend stub prepended to text).

Homogeneous pattern groups are stacked and scanned (``lax.scan``) so HLO
size is O(1) in depth; heterogeneous tails run unscanned. Remat wraps each
block. Everything is a pure function over an explicit param pytree, so
``jax.eval_shape`` gives abstract params for the dry-run without ever
materializing a 480 B-parameter model.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import recurrent as rec_lib
from repro.models.common import (ModelConfig, dense_init,
                                 replicate_for_gather, rms_norm,
                                 shard_activations, split_keys)
from repro.models.mlp import init_mlp_cfg, mlp_cfg

PyTree = Any


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------


def _has_ffn(cfg: ModelConfig, kind: str) -> bool:
    if kind in ("mlstm", "slstm"):
        return cfg.d_ff > 0
    return True


def init_block(key, cfg: ModelConfig, kind: str,
               cross: bool = False) -> PyTree:
    ks = split_keys(key, ["mix", "ffn", "cross"])
    p: Dict[str, PyTree] = {"ln1": jnp.zeros((cfg.d_model,), jnp.float32)}
    if kind == "attn":
        p["mix"] = attn_lib.init_attention(ks["mix"], cfg)
    elif kind == "rglru":
        p["mix"] = rec_lib.init_rglru(ks["mix"], cfg)
    elif kind == "mlstm":
        p["mix"] = rec_lib.init_mlstm(ks["mix"], cfg)
    elif kind == "slstm":
        p["mix"] = rec_lib.init_slstm(ks["mix"], cfg)
    else:
        raise ValueError(kind)
    if cross:
        p["ln_cross"] = jnp.zeros((cfg.d_model,), jnp.float32)
        p["cross"] = attn_lib.init_cross_attention(ks["cross"], cfg)
    if _has_ffn(cfg, kind):
        p["ln2"] = jnp.zeros((cfg.d_model,), jnp.float32)
        if cfg.n_experts and kind == "attn":
            p["ffn"] = moe_lib.init_moe(ks["ffn"], cfg)
        else:
            p["ffn"] = init_mlp_cfg(ks["ffn"], cfg)
    return p


def apply_block(p: PyTree, x: jnp.ndarray, cfg: ModelConfig, kind: str, *,
                positions, enc_out=None, causal: bool = True
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence block application. Returns (x, aux_loss)."""
    aux = jnp.float32(0.0)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind == "attn":
        if causal:
            h = attn_lib.attention(p["mix"], h, cfg, positions)
        else:
            h = attn_lib.encoder_attention(p["mix"], h, cfg, positions)
    elif kind == "rglru":
        h = rec_lib.rglru_block(p["mix"], h, cfg)
    elif kind == "mlstm":
        h = rec_lib.mlstm_block(p["mix"], h, cfg)
    elif kind == "slstm":
        h = rec_lib.slstm_block(p["mix"], h, cfg)
    x = x + h
    if "cross" in p:
        h = rms_norm(x, p["ln_cross"], cfg.norm_eps)
        x = x + attn_lib.cross_attention(p["cross"], h, enc_out, cfg)
    if "ffn" in p:
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.n_experts and kind == "attn":
            aux = moe_lib.aux_load_balance_loss(p["ffn"], h, cfg)
            h = moe_lib.moe(p["ffn"], h, cfg)
        else:
            h = mlp_cfg(p["ffn"], h, cfg)
        x = x + h
    return x, aux


def apply_block_decode(p: PyTree, x: jnp.ndarray, cfg: ModelConfig,
                       kind: str, state: PyTree, *, pos, enc_out=None
                       ) -> Tuple[jnp.ndarray, PyTree]:
    """One-token block application with recurrent/KV state."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind == "attn":
        h, new_state = attn_lib.attention_decode(p["mix"], h, cfg, state, pos)
    elif kind == "rglru":
        h, new_state = rec_lib.rglru_decode(p["mix"], h, cfg, state)
    elif kind == "mlstm":
        h, new_state = rec_lib.mlstm_decode(p["mix"], h, cfg, state)
    elif kind == "slstm":
        h, new_state = rec_lib.slstm_decode(p["mix"], h, cfg, state)
    x = x + h
    if "cross" in p:
        h = rms_norm(x, p["ln_cross"], cfg.norm_eps)
        x = x + attn_lib.cross_attention(p["cross"], h, enc_out, cfg)
    if "ffn" in p:
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.n_experts and kind == "attn":
            h = moe_lib.moe(p["ffn"], h, cfg)
        else:
            h = mlp_cfg(p["ffn"], h, cfg)
        x = x + h
    return x, new_state


def init_block_state(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     dtype) -> PyTree:
    if kind == "attn":
        return attn_lib.init_kv_cache(cfg, batch, max_len, dtype)
    if kind == "rglru":
        return rec_lib.init_rglru_state(cfg, batch, dtype)
    if kind == "mlstm":
        return rec_lib.init_mlstm_state(cfg, batch)
    if kind == "slstm":
        return rec_lib.init_slstm_state(cfg, batch)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# stack layout: scanned groups + tail
# ---------------------------------------------------------------------------


def _stack_layout(cfg: ModelConfig) -> Tuple[int, Tuple[str, ...],
                                             Tuple[str, ...]]:
    """Returns (n_groups, period_kinds, tail_kinds)."""
    pattern = cfg.pattern_for_depth()
    period = cfg.block_pattern
    if not cfg.scan_layers:
        return 0, (), pattern
    n_groups = cfg.n_layers // len(period)
    tail = pattern[n_groups * len(period):]
    if n_groups < 2:        # scanning 0/1 group is pointless
        return 0, (), pattern
    return n_groups, period, tail


def _init_stack(key, cfg: ModelConfig, cross: bool) -> PyTree:
    n_groups, period, tail = _stack_layout(cfg)
    out: Dict[str, PyTree] = {}
    if n_groups:
        def init_group(k):
            gk = split_keys(k, [f"p{i}" for i in range(len(period))])
            return {f"p{i}": init_block(gk[f"p{i}"], cfg, kind, cross)
                    for i, kind in enumerate(period)}
        keys = jax.random.split(key, n_groups + 1)
        stacked = jax.vmap(init_group)(keys[:n_groups])
        out["scan"] = stacked
        key = keys[-1]
    tkeys = jax.random.split(key, max(len(tail), 1))
    for i, kind in enumerate(tail):
        out[f"tail_{i}"] = init_block(tkeys[i], cfg, kind, cross)
    return out


def _apply_stack(params: PyTree, x: jnp.ndarray, cfg: ModelConfig, *,
                 positions, enc_out=None, causal=True
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    n_groups, period, tail = _stack_layout(cfg)
    aux_total = jnp.float32(0.0)

    def one_group(carry, gparams):
        x, aux = carry
        for i, kind in enumerate(period):
            blk = functools.partial(apply_block, cfg=cfg, kind=kind,
                                    positions=positions, enc_out=enc_out,
                                    causal=causal)
            if cfg.remat:
                blk = jax.checkpoint(blk)
            x, a = blk(gparams[f"p{i}"], x)
            # constrain OUTSIDE the checkpoint boundary (inside trips the
            # SPMD partitioner's dynamic-slice handling)
            x = shard_activations(x, cfg)
            aux = aux + a
        return (x, aux), None

    x = shard_activations(x, cfg)
    if n_groups:
        (x, aux_total), _ = jax.lax.scan(one_group, (x, aux_total),
                                         params["scan"])
    for i, kind in enumerate(tail):
        blk = functools.partial(apply_block, cfg=cfg, kind=kind,
                                positions=positions, enc_out=enc_out,
                                causal=causal)
        if cfg.remat:
            blk = jax.checkpoint(blk)
        x, a = blk(params[f"tail_{i}"], x)
        x = shard_activations(x, cfg)
        aux_total = aux_total + a
    return x, aux_total


def _init_stack_state(cfg: ModelConfig, batch: int, max_len: int,
                      dtype) -> PyTree:
    n_groups, period, tail = _stack_layout(cfg)
    out: Dict[str, PyTree] = {}
    if n_groups:
        def one(_):
            return {f"p{i}": init_block_state(cfg, kind, batch, max_len,
                                              dtype)
                    for i, kind in enumerate(period)}
        out["scan"] = jax.vmap(one)(jnp.arange(n_groups))
    for i, kind in enumerate(tail):
        out[f"tail_{i}"] = init_block_state(cfg, kind, batch, max_len, dtype)
    return out


def _apply_stack_decode(params: PyTree, x: jnp.ndarray, cfg: ModelConfig,
                        state: PyTree, *, pos, enc_out=None
                        ) -> Tuple[jnp.ndarray, PyTree]:
    n_groups, period, tail = _stack_layout(cfg)
    new_state: Dict[str, PyTree] = {}

    def one_group(x, inp):
        gparams, gstate = inp
        gnew = {}
        for i, kind in enumerate(period):
            x, s = apply_block_decode(gparams[f"p{i}"], x, cfg, kind,
                                      gstate[f"p{i}"], pos=pos,
                                      enc_out=enc_out)
            gnew[f"p{i}"] = s
        return x, gnew

    if n_groups:
        x, new_state["scan"] = jax.lax.scan(one_group, x,
                                            (params["scan"], state["scan"]))
    for i, kind in enumerate(tail):
        x, s = apply_block_decode(params[f"tail_{i}"], x, cfg, kind,
                                  state[f"tail_{i}"], pos=pos,
                                  enc_out=enc_out)
        new_state[f"tail_{i}"] = s
    return x, new_state


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------


def init_lm(key, cfg: ModelConfig) -> PyTree:
    ks = split_keys(key, ["embed", "stack", "enc", "head", "front"])
    params: Dict[str, PyTree] = {
        "embed": dense_init(ks["embed"], (cfg.vocab_size, cfg.d_model),
                            in_axis=1),
        "final_ln": jnp.zeros((cfg.d_model,), jnp.float32),
        "stack": _init_stack(ks["stack"], cfg, cross=cfg.is_encdec),
    }
    if cfg.is_encdec:
        params["encoder"] = _init_stack(ks["enc"], _enc_cfg(cfg),
                                        cross=False)
        params["enc_final_ln"] = jnp.zeros((cfg.d_model,), jnp.float32)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks["head"], (cfg.d_model,
                                                    cfg.vocab_size))
    return params


def _enc_cfg(cfg: ModelConfig) -> ModelConfig:
    return dataclasses.replace(cfg, n_layers=cfg.n_encoder_layers,
                               n_experts=0, block_pattern=("attn",))


def _embed_inputs(params, cfg: ModelConfig, tokens, prefix_embeds):
    """Token embedding (+ optional prepended modality embeddings).

    Cast to compute dtype BEFORE the replication constraint (halves the
    all-gather bytes); small token counts gather straight from the sharded
    table (replicating a 256k-row table for a 128-token decode step was a
    measured 2.9 GiB/step all-gather - EXPERIMENTS.md SS.Perf iter 2)."""
    table = params["embed"].astype(cfg.dtype)
    if tokens.size > 4096:
        table = replicate_for_gather(table, cfg)
    x = table[tokens]
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(cfg.dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return x, positions


def _lm_logits(params, cfg: ModelConfig, x) -> jnp.ndarray:
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(cfg.dtype)
    return x @ head


def encode(params, cfg: ModelConfig, enc_frames) -> jnp.ndarray:
    """Encoder for enc-dec models; enc_frames: (B, Se, d) frontend stub."""
    ec = _enc_cfg(cfg)
    B, Se, _ = enc_frames.shape
    positions = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))
    x, _ = _apply_stack(params["encoder"], enc_frames.astype(cfg.dtype), ec,
                        positions=positions, causal=False)
    return rms_norm(x, params["enc_final_ln"], cfg.norm_eps)


def forward(params, cfg: ModelConfig, tokens, *, prefix_embeds=None,
            enc_frames=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Training/prefill forward. Returns (logits, aux_loss)."""
    enc_out = None
    if cfg.is_encdec:
        assert enc_frames is not None
        enc_out = encode(params, cfg, enc_frames)
    x, positions = _embed_inputs(params, cfg, tokens, prefix_embeds)
    x, aux = _apply_stack(params["stack"], x, cfg, positions=positions,
                          enc_out=enc_out, causal=True)
    return _lm_logits(params, cfg, x), aux


_CE_CHUNK = 512


def forward_hidden(params, cfg: ModelConfig, tokens, *, prefix_embeds=None,
                   enc_frames=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Like forward() but stops at the final norm (no vocab projection)."""
    enc_out = None
    if cfg.is_encdec:
        assert enc_frames is not None
        enc_out = encode(params, cfg, enc_frames)
    x, positions = _embed_inputs(params, cfg, tokens, prefix_embeds)
    x, aux = _apply_stack(params["stack"], x, cfg, positions=positions,
                          enc_out=enc_out, causal=True)
    return rms_norm(x, params["final_ln"], cfg.norm_eps), aux


def _chunked_ce(h, head, targets, mask, n_chunks: int) -> jnp.ndarray:
    """Cross-entropy over sequence chunks: the (B, S, vocab) logits tensor
    is never materialized whole (multi-GiB at 256k vocabs); each chunk's
    logits are recomputed in the backward pass (checkpoint)."""
    B, S, d = h.shape
    c = S // n_chunks
    hc = h.reshape(B, n_chunks, c, d).swapaxes(0, 1)
    tc = targets.reshape(B, n_chunks, c).swapaxes(0, 1)
    mc = mask.reshape(B, n_chunks, c).swapaxes(0, 1)

    @jax.checkpoint
    def one(carry, xs):
        hx, tx, mx = xs
        logits = (hx @ head).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tx[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(nll * mx), None

    total, _ = jax.lax.scan(one, jnp.float32(0.0), (hc, tc, mc))
    return total


def loss_fn(params, cfg: ModelConfig, batch) -> Tuple[jnp.ndarray, Dict]:
    """Next-token cross-entropy (text positions only for vlm prefixes)."""
    h, aux = forward_hidden(params, cfg, batch["tokens"],
                            prefix_embeds=batch.get("prefix_embeds"),
                            enc_frames=batch.get("enc_frames"))
    P = 0 if batch.get("prefix_embeds") is None else \
        batch["prefix_embeds"].shape[1]
    h = h[:, P:, :]
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(cfg.dtype)
    targets = batch["labels"]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones(targets.shape, jnp.float32)
    S = h.shape[1]
    n_chunks = S // _CE_CHUNK if S % _CE_CHUNK == 0 and S > _CE_CHUNK else 1
    if n_chunks > 1:
        total_nll = _chunked_ce(h, head, targets, mask, n_chunks)
    else:
        logits = (h @ head).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        total_nll = jnp.sum(nll * mask)
    loss = total_nll / jnp.maximum(jnp.sum(mask), 1.0)
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux": aux,
                   "tokens": jnp.sum(mask)}


# -- decode -----------------------------------------------------------------


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                      *, enc_out=None) -> PyTree:
    state = {"layers": _init_stack_state(cfg, batch, max_len, cfg.dtype)}
    if cfg.is_encdec:
        state["enc_out"] = enc_out
    return state


def decode_step(params, cfg: ModelConfig, state, tokens, pos
                ) -> Tuple[jnp.ndarray, PyTree]:
    """One decode step. tokens: (B,) int32; pos: () int32, or (B,) int32
    for per-row positions (slot continuous batching).

    Returns (logits (B, vocab), new_state).
    """
    x = params["embed"].astype(cfg.dtype)[tokens][:, None, :]
    enc_out = state.get("enc_out")
    x, new_layers = _apply_stack_decode(params["stack"], x, cfg,
                                        state["layers"], pos=pos,
                                        enc_out=enc_out)
    logits = _lm_logits(params, cfg, x)[:, 0, :]
    new_state = dict(state)
    new_state["layers"] = new_layers
    return logits, new_state


# Leaves the blocks read in float32: the norm gains (``rms_norm`` casts its
# ``scale`` to float32) and the recurrent decay and gate biases, which are
# added to float32 pre-activations. Every other float leaf is read only
# through ``.astype(cfg.dtype)`` (``x.dtype`` is ``cfg.dtype`` in a step).
_FLOAT32_LEAVES = frozenset({"ln1", "ln2", "ln_cross", "final_ln",
                             "enc_final_ln", "lam", "b_a", "b_x", "bf",
                             "bi"})


def compute_casts(params, cfg: ModelConfig) -> PyTree:
    """A tree of bools like ``params``: True at each float leaf that the
    model reads only as ``cfg.dtype`` and that is not in ``cfg.dtype``."""
    def cast(path, x):
        return (getattr(path[-1], "key", None) not in _FLOAT32_LEAVES
                and jnp.issubdtype(x.dtype, jnp.floating)
                and x.dtype != cfg.dtype)
    return jax.tree_util.tree_map_with_path(cast, params)


@functools.partial(jax.jit, static_argnums=1)
def _astype_all(leaves, dtype):
    return tuple(x.astype(dtype) for x in leaves)


def compute_params(params, cfg: ModelConfig) -> PyTree:
    """``params`` with the ``compute_casts`` leaves cast to ``cfg.dtype``
    in one jitted call: ``decode_step`` then multiplies the operands it
    would have cast itself, bit for bit, without casting them each call.
    Every other leaf is the caller's own array; ``params`` is unchanged."""
    flat, treedef = jax.tree_util.tree_flatten(params)
    mask = jax.tree_util.tree_leaves(compute_casts(params, cfg))
    cast = iter(_astype_all(tuple(x for m, x in zip(mask, flat) if m),
                            jnp.dtype(cfg.dtype)) if any(mask) else ())
    return jax.tree_util.tree_unflatten(
        treedef, [next(cast) if m else x for m, x in zip(mask, flat)])


def prefill(params, cfg: ModelConfig, tokens, *, max_len: int,
            prefix_embeds=None, enc_frames=None
            ) -> Tuple[jnp.ndarray, PyTree]:
    """Process a prompt and build a decode state by stepping (reference
    implementation used by tests; production serving uses forward() for
    logits and batch-writes the cache)."""
    B, S = tokens.shape
    enc_out = encode(params, cfg, enc_frames) if cfg.is_encdec else None
    state = init_decode_state(cfg, B, max_len, enc_out=enc_out)
    logits = None
    x, _ = _embed_inputs(params, cfg, tokens, prefix_embeds)
    total = x.shape[1]
    for t in range(total):
        tok_x = x[:, t]
        # re-embedding bypass: feed embeddings directly
        logits, state = _decode_step_embed(params, cfg, state, tok_x,
                                           jnp.int32(t))
    return logits, state


def _decode_step_embed(params, cfg, state, x_embed, pos):
    x = x_embed[:, None, :]
    enc_out = state.get("enc_out")
    x, new_layers = _apply_stack_decode(params["stack"], x, cfg,
                                        state["layers"], pos=pos,
                                        enc_out=enc_out)
    logits = _lm_logits(params, cfg, x)[:, 0, :]
    new_state = dict(state)
    new_state["layers"] = new_layers
    return logits, new_state
