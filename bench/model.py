"""A configuration file, the model the program builds from it, and the
benchmark's own weights.

``configs/<name>.json`` holds the published config as it is run
(``config``, keyed as in the source), the keys changed from the source
(``reduced``, with their published values in ``published``), sizes the
source leaves open (``assumed``), the deployment it stands for, and
``keys``: which source key gives each size the benchmark counts with.
``program`` names the program's registered architecture and any
overrides; :func:`program_config` checks that what the program builds
has exactly these sizes.
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def dims(conf: dict) -> dict:
    """The sizes the counts and the reference use, read from the
    source-keyed ``config`` through ``keys``; ``fixed`` holds the ones
    the source states in its code rather than its config."""
    out = {k: conf["config"][v] for k, v in conf["keys"].items()}
    out.update(conf.get("fixed", {}))
    return out


def program_config(conf: dict):
    """The program's ``ModelConfig`` for this file, checked size by size
    against :func:`dims`."""
    from repro.configs import get_config
    prog = conf["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]),
                              **prog.get("overrides", {}))
    d = dims(conf)
    want = {"d_model": d["d_model"], "n_heads": d["n_heads"],
            "n_kv_heads": d["n_kv_heads"], "hd": d["head_dim"],
            "d_ff": d["d_ff"], "n_layers": d["n_layers"],
            "vocab_size": d["vocab"], "norm_eps": d["norm_eps"],
            "qkv_bias": d["qkv_bias"],
            "tie_embeddings": d["tie_embeddings"],
            "rope_kind": "full" if d["rotary_dim"] == d["head_dim"]
            else "2d", "mlp_act": "swiglu", "block_pattern": ("attn",),
            "n_experts": 0}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"{conf['name']}: program config differs from "
                         f"the file (program, file): {bad}")
    if jnp.dtype(cfg.dtype) != jnp.dtype(d["compute_dtype"]):
        raise ValueError(f"{conf['name']}: program computes in "
                         f"{jnp.dtype(cfg.dtype)}, file states "
                         f"{d['compute_dtype']}")
    return cfg


def weight_shapes(d: dict) -> dict:
    """The parameter tree the program's decoder takes, as shapes: a
    stack of ``n_layers`` layers scanned under ``stack/scan/p0``."""
    D, H, KV, hd, F, L, V = (d["d_model"], d["n_heads"], d["n_kv_heads"],
                             d["head_dim"], d["d_ff"], d["n_layers"],
                             d["vocab"])
    layer = {"ln1": (L, D), "ln2": (L, D),
             "mix": {"wq": (L, D, H * hd), "wk": (L, D, KV * hd),
                     "wv": (L, D, KV * hd), "wo": (L, H * hd, D)},
             "ffn": {"w_gate": (L, D, F), "w_up": (L, D, F),
                     "w_down": (L, F, D)}}
    return {"embed": (V, D), "final_ln": (D,), "lm_head": (D, V),
            "stack": {"scan": {"p0": layer}}}


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def make_weights(d: dict, seed: int):
    """Seeded weights in float32, built on the device in one program.

    Matrices are normal with variance 1/fan-in (fan-in is the model
    width for the embedding); norm gains are drawn around 0 with
    standard deviation 0.1, so that the gain path is exercised (the
    program scales by 1 + gain)."""
    flat, tree = jax.tree_util.tree_flatten_with_path(
        weight_shapes(d), is_leaf=_is_shape)
    names = [path[-1].key for path, _ in flat]
    shapes = [s for _, s in flat]

    def build(key):
        out = []
        for k, name, s in zip(jax.random.split(key, len(shapes)), names,
                              shapes):
            x = jax.random.normal(k, s, jnp.float32)
            if "ln" in name:
                out.append(0.1 * x)
            else:
                fan_in = s[-1] if name == "embed" else s[-2]
                out.append(x / jnp.sqrt(jnp.float32(fan_in)))
        return jax.tree_util.tree_unflatten(tree, out)

    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 31),
                             seed // 2 ** 31)
    return jax.block_until_ready(jax.jit(build)(key))


def check_tree(params, cfg) -> None:
    """The weights must have the tree, shapes and types the program's
    own initialiser gives."""
    from repro.models import lm
    want = jax.eval_shape(lambda k: lm.init_lm(k, cfg),
                          jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    if (jax.tree_util.tree_structure(want)
            != jax.tree_util.tree_structure(got)
            or jax.tree_util.tree_leaves(want)
            != jax.tree_util.tree_leaves(got)):
        raise ValueError("benchmark weights do not match the program's "
                         "parameter tree")
