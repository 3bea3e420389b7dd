"""What both entries share: the program's model config from a
configuration file, and per-layer norms of a parameter tree."""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def model_config(doc: dict):
    """The program's ``ModelConfig`` for a configuration file: the named
    preset with the file's constants and the system's overrides.  Refuses
    a preset whose sizes differ from the file's."""
    from repro.configs import get_config  # noqa: PLC0415

    m, sysd, att = doc["model"], doc["system"], doc["attention"]
    cfg = get_config(sysd["model"]).replace(
        norm_eps=float(m["rms_norm_eps"]), rope_theta=float(m["rope_theta"]),
        **sysd.get("overrides", {}),
    )
    want = {
        "d_model": m["hidden_size"], "n_layers": m["num_hidden_layers"],
        "n_heads": m["num_attention_heads"], "n_kv_heads": m["num_key_value_heads"],
        "d_ff": m["intermediate_size"], "vocab": m["vocab_size"],
        "tie_embeddings": m["tie_word_embeddings"], "attention": att["kind"],
    }
    got = {k: getattr(cfg, k) for k in want}
    got["attention"] = cfg.attention
    if cfg.taylor.order != att["order"] or cfg.taylor.alpha != att["alpha"]:
        raise ValueError(f"program taylor config {cfg.taylor} differs from {att}")
    if got != want:
        raise ValueError(f"program config {got} differs from the file's {want}")
    return cfg


def leaf_names(tree) -> list:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [".".join(str(getattr(k, "key", k)) for k in path) for path, _ in flat]


@jax.jit
def _norms(tree):
    def one(x):
        x = x.astype(jnp.float32)
        # stacked layers ([groups, 1, ...]): one norm per layer
        axes = tuple(range(2, x.ndim)) if x.ndim >= 3 else None
        return jnp.sqrt(jnp.sum(x * x, axis=axes)).reshape(-1)
    return [one(x) for x in jax.tree.leaves(tree)]


def layer_norms(tree) -> Dict[str, float]:
    """L2 norm of every leaf, stacked leaves split per layer:
    ``{"blocks.group.r0.attn.wq.w[3]": ..., "embed.w": ...}``."""
    out = {}
    for name, vals in zip(leaf_names(tree), _norms(tree)):
        vals = np.asarray(vals)
        if name.startswith("blocks."):
            out.update({f"{name}[{i}]": float(v) for i, v in enumerate(vals)})
        else:
            out[name] = float(vals[0])
    return out


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float], keep=None) -> tuple:
    """``(gap, leaf)``: the largest ``|‖prog‖ − ‖ref‖|`` over the leaves,
    against the larger of the leaf's reference norm and the median leaf's."""
    names = [k for k in ref if keep is None or k in keep]
    med = float(np.median([ref[k] for k in names]))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in names}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf
