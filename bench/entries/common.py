"""What both entries share: the program's model config from a
configuration file, and per-layer norms of a parameter tree."""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


# published key of a configuration's ``model`` block -> the program's
# ``ModelConfig`` field that holds it ("a.b" reads ``cfg.a.b``).  A file adds
# the keys of its own architecture under ``compared``.
COMPARED = {
    "hidden_size": "d_model", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab",
    "tie_word_embeddings": "tie_embeddings", "hidden_act": "act",
    "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
}
# constants the file sets in the program's config rather than checks
APPLIED = ("rms_norm_eps", "rope_theta")


def _field(cfg, path: str):
    for name in path.split("."):
        cfg = getattr(cfg, name, None)
    return cfg


def model_config(doc: dict):
    """The program's ``ModelConfig`` for a configuration file: the named
    preset with the file's constants and the system's overrides.

    Every key of the file's ``model`` block is compared with the program's
    field that ``COMPARED`` or the file's own ``compared`` maps it to, or
    is listed, with the reason, under the file's ``not_compared``.  Refuses
    a preset whose values differ from the file's, and a key that is
    neither mapped nor listed."""
    from repro.configs import get_config  # noqa: PLC0415

    m, sysd, att = doc["model"], doc["system"], doc["attention"]
    fields = dict(COMPARED, **doc.get("compared", {}))
    applied = {fields[k]: float(m[k]) for k in APPLIED if k in m}
    cfg = get_config(sysd["model"]).replace(**applied, **sysd.get("overrides", {}))
    skip = doc.get("not_compared", {})
    unknown = sorted(set(m) - set(fields) - set(skip))
    if unknown:
        raise ValueError(f"model keys {unknown} are neither compared nor listed under "
                         "not_compared")
    want = {k: v for k, v in m.items() if k in fields and k not in skip}
    got = {k: _field(cfg, fields[k]) for k in want}
    want["attention"], got["attention"] = att["kind"], cfg.attention
    if cfg.taylor.order != att["order"] or cfg.taylor.alpha != att["alpha"]:
        raise ValueError(f"program taylor config {cfg.taylor} differs from {att}")
    if got != want:
        diff = {k: (want[k], got[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"program config differs from the file's (file, program): {diff}")
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
