"""Shared by the language-model drivers: the program's model built from
the configuration file, and the seeded weights placed in its tree."""

from __future__ import annotations

from typing import Dict

import jax

# the reference's leaf names, by the last key of the program's tree path
_PROGRAM_KEYS = {"table": "embed"}


def model_config(cfg: Dict):
    """The program's ModelConfig for this file's sizes."""
    from repro.configs import get_config
    return get_config(cfg["program_arch"]).replace(
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), dtype=cfg["torch_dtype"])


def leaf_name(path) -> str:
    key = path[-1].key
    return _PROGRAM_KEYS.get(key, key)


def program_params(model, weights: Dict):
    """The program's parameter tree holding the reference's weight
    arrays (no copy); its structure, shapes and dtypes are checked
    against the program's own ``init``."""
    from repro.models import unbox
    abstract = jax.eval_shape(lambda: unbox(model.init(jax.random.PRNGKey(0))))
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    leaves = []
    for path, want in paths:
        got = weights[leaf_name(path)]
        if got.shape != want.shape or got.dtype != want.dtype:
            raise ValueError(f"{jax.tree_util.keystr(path)}: the program wants "
                             f"{want.shape} {want.dtype}, the weights are "
                             f"{got.shape} {got.dtype}")
        leaves.append(got)
    if len(leaves) != len(weights):
        raise ValueError(f"the program has {len(leaves)} weight arrays, the "
                         f"reference {len(weights)}")
    return jax.tree_util.tree_unflatten(treedef, leaves)


def leaf_norms(tree) -> Dict[str, float]:
    """Per-leaf float32 L2 norms of a tree shaped like the parameters."""
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jax.numpy.linalg.norm(
        x.astype(jax.numpy.float32).ravel()) for x in xs])(
            [x for _, x in paths])
    return {leaf_name(p): float(n) for (p, _), n in zip(paths, norms)}


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   leaves) -> float:
    """The largest |norm(program) - norm(reference)| over ``leaves``, each
    against the larger of that leaf's reference norm and the median
    leaf's reference norm."""
    ref_vals = sorted(ref[n] for n in leaves)
    median = ref_vals[len(ref_vals) // 2]
    return max(abs(prog[n] - ref[n]) / max(ref[n], median, 1e-30)
               for n in leaves)
