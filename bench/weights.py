"""Seeded weights, drawn on the device in one jitted call.

The tree has the layout the program's model takes (``embed``, one scanned
stage ``stages/s0/b0`` with a leading layer axis, ``final_norm``, and
``lm_head`` only when embeddings are untied), written out here from the
sizes so that neither the benchmark nor its reference runs the program's
initializer.  Matrices are normal with variance 1/fan-in, embeddings
normal with scale 0.02, norm scales 1.  Where ``orthonormal`` names
leaves (the manifold-constrained ones in training), those are drawn with
orthonormal columns (QR of a normal draw at full f32 precision), which
is a feasible starting point on the Stiefel manifold.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ATTN = ("wq", "wk", "wv", "wo")


def sizes_of(config: dict) -> dict:
    """The model's sizes from a configuration file's published keys."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    return {"d": d, "h": h, "hkv": config["num_key_value_heads"],
            "hd": config.get("head_dim", d // h),
            "ff": config["intermediate_size"], "vocab": config["vocab_size"],
            "layers": config["num_hidden_layers"],
            "tied": bool(config["tie_word_embeddings"]),
            "eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"])}


def shapes(sz: dict) -> dict:
    """Leaf shapes, in the program's parameter layout."""
    d, hd, L = sz["d"], sz["hd"], sz["layers"]
    blk = {"ln1": {"scale": (L, d)},
           "attn": {"wq": (L, d, sz["h"] * hd), "wk": (L, d, sz["hkv"] * hd),
                    "wv": (L, d, sz["hkv"] * hd), "wo": (L, sz["h"] * hd, d)},
           "ln2": {"scale": (L, d)},
           "mlp": {"w_gate": (L, d, sz["ff"]), "w_up": (L, d, sz["ff"]),
                   "w_down": (L, sz["ff"], d)}}
    out = {"embed": (sz["vocab"], d), "stages": {"s0": {"b0": blk}},
           "final_norm": {"scale": (d,)}}
    if not sz["tied"]:
        out["lm_head"] = (d, sz["vocab"])
    return out


def _paths(tree, prefix=""):
    for k in sorted(tree):
        v = tree[k]
        p = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            yield from _paths(v, p)
        else:
            yield p, v


def _set(tree: dict, path: str, value) -> None:
    keys = path.split("/")
    for k in keys[:-1]:
        tree = tree.setdefault(k, {})
    tree[keys[-1]] = value


def is_orthonormal_leaf(path: str, shape) -> bool:
    """Attention projections that are tall or square (d >= r): the leaves
    a Stiefel policy over ``attn/(wq|wk|wv|wo)`` constrains."""
    return path.rsplit("/", 1)[-1] in ATTN and shape[-2] >= shape[-1]


def _leaf(key, path: str, shape, orthonormal: bool):
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        return jnp.ones(shape, jnp.float32)
    if name == "embed":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    a = jax.random.normal(key, shape, jnp.float32)
    if orthonormal and is_orthonormal_leaf(path, shape):
        q, r = jnp.linalg.qr(a)
        # sign fix: a unique factor, independent of the QR routine's choice
        s = jnp.sign(jnp.diagonal(r, axis1=-2, axis2=-1))
        return q * jnp.where(s == 0, 1.0, s)[..., None, :]
    return a * (shape[-2] ** -0.5)


def draw(key, sz: dict, dtype, orthonormal: bool = False) -> dict:
    """All leaves from ``key`` (traceable: jit this with the key as an
    argument so one compiled program serves every seed)."""
    out: dict = {}
    with jax.default_matmul_precision("highest"):
        for i, (path, shape) in enumerate(_paths(shapes(sz))):
            leaf = _leaf(jax.random.fold_in(key, i), path, shape, orthonormal)
            _set(out, path, leaf.astype(dtype))
    return out


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw_jit(key, sz_items, dtype_name, orthonormal):
    return draw(key, dict(sz_items), jnp.dtype(dtype_name), orthonormal)


def draw_on_device(key, sz: dict, dtype, orthonormal: bool = False) -> dict:
    return _draw_jit(key, tuple(sorted(sz.items())), jnp.dtype(dtype).name,
                     orthonormal)


def key_for(seed: int, stream: int = 0):
    """A PRNG key from any whole-number seed (``--seed`` may exceed 32
    bits): numpy folds the seed into 31 bits first."""
    word = int(np.random.default_rng([int(seed) % 2**63, stream])
               .integers(0, 2**31 - 1))
    return jax.random.PRNGKey(word)


def check_layout(params: dict, program_params_shape: dict) -> None:
    """The drawn tree must have the program's layout exactly."""
    a = jax.tree_util.tree_structure(params)
    b = jax.tree_util.tree_structure(program_params_shape)
    if a != b:
        raise ValueError(f"weight tree {a} does not match the program's {b}")
    for x, y in zip(jax.tree.leaves(params),
                    jax.tree.leaves(program_params_shape)):
        if tuple(x.shape) != tuple(y.shape):
            raise ValueError(f"leaf shape {x.shape} != program's {y.shape}")
