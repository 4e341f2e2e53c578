"""Model weights made by the benchmark, from the run seed, on the device.

The weights are the benchmark's input data: the program is handed them,
and the plain reference (``reference.py``) reads the same arrays.  So
the reference never reads anything the program made.

The tree is the program's parameter layout (its checkpoint format):
names, shapes and storage dtypes, the LM's from its block
(``bench/lm/<block>.py``, ``layout``).  ``check_layout`` compares
it with the layout the program itself builds, so a change of format
fails loudly at set-up instead of serving a model nobody checked.
"""
from __future__ import annotations

import math
from types import ModuleType
from typing import Any, Dict

import jax
import jax.numpy as jnp

BF16, F32 = jnp.bfloat16, jnp.float32

# one draw holds at most this many elements; bigger leaves are drawn in
# row blocks under lax.map, so the temporaries stay small
_BLOCK = 1 << 26


class Leaf:
    """A weight to draw: shape, storage dtype and kind."""

    def __init__(self, shape, dtype, kind: str, scale: float = 0.0):
        self.shape = tuple(int(s) for s in shape)
        self.dtype = dtype
        self.kind = kind          # dense | norm | bias
        self.scale = scale


def dense(shape, fan_in=None, scale=None):
    """A bfloat16 matrix drawn with standard deviation ``scale``, by
    default ``fan_in ** -0.5`` (``fan_in`` defaults to the second-to-last
    axis)."""
    fan_in = fan_in if fan_in is not None else shape[-2]
    return Leaf(shape, BF16, "dense", scale if scale is not None
                else fan_in ** -0.5)


def vit_layout(v: Dict[str, Any], d_lm: int) -> Dict[str, Any]:
    d, f, R = v["d_model"], v["d_ff"], v["n_layers"]
    p2, g2 = v["patch"] ** 2, v["group"] ** 2
    n_patches = (v["image"] // v["patch"]) ** 2
    return {
        "patch_embed": dense((p2, d), p2),
        "pos_embed": dense((n_patches, d), scale=0.02),
        "blocks": {
            "ln1": {"scale": Leaf((R, d), F32, "norm")},
            "wq": dense((R, d, d), d), "wk": dense((R, d, d), d),
            "wv": dense((R, d, d), d), "wo": dense((R, d, d), d),
            "ln2": {"scale": Leaf((R, d), F32, "norm")},
            "ffn": {"wg": dense((R, d, f), d), "wu": dense((R, d, f), d),
                    "wd": dense((R, f, d), f)},
        },
        "final_norm": {"scale": Leaf((d,), F32, "norm")},
        "projector": dense((g2 * d, d_lm), g2 * d),
    }


def _is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def _draw_block(key, shape, leaf: Leaf):
    if leaf.kind == "norm":
        # scales near 1, not all 1: a norm whose scale is dropped shows
        u = jax.random.uniform(key, shape, F32, -1.0, 1.0)
        return (1.0 + 0.1 * u).astype(leaf.dtype)
    # uniform with the variance of N(0, scale^2)
    u = jax.random.uniform(key, shape, BF16, -1.0, 1.0)
    return u * jnp.asarray(leaf.scale * math.sqrt(3.0), BF16)


def _draw(key, leaf: Leaf):
    shape = leaf.shape
    n = math.prod(shape)
    if n <= _BLOCK or len(shape) < 2:
        return _draw_block(key, shape, leaf)
    rows = shape[0]
    # fewest row blocks that keep each draw under _BLOCK elements
    blocks = next(b for b in range(1, rows + 1)
                  if rows % b == 0 and n // b <= _BLOCK or b == rows)
    sub = (rows // blocks,) + shape[1:]
    out = jax.lax.map(lambda k: _draw_block(k, sub, leaf),
                      jax.random.split(key, blocks))
    return out.reshape(shape)


def root_key(seed: int):
    """A key from any whole-number seed (beyond 32 bits too)."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _layout(lm: Dict[str, Any], vit: Dict[str, Any], block: ModuleType):
    return block.layout(lm), vit_layout(vit, lm["d_model"])


def make_weights(lm: Dict[str, Any], vit: Dict[str, Any], seed: int,
                 block: ModuleType):
    """(LM params, ViT params) for the config, drawn on the device in
    one jitted call; every leaf in its storage dtype.  The LM's leaves
    are those of its ``block`` (``bench/lm/<block>.py``)."""
    layout = _layout(lm, vit, block)
    leaves, treedef = jax.tree_util.tree_flatten(layout, is_leaf=_is_leaf)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        return treedef.unflatten(
            [_draw(keys[i], lf) for i, lf in enumerate(leaves)])

    return jax.jit(build)(root_key(seed))


def shapes(lm: Dict[str, Any], vit: Dict[str, Any], block: ModuleType):
    """The weight tree as ShapeDtypeStructs (no allocation)."""
    layout = _layout(lm, vit, block)
    return jax.tree_util.tree_map(
        lambda lf: jax.ShapeDtypeStruct(lf.shape, lf.dtype), layout,
        is_leaf=_is_leaf)


def check_layout(mine, programs) -> None:
    """Raise unless both trees have the same structure, shapes, dtypes."""
    a, ta = jax.tree_util.tree_flatten(mine)
    b, tb = jax.tree_util.tree_flatten(programs)
    if ta != tb:
        raise ValueError(f"weight layout differs from the program's:\n"
                         f"{ta}\nvs\n{tb}")
    for x, y in zip(a, b):
        if (tuple(x.shape), jnp.dtype(x.dtype)) != (tuple(y.shape),
                                                   jnp.dtype(y.dtype)):
            raise ValueError(f"weight leaf differs: {x} vs {y}")
