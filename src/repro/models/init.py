"""Parameter initialization with logical sharding annotations.

Every parameter leaf is created through ``ParamBuilder`` as a ``Param``
(array + tuple of *logical axis names*, one per dimension).
``split_tree`` separates a pytree of Params into (params, specs);
``repro.sharding.rules`` then maps logical names to mesh axes to produce
pjit in_shardings.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

Logical = Tuple[Optional[str], ...]


class Param(NamedTuple):
    array: jnp.ndarray
    logical: Logical


def _is_param(x) -> bool:
    return isinstance(x, Param)


class ParamBuilder:
    """Creates Param leaves with fresh PRNG splits.

    ``abstract=True`` builds ShapeDtypeStructs instead of arrays — the
    dry-run path, which must describe 480B-parameter models without
    allocating them.
    """

    def __init__(self, key: jax.Array, dtype=jnp.bfloat16, abstract: bool = False):
        self._key = key
        self.dtype = dtype
        self.abstract = abstract

    def _next(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def dense(self, shape: Sequence[int], logical: Logical, scale: float | None = None) -> Param:
        """Truncated-normal fan-in init, drawn in the storage dtype (an
        f32 draw of a 151k-row embedding would hold 3.1 GB beside it)."""
        if self.abstract:
            return Param(jax.ShapeDtypeStruct(tuple(shape), self.dtype), tuple(logical))
        if scale is None:
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
            scale = fan_in ** -0.5
        w = jax.random.truncated_normal(self._next(), -2, 2, tuple(shape), self.dtype)
        return Param(w * jnp.asarray(scale, self.dtype), tuple(logical))

    def zeros(self, shape: Sequence[int], logical: Logical, dtype=None) -> Param:
        if self.abstract:
            return Param(jax.ShapeDtypeStruct(tuple(shape), dtype or self.dtype), tuple(logical))
        return Param(jnp.zeros(tuple(shape), dtype or self.dtype), tuple(logical))

    def ones(self, shape: Sequence[int], logical: Logical, dtype=None) -> Param:
        if self.abstract:
            return Param(jax.ShapeDtypeStruct(tuple(shape), dtype or jnp.float32), tuple(logical))
        return Param(jnp.ones(tuple(shape), dtype or jnp.float32), tuple(logical))

    def value(self, arr: jnp.ndarray, logical: Logical) -> Param:
        if self.abstract:
            return Param(jax.ShapeDtypeStruct(arr.shape, arr.dtype), tuple(logical))
        return Param(arr, tuple(logical))

    def stacked(self, n: int, make: Callable[["ParamBuilder"], Any]) -> Any:
        """``n`` layers of ``make(builder)`` stacked on a new leading
        'layers' axis.  The layers are drawn one at a time under
        ``lax.map``, straight into the stacked buffers: the program holds
        one layer's ops whatever the depth, and no per-layer copy."""
        tmpl = make(ParamBuilder(self._key, self.dtype, abstract=True))
        if self.abstract:
            arrays = jax.tree_util.tree_map(
                lambda p: jax.ShapeDtypeStruct((n,) + p.array.shape, p.array.dtype),
                tmpl, is_leaf=_is_param)
        else:
            arrays = jax.lax.map(
                lambda k: split_tree(make(ParamBuilder(k, self.dtype)))[0],
                jax.random.split(self._next(), n))
        return jax.tree_util.tree_map(
            lambda p, a: Param(a, (None,) + p.logical), tmpl, arrays,
            is_leaf=_is_param)


def split_tree(tree: Any) -> Tuple[Any, Any]:
    """Separate a pytree of Params into (params, specs)."""
    params = jax.tree_util.tree_map(lambda p: p.array, tree, is_leaf=_is_param)
    specs = jax.tree_util.tree_map(lambda p: p.logical, tree, is_leaf=_is_param)
    return params, specs
