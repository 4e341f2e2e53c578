"""Declarative kernel contracts — single source of truth for dispatch.

Every public op in ``ops.py`` is described by one :class:`KernelContract`
record: which Pallas kernel it dispatches to, which ``ref.py`` oracle it
must match, and two tiers of machine-checkable rules.

  * **Preconditions** are hard requirements of *both* execution paths
    (rank/shape consistency, dtype admissibility, GQA head divisibility).
    A violated precondition raises :class:`KernelContractError` — neither
    the kernel nor the oracle can produce a meaningful answer.
  * **Eligibility rules** decide whether the Pallas kernel may run for a
    given geometry (tile alignment, visit-list shape bounds, map/mask
    agreement).  A failed eligibility rule routes to the oracle — a
    *silent fallback*, counted by ``ops.dispatch_counts()`` and audited
    statically by ``tools/check``.

The rules operate on flat "facts" dicts built by the ``*_facts``
helpers from anything carrying ``.shape``/``.dtype`` (concrete arrays,
tracers, or ``jax.ShapeDtypeStruct``), so the same predicates drive the
runtime guards in ``ops.py`` and the abstract-eval dispatch auditor in
``tools/check/dispatch_audit.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Optional, Tuple

import jax.numpy as jnp

from .flash_refresh import paged_group_fits_vmem

# Dtypes the Pallas kernels (and their oracles) accept for tensor
# operands.  f32 is the accumulator dtype everywhere; bf16/f16 are the
# storage dtypes the serving path feeds.
ADMISSIBLE_FLOAT = frozenset({"float32", "bfloat16", "float16"})

OK = "ok"


class KernelContractError(ValueError):
    """A kernel-op precondition was violated (both paths would be wrong)."""


@dataclasses.dataclass(frozen=True)
class Rule:
    """One machine-checkable clause of a contract."""

    code: str
    description: str
    predicate: Callable[[Mapping[str, Any]], bool]

    def holds(self, facts: Mapping[str, Any]) -> bool:
        return bool(self.predicate(facts))


@dataclasses.dataclass(frozen=True)
class DispatchDecision:
    """Outcome of the eligibility check for one call geometry."""

    use_kernel: bool
    reason: str  # ``OK`` or the code of the first failed rule

    def __bool__(self) -> bool:  # pragma: no cover - convenience
        return self.use_kernel


@dataclasses.dataclass(frozen=True)
class KernelContract:
    """Declarative record for one public kernel op."""

    name: str
    kernel: str  # dotted symbol of the Pallas entry point
    oracle: str  # dotted symbol of the jnp oracle it must match
    description: str
    preconditions: Tuple[Rule, ...]
    eligibility: Tuple[Rule, ...]
    tile: Optional[Tuple[int, int]] = None  # canonical (tq, tk) quantum
    visit_list: Optional[str] = None  # scalar-prefetch operand bounds
    compile_key: str = ""  # prose: what keys a fresh XLA compile
    # Max distinct compile-cache keys this op may produce across the
    # recompile-audit scenario suite (``tools/check/recompile_audit.py``).
    # ``None`` — not budgeted (op is not on a bucketed hot path).
    recompile_budget: Optional[int] = None

    def validate(self, facts: Mapping[str, Any]) -> None:
        for rule in self.preconditions:
            if not rule.holds(facts):
                raise KernelContractError(
                    f"{self.name}: precondition '{rule.code}' violated "
                    f"({rule.description}); facts={_public_facts(facts)}"
                )

    def decide(self, facts: Mapping[str, Any]) -> DispatchDecision:
        """First failed eligibility rule wins (mirrors an ``and`` chain);
        rules may therefore assume every earlier rule held."""
        for rule in self.eligibility:
            if not rule.holds(facts):
                return DispatchDecision(False, rule.code)
        return DispatchDecision(True, OK)


def _public_facts(facts: Mapping[str, Any]) -> dict:
    return {k: v for k, v in facts.items() if not callable(v)}


# ----------------------------------------------------------------------
# facts builders (shape/dtype only — safe on tracers and ShapeDtypeStruct)
# ----------------------------------------------------------------------
def _dt(x: Any) -> str:
    return jnp.dtype(x.dtype).name


def _kind(name: str) -> str:
    return jnp.dtype(name).kind


def mv_sad_facts(cur, prev, *, block: int, radius: int) -> dict:
    return {
        "cur_shape": tuple(cur.shape),
        "prev_shape": tuple(prev.shape),
        "cur_dtype": _dt(cur),
        "prev_dtype": _dt(prev),
        "block": int(block),
        "radius": int(radius),
    }


def rope_shift_facts(k, delta) -> dict:
    return {
        "k_shape": tuple(k.shape),
        "delta_shape": tuple(delta.shape),
        "k_dtype": _dt(k),
        "delta_dtype": _dt(delta),
    }


def flash_prefill_facts(q, k, v, *, causal: bool, window, q_offset: int) -> dict:
    return {
        "q_shape": tuple(q.shape),
        "k_shape": tuple(k.shape),
        "v_shape": tuple(v.shape),
        "q_dtype": _dt(q),
        "k_dtype": _dt(k),
        "v_dtype": _dt(v),
        "causal": bool(causal),
        "window": window,
        "q_offset": int(q_offset),
    }


def flash_refresh_facts(
    q, k, v, q_pos, kv_valid, *, causal: bool, window, block_map,
    positions_match: Callable[[], bool] = lambda: True,
) -> dict:
    """``positions_match`` is deferred: it may force a device sync
    (``np.asarray`` of the caller's positions), so the eligibility chain
    only evaluates it after every structural rule has held — exactly the
    short-circuit order of the historical ``and`` guard in ``ops.py``."""
    facts = {
        "q_shape": tuple(q.shape),
        "k_shape": tuple(k.shape),
        "v_shape": tuple(v.shape),
        "q_pos_shape": tuple(q_pos.shape),
        "q_dtype": _dt(q),
        "k_dtype": _dt(k),
        "v_dtype": _dt(v),
        "q_pos_dtype": _dt(q_pos),
        "kv_valid_shape": None if kv_valid is None else tuple(kv_valid.shape),
        "kv_valid_dtype": None if kv_valid is None else _dt(kv_valid),
        "causal": bool(causal),
        "window": window,
        "has_map": block_map is not None,
        "positions_match": positions_match,
    }
    if block_map is not None:
        facts.update(
            map_n_q=block_map.n_q,
            map_kv_len=block_map.kv_len,
            map_tq=block_map.tq,
            map_tk=block_map.tk,
            map_causal=block_map.causal,
            map_window=block_map.window,
        )
    return facts


def _cold_facts(cold, *, page: int) -> dict:
    """Facts for the optional int8 cold-page operand group.

    ``cold`` is None (single-precision slab) or a
    ``(k8, v8, k_scale, v_scale)`` tuple: (Pc_phys, Hkv, D) int8 slabs
    plus (n_cold, Hkv) f32 per-page-per-head dequant scales.
    """
    if cold is None:
        return {"has_cold": False}
    k8, v8, k_scale, v_scale = cold
    return {
        "has_cold": True,
        "cold_k_shape": tuple(k8.shape),
        "cold_v_shape": tuple(v8.shape),
        "cold_k_dtype": _dt(k8),
        "cold_v_dtype": _dt(v8),
        "k_scale_shape": tuple(k_scale.shape),
        "v_scale_shape": tuple(v_scale.shape),
        "k_scale_dtype": _dt(k_scale),
        "v_scale_dtype": _dt(v_scale),
    }


def flash_refresh_paged_facts(
    q, k, v, q_pos, kv_valid, page_table, *, page: int, causal: bool,
    window, block_map,
    positions_match: Callable[[], bool] = lambda: True,
    cold=None,
) -> dict:
    """Facts for the paged refresh op.  ``k``/``v`` are the batchless
    (P_phys, Hkv, D) slab; the logical KV length is derived from the
    page table (n_pages * page), which is what the block map and the
    ``kv_valid`` mask are expressed in."""
    pt_shape = tuple(page_table.shape)
    facts = {
        "q_shape": tuple(q.shape),
        "k_shape": tuple(k.shape),
        "v_shape": tuple(v.shape),
        "q_pos_shape": tuple(q_pos.shape),
        "pt_shape": pt_shape,
        "q_dtype": _dt(q),
        "k_dtype": _dt(k),
        "v_dtype": _dt(v),
        "q_pos_dtype": _dt(q_pos),
        "pt_dtype": _dt(page_table),
        "kv_valid_shape": None if kv_valid is None else tuple(kv_valid.shape),
        "kv_valid_dtype": None if kv_valid is None else _dt(kv_valid),
        "page": int(page),
        "logical_len": (
            pt_shape[1] * int(page) if len(pt_shape) == 2 else -1
        ),
        "causal": bool(causal),
        "window": window,
        "has_map": block_map is not None,
        "positions_match": positions_match,
    }
    facts.update(_cold_facts(cold, page=page))
    if block_map is not None:
        facts.update(
            map_n_q=block_map.n_q,
            map_kv_len=block_map.kv_len,
            map_tq=block_map.tq,
            map_tk=block_map.tk,
            map_causal=block_map.causal,
            map_window=block_map.window,
        )
    return facts


def flash_prefill_paged_facts(
    q, k, v, page_table, *, page: int, causal: bool, window, q_offset: int,
    cold=None,
) -> dict:
    pt_shape = tuple(page_table.shape)
    facts = {
        "q_shape": tuple(q.shape),
        "k_shape": tuple(k.shape),
        "v_shape": tuple(v.shape),
        "pt_shape": pt_shape,
        "q_dtype": _dt(q),
        "k_dtype": _dt(k),
        "v_dtype": _dt(v),
        "pt_dtype": _dt(page_table),
        "page": int(page),
        "logical_len": (
            pt_shape[1] * int(page) if len(pt_shape) == 2 else -1
        ),
        "causal": bool(causal),
        "window": window,
        "q_offset": int(q_offset),
    }
    facts.update(_cold_facts(cold, page=page))
    return facts


def flash_packed_facts(
    q, k, v, seg_id, tile_ids, tile_count, *, tq: int, tk: int
) -> dict:
    return {
        "q_shape": tuple(q.shape),
        "k_shape": tuple(k.shape),
        "v_shape": tuple(v.shape),
        "seg_shape": tuple(seg_id.shape),
        "q_dtype": _dt(q),
        "k_dtype": _dt(k),
        "v_dtype": _dt(v),
        "seg_dtype": _dt(seg_id),
        "has_map": tile_ids is not None and tile_count is not None,
        "tile_ids_shape": None if tile_ids is None else tuple(tile_ids.shape),
        "tile_count_shape": (
            None if tile_count is None else tuple(tile_count.shape)
        ),
        "tq": int(tq),
        "tk": int(tk),
    }


def ssd_scan_facts(x, log_a, b, c, *, chunk: int) -> dict:
    return {
        "x_shape": tuple(x.shape),
        "log_a_shape": tuple(log_a.shape),
        "b_shape": tuple(b.shape),
        "c_shape": tuple(c.shape),
        "x_dtype": _dt(x),
        "log_a_dtype": _dt(log_a),
        "b_dtype": _dt(b),
        "c_dtype": _dt(c),
        "chunk": int(chunk),
    }


# ----------------------------------------------------------------------
# the registry
# ----------------------------------------------------------------------
def _attn_dtype_ok(f: Mapping[str, Any]) -> bool:
    return (
        f["q_dtype"] in ADMISSIBLE_FLOAT
        and f["k_dtype"] in ADMISSIBLE_FLOAT
        and f["k_dtype"] == f["v_dtype"]
    )


# Rules for the optional int8 cold-page operand group on the paged ops.
# Every clause is vacuous when no cold group is supplied, so the plain
# single-precision slab keeps its exact pre-quantization contract.
_COLD_PRECONDITIONS = (
    Rule(
        "cold-kv-shape",
        "cold k8 and v8 are rank-3 slabs with identical shapes",
        lambda f: not f["has_cold"]
        or (
            len(f["cold_k_shape"]) == 3
            and f["cold_k_shape"] == f["cold_v_shape"]
        ),
    ),
    Rule(
        "cold-align",
        "cold slab row count divides by the page size",
        lambda f: not f["has_cold"]
        or f["cold_k_shape"][0] % f["page"] == 0,
    ),
    Rule(
        "cold-head",
        "cold slab matches the hot slab's (Hkv, D) trailing dims",
        lambda f: not f["has_cold"]
        or f["cold_k_shape"][1:] == f["k_shape"][1:],
    ),
    Rule(
        "scale-shape",
        "k/v scales are (n_cold, Hkv) per-page-per-head",
        lambda f: not f["has_cold"]
        or (
            f["k_scale_shape"]
            == (f["cold_k_shape"][0] // f["page"], f["cold_k_shape"][1])
            and f["k_scale_shape"] == f["v_scale_shape"]
        ),
    ),
)

_COLD_ELIGIBILITY = (
    Rule(
        "cold-dtype",
        "fused dequant kernel requires int8 cold pages",
        lambda f: not f["has_cold"]
        or (f["cold_k_dtype"] == "int8" and f["cold_v_dtype"] == "int8"),
    ),
    Rule(
        "scale-f32",
        "fused dequant kernel requires f32 scales (the oracle casts)",
        lambda f: not f["has_cold"]
        or (
            f["k_scale_dtype"] == "float32"
            and f["v_scale_dtype"] == "float32"
        ),
    ),
)


MV_SAD = KernelContract(
    name="mv_sad",
    kernel="repro.kernels.mv_sad.mv_sad_pallas",
    oracle="repro.kernels.ref.mv_sad_ref",
    description="Full-search block-matching motion estimation over luma.",
    preconditions=(
        Rule(
            "rank",
            "cur and prev are 2-D (H, W) luma planes",
            lambda f: len(f["cur_shape"]) == 2 and len(f["prev_shape"]) == 2,
        ),
        Rule(
            "shape-match",
            "cur and prev have identical shapes",
            lambda f: f["cur_shape"] == f["prev_shape"],
        ),
        Rule(
            "block-divisibility",
            "H and W are multiples of the macroblock edge",
            lambda f: f["cur_shape"][0] % f["block"] == 0
            and f["cur_shape"][1] % f["block"] == 0,
        ),
        Rule(
            "dtype",
            "frames are real numeric (float or integer)",
            lambda f: _kind(f["cur_dtype"]) in "fiu"
            and _kind(f["prev_dtype"]) in "fiu",
        ),
        Rule("radius", "search radius >= 1", lambda f: f["radius"] >= 1),
    ),
    eligibility=(),  # the kernel grid is the macroblock grid; no fallback
    tile=None,
    compile_key="(H, W, block, radius, dtype) — one frame geometry per stream",
)

ROPE_SHIFT = KernelContract(
    name="rope_shift",
    kernel="repro.kernels.rope_shift.rope_shift_pallas",
    oracle="repro.kernels.ref.rope_shift_ref",
    description="RoPE position correction of cached keys (paper Eq. 5).",
    preconditions=(
        Rule(
            "rank",
            "k is (B, S, n_kv, d_h) and delta is (B, S)",
            lambda f: len(f["k_shape"]) == 4 and len(f["delta_shape"]) == 2,
        ),
        Rule(
            "delta-shape",
            "delta matches k's (B, S) prefix",
            lambda f: f["delta_shape"] == f["k_shape"][:2],
        ),
        Rule(
            "delta-dtype",
            "delta is an integer position shift",
            lambda f: _kind(f["delta_dtype"]) in "iu",
        ),
        Rule(
            "k-dtype",
            "k is f32/bf16/f16",
            lambda f: f["k_dtype"] in ADMISSIBLE_FLOAT,
        ),
        Rule(
            "even-head",
            "head dim is even (rotate-half RoPE)",
            lambda f: f["k_shape"][3] % 2 == 0,
        ),
    ),
    eligibility=(
        Rule(
            "seq-tile",
            "S divides by the sequence tile min(128, S)",
            lambda f: f["k_shape"][1] % min(128, f["k_shape"][1]) == 0,
        ),
    ),
    tile=(128, 128),
    compile_key="(B, S, n_kv, d_h, dtype) — one per overlap-slab geometry",
)

FLASH_PREFILL = KernelContract(
    name="flash_prefill",
    kernel="repro.kernels.flash_prefill.flash_prefill_pallas",
    oracle="repro.kernels.ref.flash_prefill_ref",
    description="Blockwise causal GQA attention over a contiguous window.",
    preconditions=(
        Rule(
            "rank",
            "q/k/v are rank-4 (B, S, H, D)",
            lambda f: len(f["q_shape"]) == 4
            and len(f["k_shape"]) == 4
            and len(f["v_shape"]) == 4,
        ),
        Rule(
            "kv-shape",
            "k and v have identical shapes",
            lambda f: f["k_shape"] == f["v_shape"],
        ),
        Rule(
            "batch",
            "q and k share the batch dim",
            lambda f: f["q_shape"][0] == f["k_shape"][0],
        ),
        Rule(
            "head-dim",
            "q and k share the head dim",
            lambda f: f["q_shape"][3] == f["k_shape"][3],
        ),
        Rule(
            "gqa",
            "query heads divide evenly over kv heads",
            lambda f: f["q_shape"][2] % f["k_shape"][2] == 0,
        ),
        Rule("dtype", "q/k/v are f32/bf16/f16 with k == v", _attn_dtype_ok),
        Rule(
            "window",
            "sliding window is None or >= 1",
            lambda f: f["window"] is None or f["window"] >= 1,
        ),
    ),
    eligibility=(
        Rule("q-tile", "Sq divides by Tq=128", lambda f: f["q_shape"][1] % 128 == 0),
        Rule("k-tile", "Sk divides by Tk=128", lambda f: f["k_shape"][1] % 128 == 0),
    ),
    tile=(128, 128),
    compile_key="(B, Sq, Sk, H, Hkv, D, dtype, causal, window, q_offset)",
)

FLASH_REFRESH = KernelContract(
    name="flash_refresh",
    kernel="repro.kernels.flash_refresh.flash_refresh_pallas",
    oracle="repro.kernels.ref.flash_refresh_ref",
    description=(
        "Block-sparse masked attention over gathered query positions "
        "(selective KVC refresh)."
    ),
    preconditions=(
        Rule(
            "rank",
            "q/k/v rank-4, q_pos rank-2",
            lambda f: len(f["q_shape"]) == 4
            and len(f["k_shape"]) == 4
            and len(f["v_shape"]) == 4
            and len(f["q_pos_shape"]) == 2,
        ),
        Rule(
            "kv-shape",
            "k and v have identical shapes",
            lambda f: f["k_shape"] == f["v_shape"],
        ),
        Rule(
            "q-pos-shape",
            "q_pos is (B, Sq)",
            lambda f: f["q_pos_shape"]
            == (f["q_shape"][0], f["q_shape"][1]),
        ),
        Rule(
            "batch",
            "q and k share the batch dim",
            lambda f: f["q_shape"][0] == f["k_shape"][0],
        ),
        Rule(
            "head-dim",
            "q and k share the head dim",
            lambda f: f["q_shape"][3] == f["k_shape"][3],
        ),
        Rule(
            "gqa",
            "query heads divide evenly over kv heads",
            lambda f: f["q_shape"][2] % f["k_shape"][2] == 0,
        ),
        Rule("dtype", "q/k/v are f32/bf16/f16 with k == v", _attn_dtype_ok),
        Rule(
            "q-pos-dtype",
            "q_pos is integer token positions",
            lambda f: _kind(f["q_pos_dtype"]) in "iu",
        ),
        Rule(
            "kv-valid",
            "kv_valid is None or a (B, Sk) bool mask",
            lambda f: f["kv_valid_shape"] is None
            or (
                f["kv_valid_shape"] == (f["k_shape"][0], f["k_shape"][1])
                and f["kv_valid_dtype"] == "bool"
            ),
        ),
    ),
    eligibility=(
        Rule("map-present", "a RefreshBlockMap was supplied", lambda f: f["has_map"]),
        Rule(
            "map-n-q",
            "map was built for this query count",
            lambda f: f["map_n_q"] == f["q_shape"][1],
        ),
        Rule(
            "map-kv-len",
            "map was built for this cache length",
            lambda f: f["map_kv_len"] == f["k_shape"][1],
        ),
        Rule(
            "k-tile",
            "cache length divides by the map's key tile",
            lambda f: f["k_shape"][1] % f["map_tk"] == 0,
        ),
        Rule(
            "map-causal",
            "map and call agree on causal masking",
            lambda f: f["map_causal"] == f["causal"],
        ),
        Rule(
            "map-window",
            "map and call agree on the sliding window",
            lambda f: f["map_window"] == f["window"],
        ),
        Rule(
            "positions",
            "concrete q_pos equals the map's positions (traced: trusted)",
            lambda f: f["positions_match"](),
        ),
    ),
    tile=(128, 128),
    visit_list=(
        "tile_ids (n_q_tiles, t_max) int32 + tile_count (n_q_tiles,) "
        "int32, scalar-prefetched; n_q_tiles = ceil(Sq/Tq) after padding "
        "Sq to a Tq multiple, t_max <= ceil(kv_len/Tk)"
    ),
    compile_key=(
        "(B, padded Sq, kv_len, H, Hkv, D, dtype, causal, window, tq, tk, "
        "t_max) — one per (WindowLayout, cache_slots, batch) triple; the "
        "per-layout map is lru-cached so steady-state windows reuse it"
    ),
    # one key per (layout, fleet-size) pair in the CI scenario suite:
    # 5 layouts x 4 fleet sizes; steady-state windows must add zero.
    recompile_budget=20,
)

FLASH_REFRESH_PAGED = KernelContract(
    name="flash_refresh_paged",
    kernel="repro.kernels.flash_refresh.flash_refresh_paged_pallas",
    oracle="repro.kernels.ref.flash_refresh_paged_ref",
    description=(
        "Paged block-sparse refresh attention: visit list -> page table "
        "-> physical kv tile in the shared slab (core/kv_pool.py)."
    ),
    preconditions=(
        Rule(
            "rank",
            "q rank-4, slab k/v rank-3, q_pos rank-2, page_table rank-2",
            lambda f: len(f["q_shape"]) == 4
            and len(f["k_shape"]) == 3
            and len(f["v_shape"]) == 3
            and len(f["q_pos_shape"]) == 2
            and len(f["pt_shape"]) == 2,
        ),
        Rule(
            "kv-shape",
            "k and v slabs have identical shapes",
            lambda f: f["k_shape"] == f["v_shape"],
        ),
        Rule(
            "q-pos-shape",
            "q_pos is (B, Sq)",
            lambda f: f["q_pos_shape"]
            == (f["q_shape"][0], f["q_shape"][1]),
        ),
        Rule(
            "pt-batch",
            "page_table leads with q's batch dim",
            lambda f: f["pt_shape"][0] == f["q_shape"][0],
        ),
        Rule(
            "head-dim",
            "q and the slab share the head dim",
            lambda f: f["q_shape"][3] == f["k_shape"][2],
        ),
        Rule(
            "gqa",
            "query heads divide evenly over kv heads",
            lambda f: f["q_shape"][2] % f["k_shape"][1] == 0,
        ),
        Rule("dtype", "q/k/v are f32/bf16/f16 with k == v", _attn_dtype_ok),
        Rule(
            "q-pos-dtype",
            "q_pos is integer token positions",
            lambda f: _kind(f["q_pos_dtype"]) in "iu",
        ),
        Rule(
            "pt-dtype",
            "page_table is integer page ids",
            lambda f: _kind(f["pt_dtype"]) in "iu",
        ),
        Rule(
            "slab-align",
            "slab row count divides by the page size",
            lambda f: f["page"] >= 1 and f["k_shape"][0] % f["page"] == 0,
        ),
        Rule(
            "kv-valid",
            "kv_valid is a (B, n_pages * page) bool mask over logical "
            "slots (mandatory: recycled pages hold stale KV)",
            lambda f: f["kv_valid_shape"]
            == (f["q_shape"][0], f["logical_len"])
            and f["kv_valid_dtype"] == "bool",
        ),
    ) + _COLD_PRECONDITIONS,
    eligibility=(
        Rule("map-present", "a RefreshBlockMap was supplied", lambda f: f["has_map"]),
        Rule(
            "map-n-q",
            "map was built for this query count",
            lambda f: f["map_n_q"] == f["q_shape"][1],
        ),
        Rule(
            "map-kv-len",
            "map was built for the logical stream length",
            lambda f: f["map_kv_len"] == f["logical_len"],
        ),
        Rule(
            "page-tile",
            "the map's key tile equals the page size (one visit-list "
            "entry == one slab page)",
            lambda f: f["map_tk"] == f["page"],
        ),
        Rule(
            "map-causal",
            "map and call agree on causal masking",
            lambda f: f["map_causal"] == f["causal"],
        ),
        Rule(
            "map-window",
            "map and call agree on the sliding window",
            lambda f: f["map_window"] == f["window"],
        ),
        Rule(
            "group-vmem",
            "one grouped step (the g query heads of a kv head against a "
            "page) fits the scoped VMEM; the int8 twin steps per head",
            lambda f: f["has_cold"] or paged_group_fits_vmem(
                f["q_shape"][2] // f["k_shape"][1], f["q_shape"][3],
                f["q_dtype"], f["k_dtype"], f["map_tq"], f["page"]),
        ),
        Rule(
            "positions",
            "concrete q_pos equals the map's positions (traced: trusted)",
            lambda f: f["positions_match"](),
        ),
    ) + _COLD_ELIGIBILITY,
    tile=(128, 128),
    visit_list=(
        "tile_ids (n_q_tiles, t_max) + tile_count (n_q_tiles,) int32 in "
        "logical tile coordinates, plus page_table (B, n_pages) int32 — "
        "all scalar-prefetched (with (n_cold, Hkv) f32 k/v scales when a "
        "cold group rides along); the BlockSpec index map composes them: "
        "kv tile = pt[b, tile_ids[iq, it]]"
    ),
    compile_key=(
        "(B, padded Sq, n_pages, P_phys, H, Hkv, D, dtype, causal, "
        "window, tq, page, t_max) — the slab shape is pool-static and "
        "the per-layout map is lru-cached, so stream churn adds no keys"
    ),
    # same layouts x fleet sizes as flash_refresh: page tables are
    # dynamic values, so paging must add zero compile keys
    recompile_budget=20,
)

FLASH_PREFILL_PAGED = KernelContract(
    name="flash_prefill_paged",
    kernel="repro.kernels.flash_prefill.flash_prefill_paged_pallas",
    oracle="repro.kernels.ref.flash_prefill_paged_ref",
    description=(
        "Paged causal GQA attention: contiguous logical window, kv "
        "tiles DMA'd from the shared slab through the page table."
    ),
    preconditions=(
        Rule(
            "rank",
            "q rank-4, slab k/v rank-3, page_table rank-2",
            lambda f: len(f["q_shape"]) == 4
            and len(f["k_shape"]) == 3
            and len(f["v_shape"]) == 3
            and len(f["pt_shape"]) == 2,
        ),
        Rule(
            "kv-shape",
            "k and v slabs have identical shapes",
            lambda f: f["k_shape"] == f["v_shape"],
        ),
        Rule(
            "pt-batch",
            "page_table leads with q's batch dim",
            lambda f: f["pt_shape"][0] == f["q_shape"][0],
        ),
        Rule(
            "head-dim",
            "q and the slab share the head dim",
            lambda f: f["q_shape"][3] == f["k_shape"][2],
        ),
        Rule(
            "gqa",
            "query heads divide evenly over kv heads",
            lambda f: f["q_shape"][2] % f["k_shape"][1] == 0,
        ),
        Rule("dtype", "q/k/v are f32/bf16/f16 with k == v", _attn_dtype_ok),
        Rule(
            "pt-dtype",
            "page_table is integer page ids",
            lambda f: _kind(f["pt_dtype"]) in "iu",
        ),
        Rule(
            "slab-align",
            "slab row count divides by the page size",
            lambda f: f["page"] >= 1 and f["k_shape"][0] % f["page"] == 0,
        ),
        Rule(
            "causal",
            "causal masking is mandatory: it is what hides stale "
            "previous-tenant rows in recycled pages",
            lambda f: f["causal"],
        ),
        Rule(
            "window",
            "sliding window is None or >= 1",
            lambda f: f["window"] is None or f["window"] >= 1,
        ),
    ) + _COLD_PRECONDITIONS,
    eligibility=(
        Rule("q-tile", "Sq divides by Tq=128", lambda f: f["q_shape"][1] % 128 == 0),
        Rule(
            "page-tile",
            "page size equals the key tile Tk=128",
            lambda f: f["page"] == 128,
        ),
    ) + _COLD_ELIGIBILITY,
    tile=(128, 128),
    visit_list=(
        "page_table (B, n_pages) int32, scalar-prefetched; the key-axis "
        "grid runs over logical pages and the index map reads pt[b, ik]"
    ),
    compile_key=(
        "(B, Sq, n_pages, P_phys, H, Hkv, D, dtype, window, q_offset) — "
        "pool-static slab shape; page tables are dynamic values"
    ),
)

FLASH_PACKED = KernelContract(
    name="flash_packed",
    kernel="repro.kernels.flash_packed.flash_packed_pallas",
    oracle="repro.kernels.ref.flash_packed_ref",
    description=(
        "Block-diagonal attention over packed ViT rows (segment mask)."
    ),
    preconditions=(
        Rule(
            "rank",
            "q/k/v rank-4, seg_id rank-2",
            lambda f: len(f["q_shape"]) == 4
            and len(f["k_shape"]) == 4
            and len(f["v_shape"]) == 4
            and len(f["seg_shape"]) == 2,
        ),
        Rule(
            "kv-shape",
            "k and v have identical shapes",
            lambda f: f["k_shape"] == f["v_shape"],
        ),
        Rule(
            "seg-shape",
            "seg_id is (R, L)",
            lambda f: f["seg_shape"] == (f["q_shape"][0], f["q_shape"][1]),
        ),
        Rule(
            "rows",
            "q and k share the packed-row dim",
            lambda f: f["q_shape"][0] == f["k_shape"][0],
        ),
        Rule(
            "gqa",
            "query heads divide evenly over kv heads",
            lambda f: f["q_shape"][2] % f["k_shape"][2] == 0,
        ),
        Rule("dtype", "q/k/v are f32/bf16/f16 with k == v", _attn_dtype_ok),
        Rule(
            "seg-dtype",
            "seg_id is integer (-1 marks padding)",
            lambda f: _kind(f["seg_dtype"]) in "iu",
        ),
        Rule(
            "tiles-positive",
            "tq and tk are >= 1",
            lambda f: f["tq"] >= 1 and f["tk"] >= 1,
        ),
    ),
    eligibility=(
        Rule(
            "map-present",
            "per-row tile_ids and tile_count were supplied",
            lambda f: f["has_map"],
        ),
        Rule("q-tile", "L divides by tq", lambda f: f["q_shape"][1] % f["tq"] == 0),
        Rule("k-tile", "L divides by tk", lambda f: f["q_shape"][1] % f["tk"] == 0),
        Rule(
            "tile-ids-shape",
            "tile_ids leads with (R, L/tq)",
            lambda f: f["tile_ids_shape"][:2]
            == (f["q_shape"][0], f["q_shape"][1] // f["tq"]),
        ),
        Rule(
            "tile-count-shape",
            "tile_count is exactly (R, L/tq)",
            lambda f: f["tile_count_shape"]
            == (f["q_shape"][0], f["q_shape"][1] // f["tq"]),
        ),
    ),
    tile=(128, 128),
    visit_list=(
        "tile_ids (R, L/tq, t_max) + tile_count (R, L/tq) int32 dynamic "
        "values (per-row visit lists from build_pack_map); t_max <= L/tk"
    ),
    compile_key=(
        "(R, L, H, Hkv, D, dtype, tq, tk, t_max) — R is quantized by "
        "PACK_ROW_QUANTUM, L by PACK_LEN_BUCKETS, t_max by power-of-two "
        "rounding in build_pack_map, so steady-state streams reuse keys"
    ),
    # rows-quantum x len-bucket x t_max combinations the bench scenario
    # suite may legitimately produce (audited by recompile_audit.py
    # against the bucket constants in core/pruning.py)
    recompile_budget=24,
)

SSD_SCAN = KernelContract(
    name="ssd_scan",
    kernel="repro.kernels.ssd_scan.ssd_scan_pallas",
    oracle="repro.kernels.ref.ssd_chunked_scan_grouped_ref",
    description="Chunked state-space-duality scan (recurrent families).",
    preconditions=(
        Rule(
            "rank",
            "x rank-4, log_a rank-3, b/c rank-4",
            lambda f: len(f["x_shape"]) == 4
            and len(f["log_a_shape"]) == 3
            and len(f["b_shape"]) == 4
            and len(f["c_shape"]) == 4,
        ),
        Rule(
            "bc-shape",
            "b and c have identical shapes",
            lambda f: f["b_shape"] == f["c_shape"],
        ),
        Rule(
            "log-a-shape",
            "log_a matches x's (B, L, H) prefix",
            lambda f: f["log_a_shape"] == f["x_shape"][:3],
        ),
        Rule(
            "batch-len",
            "b shares x's (B, L) prefix",
            lambda f: f["b_shape"][:2] == f["x_shape"][:2],
        ),
        Rule(
            "gqa",
            "state heads divide evenly over B/C groups",
            lambda f: f["x_shape"][2] % f["b_shape"][2] == 0,
        ),
        Rule(
            "dtype",
            "x/log_a/b/c are f32/bf16/f16 with b == c",
            lambda f: f["x_dtype"] in ADMISSIBLE_FLOAT
            and f["log_a_dtype"] in ADMISSIBLE_FLOAT
            and f["b_dtype"] in ADMISSIBLE_FLOAT
            and f["b_dtype"] == f["c_dtype"],
        ),
        Rule("chunk", "chunk size >= 1", lambda f: f["chunk"] >= 1),
    ),
    # ops.ssd_scan pads L to a chunk multiple with identity steps, so
    # every geometry is kernel-eligible once preconditions hold
    eligibility=(),
    tile=(128, 128),
    compile_key="(B, padded L, H, P, G, N, dtype, chunk)",
)

CONTRACTS: dict[str, KernelContract] = {
    c.name: c
    for c in (
        MV_SAD,
        ROPE_SHIFT,
        FLASH_PREFILL,
        FLASH_PREFILL_PAGED,
        FLASH_REFRESH,
        FLASH_REFRESH_PAGED,
        FLASH_PACKED,
        SSD_SCAN,
    )
}


def contract(name: str) -> KernelContract:
    return CONTRACTS[name]


def validate(name: str, facts: Mapping[str, Any]) -> None:
    CONTRACTS[name].validate(facts)


def decide(name: str, facts: Mapping[str, Any]) -> DispatchDecision:
    return CONTRACTS[name].decide(facts)
