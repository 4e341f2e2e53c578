"""Operation counts of served windows, for utilization and rooflines.

``vit_flops`` .. ``decode_flops`` are a copy of the program's ledger
(``repro/serving/flops.py``), kept here so that no later change to the
program can change the yardstick.  ``window_work`` departs from the
ledger where it counts work the serving path does not need:

  * ``prefill_flops`` counts the LM head at the last position only
    (``head_positions=1``); the ledger counts it at every query
    position, while serving reads one position's logits;
  * the projector maps to the LM width (the ledger's ``vit_flops``
    counts it square at the ViT width; the copy keeps that as is);
  * padding slots (pruned P-frame slots that hold no token) are not
    counted as LM work;
  * ``window_flops`` counts packed ViT attention per frame (kept
    patches attend within their own frame), where ``vit_flops`` over a
    window's patch total would count attention across frames.

``window_flops`` is what a window requires: the vision tower over the
patches it encoded, the LM over the positions it recomputed, the
answer's head row and one decode step.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np


# ---- copy of repro/serving/flops.py (matmul FLOPs, 2*m*n*k) ----------
def vit_flops(v: Dict[str, Any], n_patches: int) -> float:
    """Encode ``n_patches`` patches (+ projector on their groups)."""
    d, dff, g2 = v["d_model"], v["d_ff"], v["group"] ** 2
    per_tok_proj = 2 * (4 * d * d)                           # qkvo
    per_tok_ffn = 2 * (3 * d * dff)                          # swiglu 3-mat
    attn = 2 * 2 * n_patches * n_patches * d                 # logits + pv
    per_layer = n_patches * (per_tok_proj + per_tok_ffn) + attn
    proj = (n_patches // g2) * 2 * (g2 * d) * d              # as the ledger
    embed = n_patches * 2 * (v["patch"] ** 2) * d
    return float(v["n_layers"] * per_layer + proj + embed)


def layer_flops_per_token(lm: Dict[str, Any]) -> float:
    d, dh = lm["d_model"], lm["d_head"]
    f = 2 * d * (lm["n_heads"] + 2 * lm["n_kv"]) * dh        # qkv
    f += 2 * lm["n_heads"] * dh * d                          # out
    f += 2 * 3 * d * lm["d_ff"]                              # swiglu
    return float(f)


def attn_flops(lm: Dict[str, Any], pairs: float) -> float:
    """Score + value FLOPs of one attention layer over ``pairs``
    (query, key) pairs."""
    return 4.0 * pairs * lm["n_heads"] * lm["d_head"]


def prefill_flops(lm: Dict[str, Any], n_q: int, n_kv: int,
                  causal: bool = True, head_positions: int | None = None
                  ) -> float:
    """LM forward over n_q query tokens attending to n_kv cache slots
    (the ledger's form: every query attends every slot, halved when
    causal and n_q == n_kv)."""
    pairs = float(n_q) * n_kv
    if causal and n_q == n_kv:
        pairs *= 0.5
    f = lm["n_layers"] * (n_q * layer_flops_per_token(lm)
                          + attn_flops(lm, pairs))
    heads = n_q if head_positions is None else head_positions
    return f + heads * 2.0 * lm["d_model"] * lm["vocab"]


def decode_flops(lm: Dict[str, Any], n_kv: int) -> float:
    return prefill_flops(lm, 1, n_kv, causal=False)


# ---- what one served window requires ---------------------------------
def window_work(w: Dict[str, Any], lay: Dict[str, Any],
                lm: Dict[str, Any], v: Dict[str, Any]) -> Dict[str, float]:
    """FLOPs and bytes one answered window requires, by part.

    ``w``: the window's ``tokens_refreshed`` (the program's count) and,
    from the benchmark's own codec and token selection over the same
    frames, ``valid`` (the window's positions that hold a token, query
    included) and ``kept`` (patches the vision tower needs per frame it
    encodes: whole I-frames, the kept groups' patches of P-frames).
    ``lay``: window geometry (``total``, ``refresh``: the positions an
    incremental window recomputes) and the LM's ``block``, which counts
    the LM's part (``bench/lm/<block>.py``, ``work``).  Only positions
    that hold a token count: padding slots are work the static shapes
    add, not work the window needs.  Returns ``vit``, the block's
    ``lm``, ``head``, ``decode`` and its attention kernel's FLOPs and
    bytes (and any further counts of the block's), and the packed ViT
    attention kernel's FLOPs and bytes.
    """
    valid = np.asarray(w["valid"], bool)
    kept = np.asarray(w["kept"], np.float64)
    fresh = w["tokens_refreshed"] >= lay["total"]
    n_valid = int(valid.sum())
    before = np.cumsum(valid)                 # valid keys at or before p
    q = np.arange(lay["total"]) if fresh else lay["refresh"]
    q = q[valid[q]]
    pairs = float(before[q].sum())            # causal (query, key) pairs
    d, dff, g2 = v["d_model"], v["d_ff"], v["group"] ** 2
    per_tok = 2 * (4 * d * d) + 2 * (3 * d * dff)
    n_patch = float(kept.sum())
    vit = v["n_layers"] * (n_patch * per_tok + 4.0 * float((kept ** 2).sum()) * d)
    vit += n_patch * 2 * (v["patch"] ** 2) * d
    vit += n_patch / g2 * 2 * (g2 * d) * lm["d_model"]
    lm_work = lay["block"].work(q, pairs, n_valid, w, lm)
    # packed ViT attention: P-frames only (I-frames take the dense path)
    pk = kept[kept < lay["n_patches"]]
    pa_f = v["n_layers"] * 4.0 * float((pk ** 2).sum()) * d
    pa_b = v["n_layers"] * 2.0 * 4 * float(pk.sum()) * d
    return {"vit": vit, **lm_work,
            "packed_attn_flops": pa_f, "packed_attn_bytes": pa_b}


def window_flops(w, lay, lm, v) -> float:
    k = window_work(w, lay, lm, v)
    return k["vit"] + k["lm"] + k["head"] + k["decode"]
