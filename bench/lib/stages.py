"""Device time by serving stage: one table from a jitted program's module
name (``jit_<function>``, what a device trace's ``XLA Modules`` line
carries) to the stage it serves.  A module not in the table (JAX's own
eager operations between the programs, ``?`` for an operation outside
any module) is ``other``."""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

STAGES = ("codec", "vit", "prefill", "decode", "other")

MODULES = {
    # codec front end (at submit)
    "jit_encode_stream": "codec",
    "jit_decode_stream": "codec",
    # ViT encode: motion mask, token selection, full and pruned towers
    "jit_motion_mask": "vit",
    "jit_select_tokens": "vit",
    "jit_vit_full": "vit",
    "jit_vit_pruned": "vit",
    "jit_encode_packed_tokens": "vit",
    # KV reuse (rope_shift), cold-page demotion, selective refresh and
    # fresh prefill
    "jit_lm_reuse": "prefill",
    "jit_lm_reuse_paged": "prefill",
    "jit_kv_demote": "prefill",
    "jit_lm_selective": "prefill",
    "jit_lm_selective_paged": "prefill",
    "jit_lm_fresh_prefill": "prefill",
    "jit_lm_fresh_prefill_paged": "prefill",
    "jit_lm_stream_prefill": "prefill",
    # the decode step after the answer
    "jit_lm_decode": "decode",
    "jit_lm_decode_paged": "decode",
}


def stage(op_name: str) -> str:
    """Stage of a trace operation named ``<module>/<instruction>``."""
    return MODULES.get(op_name.split("/", 1)[0], "other")


def split(op_s: Dict[str, float]) -> Dict[str, float]:
    """Seconds per stage from seconds per operation
    (``trace.Summary.op_s``); every stage is present."""
    out = {s: 0.0 for s in STAGES}
    for name, sec in op_s.items():
        out[stage(name)] += sec
    return out


def top_modules(op_s: Dict[str, float], which: str = "other",
                n: int = 5) -> Iterable[Tuple[str, float]]:
    """The ``n`` modules of one stage with the most device seconds."""
    by: Dict[str, float] = {}
    for name, sec in op_s.items():
        if stage(name) == which:
            mod = name.split("/", 1)[0]
            by[mod] = by.get(mod, 0.0) + sec
    return sorted(by.items(), key=lambda kv: -kv[1])[:n]
