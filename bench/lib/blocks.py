"""Language-model blocks, one module each, found by name.

A configuration names its block in ``lm.block``; the module is
``bench/lm/<block>.py``.  A block module provides everything of the
benchmark that depends on the language model's architecture:

  * ``model_cfg(conf, vit_cfg)``: the program's ``ModelCfg`` for the
    configuration file (checked against the program's registry where the
    file names an ``arch``);
  * ``layout(lm)``: the LM's weight leaves (``weights.Leaf``), in the
    program's parameter layout;
  * ``Reference(lm, params, total, rnd)``: the LM half of the plain
    reference (``reference.Reference`` holds the rest): ``embed(ids)``,
    ``cache()``, ``slide(cache, shift, overlap, vis_len)``,
    ``window(embeds, idx, cache, kvmask) -> (final hidden state,
    cache)`` and ``head(hidden) -> logits``;
  * ``work(q, pairs, n_valid, w, lm)``: the LM's part of one window's
    work counts (``flops.window_work``): at least ``lm``, ``head`` and
    ``decode`` (``device.mfu`` sums them), and the counts that the
    roofline reader of each kernel in ``KERNELS`` reads, such as
    ``refresh_attn_flops`` and ``refresh_attn_bytes`` for
    ``flash_refresh_paged``.  A block that runs no such kernel leaves
    its counts out: the kernel's trace time is then nought and its
    reader reports nothing;
  * ``KERNELS``: the LM kernels whose device time the trace sums.

Besides the program's ``ModelCfg`` in ``model_cfg``, a block builds on
the benchmark's public parts alone: ``bench/lib/ops.py`` (the reference's arithmetic: ``mm``,
``einsum``, ``rmsnorm``, ``swiglu``, ``Frozen``, ``F32``),
``bench/lib/weights.py`` (``Leaf``, ``dense``, ``BF16``) and
``bench/lib/flops.py`` (the ledger's counts).  None of these imports a
block, so a block loads after them.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path
from types import ModuleType
from typing import Any, Dict

BENCH = Path(__file__).resolve().parents[1]


def load_module(path: Path, prefix: str) -> ModuleType:
    """The Python file ``path``, loaded as a module of its own."""
    name = prefix + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(lm: Dict[str, Any], bench_dir: Path = BENCH) -> ModuleType:
    """The block module that the configuration's ``lm.block`` names."""
    where = bench_dir / "lm"
    name = lm.get("block")
    if not name:
        raise ValueError(f"the configuration's lm names no block: set "
                         f"lm.block to the name of a module {where}/<block>.py")
    path = where / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"no LM block {name!r}: {path} does not exist")
    return load_module(path, "bench_lm_")
