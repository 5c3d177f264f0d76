"""Device time of the search program by stage.

The program traces each stage of its search under a name scope
``hi2.<stage>`` (DESIGN.md §9: dispatch, gather, dedup, filter, score,
topk, refine, sparse, fuse).  The scope lands in the ``op_name``
metadata of the compiled program, which a trace read with
``jax.profiler`` does not carry; the trace names each operation by its
HLO instruction.  So the stage of each instruction is read from the
compiled search program of the cell: ``hybrid_index.search`` lowered
at the shapes and static arguments ``Server.query`` serves the cell
with, and compiled after the window; its instructions bear the names
of the program that ran (``bench/tests/test_bench_scopes.py``
compares the two).  An instruction whose ``op_name`` holds no ``hi2.``
scope (a copy or layout change XLA added, a program without the
scopes) is :data:`UNSCOPED`.
"""
from __future__ import annotations

import functools
import json
import re
from types import SimpleNamespace
from typing import Optional

from bench import trace_reduce

UNSCOPED = "unscoped"
#: the search program's modules in a trace
MODULE = "jit_search"
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_STAGE = re.compile(r"(?:^|/)hi2\.(\w+)")


def stage_map(hlo_text: str) -> dict:
    """Instruction name -> its ``hi2.`` stage, or :data:`UNSCOPED`, for
    every instruction of a compiled program's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        op = _OP_NAME.search(line)
        stages = set(_STAGE.findall(op.group(1))) if op else set()
        out[m.group(1)] = stages.pop() if len(stages) == 1 else UNSCOPED
    return out


def search_program_text(cfg: dict) -> str:
    """The compiled text of the cell's search program, lowered from the
    served shapes alone: the index as the dry run describes it
    (``launch/cells.py``), every call padded to ``max_batch``."""
    import jax
    import jax.numpy as jnp

    from repro.core import hybrid_index as hi
    from repro.launch import cells

    index = cells._hi2_abstract_index(SimpleNamespace(**cfg))
    b = cfg["max_batch"]
    qe = jax.ShapeDtypeStruct((b, cfg["hidden"]), jnp.float32)
    qt = jax.ShapeDtypeStruct((b, cfg["query_len"]), jnp.int32)
    return hi.search.lower(
        index, qe, qt, kc=cfg["kc"], k2=cfg["k2"], top_r=cfg["top_r"],
        use_kernel=cfg["use_kernel"]).compile().as_text()


@functools.lru_cache(maxsize=4)
def _cell_stage_map(cfg_json: str) -> dict:
    return stage_map(search_program_text(json.loads(cfg_json)))


def cell_stage_map(cfg: dict) -> dict:
    """:func:`stage_map` of the cell's search program (compiled once per
    configuration in a process)."""
    return _cell_stage_map(json.dumps(cfg, sort_keys=True))


def stage_seconds(red: dict, smap: dict) -> Optional[dict]:
    """Device seconds of the traced window's operations by stage, over
    the operations of the program ``smap`` describes; None where the
    program has no stage scopes."""
    if all(s == UNSCOPED for s in smap.values()):
        return None
    out: dict = {}
    for name, sec in red["ops"].items():
        stage = smap.get(name)
        if stage is not None:
            out[stage] = out.get(stage, 0.0) + sec
    return out


def stage_ms(ctx, stage: str) -> Optional[float]:
    """Device milliseconds of ``stage`` per execution of the search
    program in the traced window."""
    if ctx.trace is None:
        return None
    n, _ = trace_reduce.module_stats(ctx.trace, MODULE)
    if not n:
        return None
    by_stage = stage_seconds(ctx.trace, cell_stage_map(ctx.cfg))
    if by_stage is None:
        return None
    return 1e3 * by_stage.get(stage, 0.0) / n
