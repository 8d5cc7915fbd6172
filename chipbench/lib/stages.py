"""The bound's device time split by stage: SGS, timing sweep, table
build, objectives and search, and by phase.

The program names its stages with ``jax.named_scope``
(``repro/obs/scopes.py``).  The names reach every HLO instruction's
``op_name`` metadata, but not the profiler trace: an op event there
carries only its instruction's name.  So the map from op to stage comes
from the compiled program's text, whose instruction names are the
trace's.

- An instruction's stage is the innermost stage its ``op_name`` path
  names.  A path component names a stage when it equals the name or wraps
  it, as ``vmap(sgs)`` and ``jit(sgs)`` do.
- An instruction that runs in another's computation (a ``while`` body or
  condition, a ``conditional`` branch, a ``call``, a fusion), and whose
  path names no stage beyond those the caller's path names, takes the
  caller's stage.  The rule applies transitively.  Batching under
  ``vmap`` cuts some ops' paths short, back to a frame of their caller's
  path: the SGS scan's scatters read ``.../search/.../vmap(jit(fitness_fn))``,
  and the SGS loop that runs them places them.
- The phase follows the same rules.

A share is 100 x the own time of the ops in a stage or phase over the own
time of every op in the traced window.  Ops under no stage count as
unattributed, and the five stage shares and the unattributed share sum to
100.  Where ops the program does not hold take more than
:data:`MISSING_MAX` of the window's op time, the program does not match
the trace and there is no split.  A program that does not name every
stage and phase predates the scopes (or is not the bound's) and gives no
split either.
"""
from __future__ import annotations

import re
import sys
import time

# As ``repro.obs.scopes`` names them.
STAGES = ("sgs", "timing_sweep", "sweep_table", "objectives", "search")
PHASES = ("phase1", "phase2")
UNATTRIBUTED = "unattributed"
MISSING_MAX = 0.005

_INSTR = re.compile(r"\s+(?:ROOT\s+)?%?([^\s=]+)\s+=\s")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS_ONE = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|"
    r"false_computation)=%?([\w.\-]+)")
_CALLS_MANY = re.compile(
    r"\b(?:branch_computations|called_computations)=\{([^}]*)\}")
_WRAP = re.compile(r"[\w.\-]+\((.*)\)\Z")

# One map per cell and process: rebuilding the program takes seconds.
_MAPS: dict = {}


def named(path: str, names) -> str | None:
    """The innermost of ``names`` that an ``op_name`` path names."""
    for comp in reversed(path.split("/")):
        while comp not in names:
            m = _WRAP.match(comp)
            if not m:
                break
            comp = m.group(1)
        if comp in names:
            return comp
    return None


def parse(text: str):
    """From an HLO module's text: per instruction, its computation and
    ``op_name`` path; per computation, the instructions that call it."""
    instrs, callers = {}, {}
    comp = None
    for line in text.splitlines():
        if not line or line.startswith("HloModule"):
            continue
        if not line[0].isspace():
            if line.rstrip().endswith("{"):
                comp = line.split()[1 if line.startswith("ENTRY") else 0]
                comp = comp.lstrip("%")
            continue
        m = _INSTR.match(line)
        if not m or comp is None:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        instrs[name] = (comp, op.group(1) if op else "")
        called = _CALLS_ONE.findall(line)
        for group in _CALLS_MANY.findall(line):
            called += [c.strip().lstrip("%") for c in group.split(",")]
        for c in called:
            callers.setdefault(c, []).append(name)
    return instrs, callers


def attribute(text: str) -> dict:
    """Each instruction of the program's text -> ``(stage, phase)``,
    either None where no rule gives one."""
    instrs, callers = parse(text)
    memo = {}

    def resolve(name, names):
        if (name, names) in memo:
            return memo[name, names]
        comp, path = instrs[name]
        up = callers.get(comp, [])
        # What the callers' paths already name says nothing new: batching
        # can cut an op's path short, back to a name its caller has.
        known = {n for c in up for n in names if named(instrs[c][1], (n,))}
        got = named(path, tuple(n for n in names if n not in known))
        if got is None and up:
            outer = {resolve(c, names) for c in up}
            got = outer.pop() if len(outer) == 1 else None
        memo[name, names] = got
        return got

    return {n: (resolve(n, STAGES), resolve(n, PHASES))
            for n in instrs}


def complete(table: dict) -> bool:
    """Whether the program names every stage and phase."""
    stages = {s for s, _ in table.values()}
    phases = {p for _, p in table.values()}
    return set(STAGES) <= stages and set(PHASES) <= phases


def split(ops: dict, table: dict) -> dict | None:
    """Shares (%) of the ops' own time per stage, per phase and
    unattributed; ``ops`` as the trace reduction gives them (name ->
    ``{"seconds": ...}``), ``table`` as :func:`attribute` gives it.  None
    where ops missing from ``table`` hold more than :data:`MISSING_MAX`
    of the time."""
    total = sum(v["seconds"] for v in ops.values())
    if total <= 0:
        return None
    out = dict.fromkeys(STAGES + PHASES + (UNATTRIBUTED,), 0.0)
    missing = 0.0
    for name, v in ops.items():
        if name not in table:
            missing += v["seconds"]
            out[UNATTRIBUTED] += v["seconds"]
            continue
        stage, phase = table[name]
        out[stage or UNATTRIBUTED] += v["seconds"]
        if phase:
            out[phase] += v["seconds"]
    if missing > MISSING_MAX * total:
        return None
    return {k: 100.0 * s / total for k, s in out.items()}


def program_text(cell) -> str:
    """The cell's bound program as compiled: the driver's own set-up, run
    again at the cell's configuration.  The configuration fixes every
    shape, so the compile cache gives back the program the window ran."""
    import registry
    t0 = time.perf_counter()
    state = registry.driver(cell.driver).setup(cell, 0, lambda _: None)
    text = state.solve.as_text()
    print(f"chipbench: stage map: program rebuilt in "
          f"{time.perf_counter() - t0} s", file=sys.stderr, flush=True)
    return text


def share(ctx: dict, key: str) -> float | None:
    """The traced unit's share (%) of device time under ``key``: a stage,
    a phase or :data:`UNATTRIBUTED`."""
    if not ctx["trace"] or not ctx["device_kind"]:
        return None
    cell = ctx["cell"]
    if cell.name not in _MAPS:
        table = attribute(program_text(cell))
        _MAPS[cell.name] = table if complete(table) else None
    table = _MAPS[cell.name]
    if table is None:
        return None
    got = split(ctx["trace"]["ops"], table)
    return None if got is None else got[key]
