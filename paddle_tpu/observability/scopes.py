"""Names inside the compiled step: the name scopes the lowering writes, and
the way back from a compiled executable's instructions to them.

The lowering (core/executor.py `_run_op` and friends; the mesh path shares
it) enters one `jax.named_scope` per Program op, so that every instruction of
the compiled step carries a path of the form ``[opt/]u.<unit>/op.<op type>``
in its ``op_name`` metadata. The prefixes make the path describe itself: a
reader tells a unit from an op type from one of jax's own path components
(`while`, `body`, `checkpoint`, ...) by the text alone, whatever process
compiled it.

- **unit** (``u.``) is the model part the op was built in
  (`core.program.unit`; nested units give one component each). An op
  implementation may name a part of itself with `unit_scope`
  (``u.rows/op.lookup_table_grad/u.merge`` reads as unit ``rows/merge``: the
  sort and merge of duplicate ids).
- **op type** (``op.``) is the Program op's type.
- **phase** is derived, never written by a model: ops lowered after the
  block's `autodiff` op (everything optimizer.py appends) and the fusable
  parameter updates run under ``opt/``; backward is what jax itself marks
  ``transpose(jvp(...))`` plus whatever runs under the ``autodiff`` walk
  (custom gradients, cotangent sums, recomputed forward work: that is when
  its time is spent); the rest is forward.

jax's persistent compile cache leaves metadata, and so these names, out of
its key: `scheme_name` puts a digest of the names the lowering will write
for a program into the jitted step's own name, which is hashed, so that a
step whose scopes changed is never handed an executable cached under the old
ones.

`op_scopes(compiled)` parses ``compiled.as_text()`` into
``{HLO instruction name: OpScope}``. Nothing is parsed at compile time or on
the hot path; a parsed map is kept for the last few executables asked about.
`hottest_step()` hands out the compiled step this process dispatched most
often, for readers that are given no handle on the executor.

jax is imported inside the functions: the observability package is loaded by
processes that must never import it (the pserver host).
"""
from __future__ import annotations

import functools
import re
import weakref
from collections import Counter
from typing import Dict, NamedTuple, Optional, Tuple

__all__ = ["OpScope", "op_scope", "unit_scope", "autodiff_scope",
           "scheme_name", "is_scheme_name", "op_scopes", "track_step",
           "hottest_step", "PHASES"]

PHASES = ("fwd", "bwd", "opt", "mixed", "none")
_OPT, _AUTODIFF = "opt", "autodiff"
_UNIT, _OP = "u.", "op."
# the version of what is written: of this grammar and of the names op
# implementations give parts of themselves (`unit_scope`). `scheme_name`
# hashes it; a change to either without a new number would be handed the
# executables cached under the old names
_SCHEME = 2
# the names `scheme_name` has handed out: how the set-up account
# (setup_account.py) tells a step of ours from any other jitted function
_NAMED = set()


def op_scope(op_type: str, unit: Optional[str] = None, opt: bool = False):
    """The name scope of one Program op:
    ``[opt/][u.<unit>/...]op.<op type>``."""
    import jax

    parts = [_OPT] if opt else []
    if unit:
        parts += [_UNIT + u for u in unit.split("/")]
    parts.append(_OP + op_type)
    return jax.named_scope("/".join(parts))


def unit_scope(name: str):
    """A named part inside an op's implementation (``merge`` inside the row
    update of the model's ``rows`` gives the unit path ``rows/merge``). A new
    name, like any change to what is written, takes a new `_SCHEME`."""
    import jax

    return jax.named_scope(_UNIT + name)


def autodiff_scope():
    """What the `autodiff` walk runs under: backward work, whatever it is."""
    import jax

    return jax.named_scope(_AUTODIFF)


def scheme_name(base: str, program) -> str:
    """``<base>_<digest>``: the name of a jitted step of `program`, with a
    digest of the scopes the lowering will write into it: the scheme's
    version and the unit and type of every op in order (the phase follows
    from the order)."""
    import zlib

    from ..core.program import UNIT_ATTR

    h = zlib.crc32(f"scheme {_SCHEME};".encode())
    for block in program.blocks:
        for op in block.ops:
            h = zlib.crc32(f"{op.attrs.get(UNIT_ATTR)}/{op.type};".encode(), h)
    name = f"{base}_{h & 0xFFFFFFFF:08x}"
    _NAMED.add(name)
    return name


def is_scheme_name(name: str) -> bool:
    """Whether `scheme_name` gave out `name` in this process: the function
    so named is a step of ours."""
    return name in _NAMED


# ---------------------------------------------------------------------------
# from the compiled text back to the scopes
# ---------------------------------------------------------------------------

class OpScope(NamedTuple):
    """What one HLO instruction of a compiled step belongs to. For a fusion
    (or any instruction that calls computations) the fused instructions
    decide."""
    name: str                    # HLO instruction name, e.g. "fusion.280"
    text: str                    # the instruction's line in as_text()
    phase: str                   # one of PHASES
    unit: Optional[str]          # the unit path of its matrix product, else
                                 # the one most of its instructions name
    op_types: Tuple[str, ...]    # Program op types found, sorted
    has_dot: bool                # it, or what it calls, holds a dot/convolution


_JIT_NAME = re.compile(r"\bp?jit\([^()]*\)")
_WRAPPER = re.compile(r"[A-Za-z_][\w.\-]*\(")


@functools.lru_cache(maxsize=65536)
def _read_op_name(op_name: str):
    """(phase or None, unit path or None, op type or None) of one op_name,
    e.g. ``jit(step)/autodiff/transpose(jvp(u.bert_layer_0/op.mul))/transpose``
    -> ("bwd", "bert_layer_0", "mul")."""
    # a jit's argument is a function's name, not a scope; every other
    # transform wraps a piece of the name stack, which is kept
    path = _JIT_NAME.sub("", op_name)
    path = _WRAPPER.sub("/", path).replace(")", "/")
    parts = [p for p in path.split("/") if p][:-1]    # the last is the primitive
    opt = _OPT in parts
    backward = "transpose(" in op_name or _AUTODIFF in parts
    unit = "/".join(p[len(_UNIT):] for p in parts if p.startswith(_UNIT))
    op_type = next((p[len(_OP):] for p in parts if p.startswith(_OP)), None)
    if not (opt or backward or unit or op_type):
        return None, None, None
    phase = "opt" if opt else "bwd" if backward else "fwd"
    return phase, unit or None, op_type


def _phase_of(phases) -> str:
    """One phase for an instruction from those of its fused instructions.
    Forward-named work that XLA moved into a kernel of the backward pass (the
    residuals jax computes under `jvp(...)`, fused into their consumer) counts
    as backward, like recomputation: that is when its time is spent. A kernel
    that holds optimizer work and anything else is `mixed`."""
    found = set(phases)
    if not found:
        return "none"
    if found == {"opt"}:
        return "opt"
    if "opt" in found:
        return "mixed"
    return "bwd" if "bwd" in found else "fwd"


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:calls|to_apply|body|condition|true_computation|"
                    r"false_computation)=%?([\w.\-]+)")
_CALL_LISTS = re.compile(r"\b(?:branch_computations|called_computations)="
                         r"\{([^}]*)\}")
_DOT_OPCODES = ("dot", "convolution")


def _parse(text: str) -> Dict[str, OpScope]:
    # pass 1: every instruction with its own reading, by computation
    computations: Dict[str, list] = {}
    current = None
    for line in text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = computations.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.group(1), m.group(2)
        opcode = _OPCODE.search(" " + rest)
        called = _CALLS.findall(rest)
        for group in _CALL_LISTS.findall(rest):
            called += [c.strip().lstrip("%") for c in group.split(",")
                       if c.strip()]
        op_name = _OP_NAME.search(rest)
        reading = (_read_op_name(op_name.group(1)) if op_name
                   else (None, None, None))
        current.append((name, line.strip(), reading,
                        bool(opcode) and opcode.group(1) in _DOT_OPCODES,
                        called))

    # pass 2: what an instruction holds, with the computations it calls. A
    # unit named by a matrix product outvotes any number of small operations
    # fused around it (the head's 13 ms gradient matmul carries the last
    # layer's layer-norm reductions): a vote of its own weight class
    dot_vote = 1 << 20
    summaries: Dict[str, tuple] = {}

    def held(reading, is_dot, called, seen=()):
        phase, unit, op_type = reading
        phases = {phase} if phase else set()
        votes = Counter({unit: dot_vote if is_dot else 1} if unit else {})
        types = {op_type} if op_type else set()
        for comp in called:
            p, v, t, d = summary(comp, seen)
            phases |= p
            votes.update(v)
            types |= t
            is_dot = is_dot or d
        return phases, votes, types, is_dot

    def summary(comp: str, seen=()):
        if comp not in summaries:
            phases, votes, types, dot = set(), Counter(), set(), False
            if comp not in seen:               # a recursion guard, not a case
                for _, _, reading, is_dot, called in computations.get(
                        comp, ()):
                    p, v, t, d = held(reading, is_dot, called, seen + (comp,))
                    phases |= p
                    votes.update(v)
                    types |= t
                    dot = dot or d
            summaries[comp] = (phases, votes, types, dot)
        return summaries[comp]

    out: Dict[str, OpScope] = {}
    for instructions in computations.values():
        for name, line, reading, is_dot, called in instructions:
            phases, votes, types, dot = held(reading, is_dot, called)
            top = sorted(votes.items(), key=lambda kv: (-kv[1], kv[0]))
            out[name] = OpScope(
                name=name, text=line, phase=_phase_of(phases),
                unit=top[0][0] if top else None,
                op_types=tuple(sorted(types)), has_dot=dot)
    return out


# the last few executables asked about, each with its map: a traced run's
# readers all ask about the same one (strong references, hence the bound)
_PARSED: list = []
_PARSED_MAX = 4


def op_scopes(compiled) -> Dict[str, OpScope]:
    """``{HLO instruction name: OpScope}`` of a compiled executable (anything
    with `as_text()`, or the text itself, from whatever process)."""
    if isinstance(compiled, str):
        return _parse(compiled)
    for held, parsed in _PARSED:
        if held is compiled:
            return parsed
    parsed = _parse(compiled.as_text())
    _PARSED.append((compiled, parsed))
    del _PARSED[:-_PARSED_MAX]
    return parsed


# ---------------------------------------------------------------------------
# the step this process runs
# ---------------------------------------------------------------------------

# the executor's and the mesh path's step objects: each counts its calls
# (`calls`) and can hand out its executable (`compiled()`)
_STEPS: "weakref.WeakSet" = weakref.WeakSet()


def track_step(step) -> None:
    _STEPS.add(step)


def hottest_step():
    """The compiled step dispatched most often in this process (the training
    step, not the startup program's), or None before any dispatch."""
    steps = [s for s in _STEPS if s.calls]
    if not steps:
        return None
    return max(steps, key=lambda s: s.calls).compiled()
