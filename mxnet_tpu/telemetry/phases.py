"""Step phases — which part of a train step a device operation belongs to.

The two step programs (``executor.py`` ``_build_fbu`` / ``fb``,
``parallel/trainer.py`` ``_build``) wrap their parts in
``jax.named_scope``s named here.  A scope changes no computation; it
rides every instruction's ``metadata={op_name="..."}`` through the
compiler: ``jit(fbu)/jvp(mx_fwd)/...`` on forward instructions,
``jit(fbu)/transpose(jvp(mx_fwd))/...`` on backward ones,
``jit(fbu)/mx_update/flatten/...`` on the optimizer's.  The profiler's
device events carry no scope, only the instruction's name, so a reader
joins the two: event name -> leading ``%instruction`` ->
:func:`instruction_phases` of :func:`program_hlo` (docs/faq/telemetry.md
"Step phases").

Stdlib only at import; jax is touched by :func:`register_program` and
:func:`program_hlo` alone.
"""
from __future__ import annotations

import contextlib
import re

__all__ = ["FWD_SCOPE", "LOSS_SCOPE", "UPDATE_SCOPE", "CODEC_SCOPE",
           "FLATTEN_SCOPE", "UNFLATTEN_SCOPE", "SWEEP_SCOPE",
           "COLLECTIVE_PREFIX", "FWD", "BWD", "UPDATE", "COLLECTIVE",
           "OTHER", "phase_of", "instruction_phases", "register_program",
           "program_hlo", "program_names"]

FWD_SCOPE, LOSS_SCOPE = "mx_fwd", "mx_loss"
UPDATE_SCOPE, CODEC_SCOPE = "mx_update", "mx_codec"
# inside mx_update: the flat buckets' layout changes and the one kernel
FLATTEN_SCOPE, UNFLATTEN_SCOPE, SWEEP_SCOPE = "flatten", "unflatten", "sweep"
COLLECTIVE_PREFIX = "mx_coll:"
FWD, BWD, UPDATE, COLLECTIVE, OTHER = \
    "fwd", "bwd", "update", "collective", "other"


def phase_of(op_name):
    """The phase an HLO ``op_name`` (a jax name stack; several joined
    by ``;`` on a fused instruction) belongs to."""
    if not op_name:
        return OTHER
    if COLLECTIVE_PREFIX in op_name:
        return COLLECTIVE
    if UPDATE_SCOPE in op_name or CODEC_SCOPE in op_name:
        return UPDATE
    if FWD_SCOPE in op_name or LOSS_SCOPE in op_name:
        return BWD if "transpose(" in op_name else FWD
    return OTHER


_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")
_TUPLE = re.compile(r"[\])}] tuple\(")
_PARAMETER = re.compile(r"[\])}] parameter\(\d+\)")
_NAME_STACK = "jit("        # what every op_name traced in a program holds


def instruction_phases(hlo_text):
    """``{instruction name: phase}`` over an optimized module's text.
    An instruction the program did not name — no ``op_name`` (the
    compiler's own ``copy-start`` / ``copy-done`` / ``bitcast``), or an
    argument's (``op_name="diff[132]"`` on the ``copy`` that changes a
    weight's layout) — takes the phase of the instruction it reads,
    else of the one it feeds.  A parameter, a name stack outside every
    scope (``jit(fbu)/jit(_threefry_fold_in)/xor``) and a ``tuple``,
    which gathers results of every phase, are ``other`` and hand
    nothing on."""
    phases, waiting, users = {}, [], {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        name, rest = m.groups()
        named = _OP_NAME.search(rest)
        refs = _REF.findall(rest.split(", metadata=", 1)[0])
        for r in refs:
            users.setdefault(r, []).append(name)
        if _PARAMETER.search(rest) or _TUPLE.search(rest):
            phases[name] = OTHER
        elif named and _NAME_STACK in named.group(1):
            phases[name] = phase_of(named.group(1))
        else:
            waiting.append((name, refs))

    def known(n):
        return phases.get(n, OTHER) != OTHER

    # program order resolves chains of reads, the reverse chains of feeds
    for order, side in ((waiting, None), (reversed(waiting), users)):
        for name, refs in order:
            if known(name):
                continue
            near = refs if side is None else side.get(name, ())
            src = next((r for r in near if known(r)), None)
            if src is not None:
                phases[name] = phases[src]
    for name, _refs in waiting:
        phases.setdefault(name, OTHER)
    return phases


# name -> [jitted fn, abstract args, context factory or None, HLO text]
_PROGRAMS = {}


def register_program(name, jit_fn, args, scope=None):
    """Remember how to lower ``jit_fn`` again: ``args`` is one real
    dispatch's arguments, kept as shapes, dtypes and (committed)
    shardings — no array stays alive through them.  The jitted function
    (and what its closure holds) is kept until :func:`program_hlo` has
    the text or the next registration of the name replaces this one.
    Nothing is lowered or compiled here."""
    import jax

    def abstract(a):
        if isinstance(a, jax.Array):
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype, weak_type=getattr(a, "weak_type", False),
                sharding=a.sharding if a.committed else None)
        return a

    _PROGRAMS[name] = [jit_fn, jax.tree_util.tree_map(abstract, args),
                       scope, None]


def program_hlo(name):
    """The optimized HLO text of the program registered as ``name``
    (None if there is none): lowered and compiled ONLY here, on demand
    — with the persistent compile cache on, a cache hit — and memoised."""
    ent = _PROGRAMS.get(name)
    if ent is None:
        return None
    if ent[3] is None:
        jit_fn, args, scope = ent[:3]
        with scope() if scope is not None else contextlib.nullcontext():
            ent[3] = jit_fn.lower(*args).compile().as_text()
        ent[:3] = None, None, None      # the text is all that is needed
    return ent[3]


def program_names():
    return sorted(_PROGRAMS)
