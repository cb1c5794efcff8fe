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
           "COLLECTIVE_PREFIX", "LOOP_SCOPE", "EXIT_SCOPE", "MOE_SCOPE",
           "MOE_EXPERTS_SCOPE", "ATTN_WINDOW_SCOPE", "ATTN_FULL_SCOPE",
           "ATTN_LATENT_SCOPE", "SHARED_EXPERT_SCOPE", "MTP_SCOPE",
           "ATTN_BLOCKDIFF_SCOPE", "NOISE_SCOPE", "CCA_SCOPE", "SSM_SCOPE",
           "GMU_SCOPE", "ATTN_CROSS_SCOPE", "FWD", "BWD",
           "UPDATE", "COLLECTIVE", "CONTROL", "OTHER", "LOOP",
           "EXIT", "ROUTE", "EXPERTS", "ATTN_WINDOW", "ATTN_FULL",
           "ATTN_LATENT", "SHARED_EXPERT", "ATTN_BLOCKDIFF", "NOISE", "CCA",
           "SSM", "GMU", "ATTN_CROSS",
           "LOOP_PARTS", "BLOCK_PARTS", "LATENT_PARTS", "DIFFUSION_PARTS",
           "CCA_PARTS", "HYBRID_PARTS", "phase_of", "instruction_phases",
           "scope_part_of", "instruction_scope_parts", "loop_part_of",
           "instruction_loop_parts", "block_part_of",
           "instruction_block_parts", "latent_part_of",
           "instruction_latent_parts", "diffusion_part_of",
           "instruction_diffusion_parts", "cca_part_of",
           "instruction_cca_parts", "hybrid_part_of",
           "instruction_hybrid_parts", "register_program", "program_hlo",
           "program_names"]

FWD_SCOPE, LOSS_SCOPE = "mx_fwd", "mx_loss"
UPDATE_SCOPE, CODEC_SCOPE = "mx_update", "mx_codec"
# inside mx_update: the flat buckets' layout changes and the one kernel
FLATTEN_SCOPE, UNFLATTEN_SCOPE, SWEEP_SCOPE = "flatten", "unflatten", "sweep"
COLLECTIVE_PREFIX = "mx_coll:"
# inside mx_fwd, in a block that applies one stack of layers several
# times (gluon.contrib.transformer.LoopedLM): one pass of the stack, and
# one exit's norm -> gate and projection -> cross-entropy
LOOP_SCOPE, EXIT_SCOPE = "mx_loop", "mx_exit"
# inside mx_fwd, in a block of sparse-expert layers under attention of
# two kinds (gluon.contrib.transformer.MoELM): a layer's expert part —
# router, top-k, dispatch, the experts' products, combine — with the
# grouped products and their activation in an inner scope of their own
# (transformer.routed_ffn -> parallel/moe.py routed_experts); rotary and
# attention of a layer whose queries see a window of keys, and of one
# whose queries see every key before them
# (transformer.grouped_query_attention)
MOE_SCOPE, MOE_EXPERTS_SCOPE = "mx_moe", "mx_moe_experts"
ATTN_WINDOW_SCOPE, ATTN_FULL_SCOPE = "mx_attn_window", "mx_attn_full"
# inside mx_fwd, in a block of latent-attention layers with a shared
# expert and multi-token-prediction modules
# (gluon.contrib.transformer.LatentMoELM): what latent attention runs
# around its flash call (transformer.latent_attention: the
# down-projections, the latents' norms, the up-projections, the split and
# the shared rotary key's broadcast: the call itself and its rotary are
# mx_attn_full); the expert every token takes (transformer.routed_ffn);
# a whole prediction module (transformer.latent_moe_lm_forward), which
# CROSSES the other parts (its
# layer's attention and experts carry their scopes inside it).  The
# maps match by substring, so none of these names holds an older one
ATTN_LATENT_SCOPE, SHARED_EXPERT_SCOPE = "mx_attn_latent", "mx_shared_expert"
MTP_SCOPE = "mx_mtp"
# inside mx_fwd, in a block trained by diffusion over blocks
# (gluon.contrib.transformer.MoELM with block_length): a layer's attention
# over the rows [noised ; clean] under the three-part block mask — QK-norm,
# rotary at the rows' positions, the kernels
# (transformer.grouped_query_attention) — and the noising with the gather
# of the 2 L rows' embeddings (MoELM._noised_rows, transformer._trunk)
ATTN_BLOCKDIFF_SCOPE, NOISE_SCOPE = "mx_attn_blockdiff", "mx_noise"
# inside mx_fwd, in a block of compressed convolutional attention
# (gluon.contrib.transformer.MoELM with cca;
# transformer.compressed_conv_attention): what mixes the latent
# queries and keys before the flash call — the causal convolution over
# time, the one over channels by head, the q-k mean, the values' shift,
# the QK norm with its temperature and the partial rotary.  The
# projections are the layer's, the flash call is mx_attn_full
CCA_SCOPE = "mx_cca"
# inside mx_fwd, in a decoder-hybrid-decoder block
# (gluon.contrib.transformer.SambaYLM): a whole Mamba mixer, from its
# input projection to its output projection (transformer.mamba_mixer:
# the causal convolution, the selective scan and the gate among them); a
# gated memory unit (transformer.gated_memory); a cross-attention layer's
# query projection, flash call, difference of its two maps and their
# norm (transformer.differential_attention over the shared keys and
# values).  Its window and full layers keep mx_attn_window / mx_attn_full
SSM_SCOPE, GMU_SCOPE, ATTN_CROSS_SCOPE = "mx_ssm", "mx_gmu", "mx_attn_cross"
# what jax writes into the name stack of a forward that is run again in
# the backward pass (jax.checkpoint)
REMAT_MARK = "rematted_computation"
FWD, BWD, UPDATE, COLLECTIVE, CONTROL, OTHER = \
    "fwd", "bwd", "update", "collective", "control", "other"
LOOP, EXIT = "loop", "exit"
ROUTE, EXPERTS = "route", "experts"
ATTN_WINDOW, ATTN_FULL = "attn_window", "attn_full"
ATTN_LATENT, SHARED_EXPERT = "attn_latent", "shared_expert"
ATTN_BLOCKDIFF, NOISE = "attn_blockdiff", "noise"
CCA = "cca"
SSM, GMU, ATTN_CROSS = "ssm", "gmu", "attn_cross"


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


# ``(scope, part)`` tables of the per-family maps, inner scope first: the
# first scope an op_name holds names its part (an exit's norm and gate sit
# inside the loop's body; ``mx_moe_experts`` inside ``mx_moe``)
LOOP_PARTS = ((EXIT_SCOPE, EXIT), (LOOP_SCOPE, LOOP))
BLOCK_PARTS = ((MOE_EXPERTS_SCOPE, EXPERTS), (MOE_SCOPE, ROUTE),
               (ATTN_WINDOW_SCOPE, ATTN_WINDOW), (ATTN_FULL_SCOPE, ATTN_FULL))
LATENT_PARTS = ((ATTN_LATENT_SCOPE, ATTN_LATENT),
                (SHARED_EXPERT_SCOPE, SHARED_EXPERT))
DIFFUSION_PARTS = ((ATTN_BLOCKDIFF_SCOPE, ATTN_BLOCKDIFF),
                   (NOISE_SCOPE, NOISE))
CCA_PARTS = ((CCA_SCOPE, CCA),)
HYBRID_PARTS = ((SSM_SCOPE, SSM), (GMU_SCOPE, GMU),
                (ATTN_CROSS_SCOPE, ATTN_CROSS))


def scope_part_of(op_name, scopes, flag=REMAT_MARK):
    """``(part, flagged)`` of an HLO ``op_name``: ``part`` that of the
    first ``(scope, part)`` of ``scopes`` whose scope the name holds, else
    None; ``flagged`` whether it holds ``flag`` — by default whether the
    instruction is a forward run again for the backward pass
    (``jax.checkpoint``)."""
    if not op_name:
        return None, False
    part = next((p for scope, p in scopes if scope in op_name), None)
    return part, flag in op_name


def loop_part_of(op_name):
    """:func:`scope_part_of` under :data:`LOOP_PARTS`: a looped stack's
    ``exit`` or ``loop``."""
    return scope_part_of(op_name, LOOP_PARTS)


def block_part_of(op_name):
    """:func:`scope_part_of` under :data:`BLOCK_PARTS`: an expert layer's
    ``experts`` or ``route``, or ``attn_window`` / ``attn_full``."""
    return scope_part_of(op_name, BLOCK_PARTS)


def latent_part_of(op_name):
    """``(part, in_mtp)``: :func:`scope_part_of` under
    :data:`LATENT_PARTS` (``attn_latent``, ``shared_expert``), flagged
    where a multi-token-prediction module (``mx_mtp``) runs the
    instruction, whatever its part."""
    return scope_part_of(op_name, LATENT_PARTS, MTP_SCOPE)


def diffusion_part_of(op_name):
    """:func:`scope_part_of` under :data:`DIFFUSION_PARTS`:
    ``attn_blockdiff`` or ``noise``."""
    return scope_part_of(op_name, DIFFUSION_PARTS)


def cca_part_of(op_name):
    """:func:`scope_part_of` under :data:`CCA_PARTS`: ``cca``."""
    return scope_part_of(op_name, CCA_PARTS)


def hybrid_part_of(op_name):
    """:func:`scope_part_of` under :data:`HYBRID_PARTS`: a Mamba mixer's
    ``ssm``, a gated memory unit's ``gmu`` or ``attn_cross``."""
    return scope_part_of(op_name, HYBRID_PARTS)


_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")
_TUPLE = re.compile(r"[\])}] tuple\(")
_PARAMETER = re.compile(r"[\])}] parameter\(\d+\)")
_NAME_STACK = "jit("        # what every op_name traced in a program holds


_CONTROL = re.compile(r"[\])}] (?:while|conditional|call)\(")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(")
# the computations a control instruction runs (a while's condition is a
# compare of the counter, not its body)
_BODIES = re.compile(r"(?:body|to_apply|true_computation|false_computation)"
                     r"=%([\w.\-]+)|branch_computations=\{([^}]*)\}")


def _classify(hlo_text, of, nothing, control):
    """``{instruction name: of(op_name)}`` over an optimized module's
    text, with the inheritance :func:`instruction_phases` describes;
    ``nothing`` is the class that hands nothing on.  A ``while`` /
    ``conditional`` / ``call`` whose body's instructions are in the text
    (so in this map, to be found as events of their own) is ``control``
    and lends the class of its own name only to an instruction no other
    neighbour names; one whose body is not there keeps the class of its
    own name, like any instruction."""
    found, lent, waiting, users = {}, {}, [], {}
    controls, bodies, computation = set(), set(), None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            head = _COMPUTATION.match(line)
            computation = head.group(1) if head else computation
            continue
        name, rest = m.groups()
        named = _OP_NAME.search(rest)
        own = of(named.group(1)) \
            if named and _NAME_STACK in named.group(1) else None
        refs = _REF.findall(rest.split(", metadata=", 1)[0])
        for r in refs:
            users.setdefault(r, []).append(name)
        if _PARAMETER.search(rest) or _TUPLE.search(rest):
            found[name] = nothing
            continue
        bodies.add(computation)     # it holds what an event can name
        if _CONTROL.search(rest):
            ran = [c for one, many in _BODIES.findall(rest)
                   for c in [one] + _REF.findall(many) if c]
            # callees are printed before their callers
            if ran and bodies.issuperset(ran):
                found[name] = control
                if own is not None:
                    # what is unpacked from a loop's result and nothing
                    # else names (a weight carried through one loop and
                    # prefetched for the next) belongs to the loop's class
                    lent[name] = own
                controls.add(name)
                continue
        if own is not None:
            found[name] = own
        else:
            waiting.append((name, refs))

    def known(n):
        return n not in controls and found.get(n, nothing) != nothing

    def lends(n):
        return known(n) or lent.get(n, nothing) != nothing

    # program order resolves chains of reads, the reverse chains of feeds;
    # a loop's own class is tried only for what is still unnamed after both
    for has in (known, lends):
        for order, side in ((waiting, None), (reversed(waiting), users)):
            for name, refs in order:
                if known(name):
                    continue
                near = refs if side is None else side.get(name, ())
                src = next((r for r in near if has(r)), None)
                if src is not None:
                    found[name] = found[src] if known(src) else lent[src]
    for name, _refs in waiting:
        found.setdefault(name, nothing)
    return found


def instruction_phases(hlo_text):
    """``{instruction name: phase}`` over an optimized module's text.
    An instruction the program did not name — no ``op_name`` (the
    compiler's own ``copy-start`` / ``copy-done`` / ``bitcast``), or an
    argument's (``op_name="diff[132]"`` on the ``copy`` that changes a
    weight's layout) — takes the phase of the instruction it reads,
    else of the one it feeds.  A parameter, a name stack outside every
    scope (``jit(fbu)/jit(_threefry_fold_in)/xor``) and a ``tuple``,
    which gathers results of every phase, are ``other`` and hand
    nothing on.  A ``while`` (a ``lax.scan``), ``conditional`` or
    ``call`` whose body's instructions are in the text is ``control``:
    where the profiler gives it an event of its own, that event spans its
    body's instructions, which are events too and carry their own phases
    (on the v5e a ``while``'s event is covered to 99.99 % by them,
    PERF.md).  One whose body is not in the text keeps the phase of its
    own name, so its time counts once, for a phase or for ``other``."""
    return _classify(hlo_text, phase_of, OTHER, CONTROL)


def instruction_scope_parts(hlo_text, scopes, flag=REMAT_MARK):
    """``{instruction name: (part, flagged)}`` beside
    :func:`instruction_phases`, each named instruction classed by
    :func:`scope_part_of` under ``scopes`` and ``flag``; the same
    inheritance."""
    return _classify(hlo_text,
                     lambda op_name: scope_part_of(op_name, scopes, flag),
                     (None, False), (None, False))


def instruction_loop_parts(hlo_text):
    """:func:`instruction_scope_parts` under :data:`LOOP_PARTS`, for a
    program that applies one stack of layers several times."""
    return instruction_scope_parts(hlo_text, LOOP_PARTS)


def instruction_block_parts(hlo_text):
    """:func:`instruction_scope_parts` under :data:`BLOCK_PARTS`, for a
    program whose layers route tokens to experts under window and full
    attention."""
    return instruction_scope_parts(hlo_text, BLOCK_PARTS)


def instruction_latent_parts(hlo_text):
    """:func:`instruction_scope_parts` under :data:`LATENT_PARTS`, flagged
    by ``mx_mtp``, for a program of latent-attention layers with a shared
    expert and prediction modules."""
    return instruction_scope_parts(hlo_text, LATENT_PARTS, MTP_SCOPE)


def instruction_diffusion_parts(hlo_text):
    """:func:`instruction_scope_parts` under :data:`DIFFUSION_PARTS`, for
    a program trained by diffusion over blocks."""
    return instruction_scope_parts(hlo_text, DIFFUSION_PARTS)


def instruction_cca_parts(hlo_text):
    """:func:`instruction_scope_parts` under :data:`CCA_PARTS`, for a
    program of compressed convolutional attention."""
    return instruction_scope_parts(hlo_text, CCA_PARTS)


def instruction_hybrid_parts(hlo_text):
    """:func:`instruction_scope_parts` under :data:`HYBRID_PARTS`, for a
    program of Mamba mixers, gated memory units and cross-attention."""
    return instruction_scope_parts(hlo_text, HYBRID_PARTS)


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
        from ..ops.pallas_kernels import export_flash_fwd_calls
        from ..parallel.moe import export_route_passes
        export_flash_fwd_calls(name, ent[3])
        export_route_passes(name, ent[3])
    return ent[3]


def program_names():
    return sorted(_PROGRAMS)
