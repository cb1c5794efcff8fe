"""Operator registry — the TPU-native replacement for the NNVM op registry.

Reference contract being re-designed (not ported):
- ``nnvm::Op`` global registry with typed attributes, consumed via
  ``Op::GetAttr<FCompute>(...)`` (reference: src/imperative/imperative.cc:47,
  include/mxnet/op_attr_types.h:107-257).
- dmlc::Parameter attr structs that power Python kwargs/docstrings.

TPU-native design: every operator is ONE pure jax function
``fn(*arrays, **attrs) -> array | tuple``.  That single function plays all
the reference's per-op roles at once:

- ``FCompute``      -> the function body (jnp/lax/pallas), jit-compilable.
- ``FInferShape``/``FInferType`` -> ``jax.eval_shape`` abstract evaluation.
- ``FGradient``     -> ``jax.vjp`` (custom grads via ``jax.custom_vjp``
                       inside the impl where MXNet semantics differ,
                       e.g. SoftmaxOutput ignoring head gradients).
- ``FStatefulCompute`` -> explicit state threading: stateful ops take and
                       return state arrays (aux states, RNG keys) —
                       no hidden mutation, so everything stays traceable.

Context-dependent behaviour (train vs predict mode, RNG) is injected by
the caller through reserved attrs ``__is_train__`` and ``__rng__`` —
declared by the op via ``needs_is_train`` / ``needs_rng`` flags.
"""
from __future__ import annotations

import ast
import inspect

from ..base import MXNetError

__all__ = ["OpDef", "Param", "register", "get_op", "list_ops",
           "coerce_attrs"]

_OP_REGISTRY: dict[str, "OpDef"] = {}

# Optional ARRAY inputs: keyword-with-default fn parameters that are
# tensors, not attrs (filled positionally by the dispatcher).  Single
# source of truth — symbol composition imports this to decide which
# variables to auto-create.
OPTIONAL_ARRAY_INPUTS = frozenset({
    "bias", "gamma", "state_cell", "sequence_length",
    "data_lengths", "label_lengths", "trans", "positions",
    "position_draws", "block_draws", "position_weight"})

# Framework metadata attrs that ride along with any op call and are not
# op parameters (reference: node attrs like `name` live on the NNVM node,
# not in the dmlc::Parameter struct).  `__*__` attrs (scope attrs such as
# __lr_mult__, runtime injections __is_train__/__rng__) also pass through.
_PASSTHROUGH_ATTRS = frozenset({"name", "ctx_group"})


class Param:
    """Declarative typed op parameter — the native analogue of a
    ``dmlc::Parameter`` field (reference include/mxnet/imperative.h:39-53,
    dmlc-core parameter.h): type, default, range, and doc in one place,
    enforced at call time and rendered into the generated docstring.

    ptype: one of int/float/bool/str/tuple (python types), a tuple of
    allowed strings (an enum), or None meaning "any value" (name-checked
    but not type-checked).  ``low``/``high`` bound numeric values — for
    tuple params they bound every element.  ``elem`` sets the element
    type of tuple params (int, float, or None for pass-through);
    defaults to int, the reference's TShape behaviour.

    ``derived`` marks a table entry auto-derived from the op fn's
    signature rather than hand-declared (see ``OpDef``): it still gates
    the set of accepted kwarg names and applies inferred type checks,
    but carries no range/enum constraints.
    """

    __slots__ = ("name", "ptype", "default", "low", "high", "required",
                 "doc", "elem", "derived")

    def __init__(self, name, ptype, default=None, low=None, high=None,
                 required=False, doc="", elem=int, derived=False):
        self.name = name
        self.ptype = ptype
        self.default = default
        self.low = low
        self.high = high
        self.required = required
        self.doc = doc
        self.elem = elem
        self.derived = derived

    # -- rendering ------------------------------------------------------
    def describe(self):
        if self.ptype is None:
            ty = "any"
        elif isinstance(self.ptype, tuple):
            ty = "{%s}" % ", ".join(repr(v) for v in self.ptype)
        elif self.ptype is tuple and self.elem is not None:
            ty = "tuple of %s" % self.elem.__name__
        else:
            ty = self.ptype.__name__
        parts = ["%s : %s" % (self.name, ty)]
        if self.required:
            parts.append("required")
        else:
            parts.append("default=%r" % (self.default,))
        if self.low is not None or self.high is not None:
            parts.append("range=[%s, %s]" %
                         ("-inf" if self.low is None else self.low,
                          "inf" if self.high is None else self.high))
        head = ", ".join(parts)
        return head + ("\n    " + self.doc if self.doc else "")

    # -- enforcement ----------------------------------------------------
    def check(self, opname, value):
        """Validate + normalize one value; raises MXNetError naming the
        op and the parameter (reference: dmlc::ParamError)."""
        def fail(why):
            raise MXNetError(
                "%s: invalid parameter %s=%r — %s" %
                (opname, self.name, value, why))

        if value is None:
            if self.required:
                fail("a value is required")
            return value
        if self.ptype is None:                      # any: name-gated only
            return value
        if isinstance(self.ptype, tuple):           # enum
            if value not in self.ptype:
                fail("expected one of %s" % (self.ptype,))
            return value
        if self.ptype is bool:
            if isinstance(value, (bool, int)) or value in (0, 1):
                return bool(value)
            fail("expected a boolean")
        if self.ptype is int:
            import numbers
            if isinstance(value, bool) or \
                    not isinstance(value, numbers.Integral):
                fail("expected an integer")
            self._range(fail, int(value))
            return int(value)
        if self.ptype is float:
            import numbers
            if not isinstance(value, numbers.Real) or \
                    isinstance(value, bool):
                fail("expected a number")
            self._range(fail, float(value))
            return float(value)
        if self.ptype is str:
            if not isinstance(value, str):
                fail("expected a string")
            return value
        if self.ptype is tuple:
            # None elements pass through: dmlc::optional<int> parity
            # (reference slice begin/end/step accept per-axis None,
            # src/operator/tensor/matrix_op-inl.h SliceParam)
            cast = self.elem if self.elem is not None else (lambda v: v)
            what = ("a tuple of %ss" % self.elem.__name__
                    if self.elem is not None else "a tuple")
            if isinstance(value, (int, float)) and not \
                    isinstance(value, bool):
                value = (cast(value),)
            if not isinstance(value, (tuple, list)):
                fail("expected %s" % what)
            try:
                t = tuple(None if v is None else cast(v) for v in value)
            except (TypeError, ValueError):
                fail("expected %s" % what)
            if self.elem is not None:
                for v in t:
                    if v is not None:
                        self._range(fail, v)
            return t
        return value  # pragma: no cover - unknown ptype passes through

    def _range(self, fail, v):
        if self.low is not None and v < self.low:
            fail("below the allowed minimum %s" % self.low)
        if self.high is not None and v > self.high:
            fail("above the allowed maximum %s" % self.high)


def _infer_param(name, default):
    """One signature-derived Param: type inferred from the default value.

    `dtype` params stay untyped (users pass strings, numpy dtypes, or
    type objects interchangeably); `None` defaults carry no type
    information and stay untyped too — the entry still gates the kwarg
    NAME, which is what kills silent typos."""
    if name == "dtype" or default is None:
        return Param(name, None, default=default, derived=True)
    if isinstance(default, bool):
        return Param(name, bool, default=default, derived=True)
    if isinstance(default, int):
        return Param(name, int, default=default, derived=True)
    if isinstance(default, float):
        return Param(name, float, default=default, derived=True)
    if isinstance(default, str):
        return Param(name, str, default=default, derived=True)
    if isinstance(default, (tuple, list)):
        elem = (float if any(isinstance(v, float) for v in default)
                else int)
        return Param(name, tuple, default=tuple(default), elem=elem,
                     derived=True)
    return Param(name, None, default=default, derived=True)


class SigSplit:
    """Classification of an op fn's named parameters — the ONE source of
    truth shared by the nd dispatcher, NDArray method codegen, symbol
    composition, and param-table derivation (each previously re-walked
    the signature with hand-copied rules).

    required:  positional array inputs (no default), declaration order
    optional:  optional array inputs (OPTIONAL_ARRAY_INPUTS ∩ signature)
    attrs:     {name: default} for keyword attrs (``__*__`` excluded)
    variadic:  fn takes *args (e.g. Concat) — array binding is by call
               order, named slotting does not apply
    """

    __slots__ = ("required", "optional", "attrs", "variadic",
                 "_order", "_names")

    def __init__(self, fn):
        self.required, self.optional = [], []
        self.attrs = {}
        self.variadic = False
        self._order = self._names = None
        try:
            sig = inspect.signature(fn)
        except (TypeError, ValueError):  # pragma: no cover - builtins
            return
        for p in sig.parameters.values():
            if p.kind == inspect.Parameter.VAR_POSITIONAL:
                self.variadic = True
                continue
            if p.kind == inspect.Parameter.VAR_KEYWORD:
                continue
            if p.default is inspect.Parameter.empty:
                if p.kind == inspect.Parameter.KEYWORD_ONLY:
                    # keyword-only without default: an attr, not an
                    # array slot (arrays always bind positionally)
                    self.attrs[p.name] = None
                else:
                    self.required.append(p.name)
            elif p.name in OPTIONAL_ARRAY_INPUTS:
                self.optional.append(p.name)
            elif not p.name.startswith("__"):
                self.attrs[p.name] = p.default

    def array_order(self):
        """Array-input names in declaration order (None for variadic ops
        — those bind by call order only).  Cached: this runs on every
        imperative dispatch."""
        if self._order is None and not self.variadic:
            self._order = self.required + self.optional
        return self._order

    def array_names(self):
        if self._names is None:
            self._names = frozenset(self.required) | frozenset(self.optional)
        return self._names


def _derive_params(split, declared, mutate_aux, attr_defaults):
    """Complete an op's parameter table from its fn signature — the
    scripted leg of the dmlc::Parameter migration (reference declares a
    Parameter struct per op, e.g. src/operator/nn/convolution-inl.h:50-100;
    here the fn signature IS the declaration, so the table is derived
    from it).  Hand-declared entries win; keyword-with-default fn
    parameters fill the rest.  Optional ARRAY inputs (bias, gamma, ...)
    and reserved ``__*__`` runtime injections are not attrs."""
    derived = {}
    for n, default in split.attrs.items():
        if n in mutate_aux or n in declared:
            continue
        derived[n] = _infer_param(n, attr_defaults.get(n, default))
    for n, v in attr_defaults.items():
        if n not in derived and n not in declared and not n.startswith("__"):
            derived[n] = _infer_param(n, v)
    return derived


class OpDef:
    """Metadata + implementation for one operator."""

    def __init__(self, name, fn, *, num_outputs=1, aliases=(),
                 needs_is_train=False, needs_rng=False,
                 mutate_aux=(), attr_defaults=None, doc=None, params=None,
                 free_attrs=False):
        self.name = name
        self.fn = fn
        self.num_outputs = num_outputs  # int or callable(attrs)->int
        self.aliases = tuple(aliases)
        self.needs_is_train = needs_is_train
        self.needs_rng = needs_rng
        # names of inputs that are auxiliary state (returned updated as
        # trailing outputs), e.g. BatchNorm moving_mean/moving_var
        self.mutate_aux = tuple(mutate_aux)
        self.attr_defaults = dict(attr_defaults or {})
        self.doc = doc or (fn.__doc__ or "")
        # typed parameter table (dmlc::Parameter analogue): hand-declared
        # entries (types/ranges/enums/docs) merged over signature-derived
        # ones, so EVERY op has a complete table of accepted kwarg names.
        self.params = {p.name: p for p in (params or ())}
        self.free_attrs = free_attrs
        self.sig = SigSplit(fn)
        if not free_attrs:
            self.params.update(_derive_params(
                self.sig, self.params, self.mutate_aux, self.attr_defaults))

    def validate_attrs(self, attrs):
        """Enforce the parameter table on user attrs.

        Unknown kwargs raise, naming the op and the nearest valid
        parameter (reference: dmlc::Parameter Init() throws on unknown
        keys).  Reserved runtime/scope attrs (``__*__``) and framework
        metadata (``name``, ``ctx_group``) pass through untouched;
        required params missing from attrs raise."""
        for k, v in attrs.items():
            if k.startswith("__") or k in _PASSTHROUGH_ATTRS:
                continue
            spec = self.params.get(k)
            if spec is None:
                if self.free_attrs:
                    continue
                import difflib
                close = difflib.get_close_matches(k, self.params, n=1)
                hint = "; did you mean %r?" % close[0] if close else ""
                raise MXNetError(
                    "%s: unknown parameter %r%s  (valid parameters: %s)"
                    % (self.name, k, hint,
                       ", ".join(sorted(self.params)) or "<none>"))
            attrs[k] = spec.check(self.name, v)
        for spec in self.params.values():
            if spec.required and attrs.get(spec.name) is None:
                raise MXNetError(
                    "%s: required parameter %r is missing"
                    % (self.name, spec.name))
        return attrs

    def n_outputs(self, attrs):
        if callable(self.num_outputs):
            return self.num_outputs(attrs)
        return self.num_outputs

    def gen_doc(self):
        """Render the op's docstring: array inputs from the signature,
        then the typed parameter table — the native stand-in for
        dmlc::Parameter's declarative field docs (__FIELDS__ rendered
        into every op docstring in the reference; dmlc-core
        parameter.h).  Cached after first render."""
        if getattr(self, "_doc_cache", None) is not None:
            return self._doc_cache
        lines = [self.doc.strip() or "%s operator." % self.name, "",
                 "Parameters", "----------"]
        for n in self.sig.required:
            kind = ("aux state" if n in self.mutate_aux
                    else "required input")
            lines.append("%s : NDArray/Symbol (%s)" % (n, kind))
        if self.sig.variadic:
            lines.append("*data : NDArray/Symbol (variadic input)")
        for n in self.sig.optional:
            lines.append("%s : NDArray/Symbol (optional input)" % n)
        lines += [p.describe() for p in self.params.values()]
        if not callable(self.num_outputs) and self.num_outputs > 1:
            lines.append("")
            lines.append("Outputs: %d (%s aux write-back)"
                         % (self.num_outputs,
                            "%d" % len(self.mutate_aux)
                            if self.mutate_aux else "no"))
        self._doc_cache = "\n".join(lines)
        return self._doc_cache

    def __repr__(self):
        return "OpDef(%s)" % self.name


def register(name, *, num_outputs=1, aliases=(), needs_is_train=False,
             needs_rng=False, mutate_aux=(), attr_defaults=None,
             params=None, free_attrs=False):
    """Decorator: register a pure jax function as an operator.

    ``free_attrs=True`` opts the op out of unknown-kwarg rejection
    (reserved for genuinely open-ended attr surfaces)."""

    def _wrap(fn):
        op = OpDef(name, fn, num_outputs=num_outputs, aliases=aliases,
                   needs_is_train=needs_is_train, needs_rng=needs_rng,
                   mutate_aux=mutate_aux, attr_defaults=attr_defaults,
                   params=params, free_attrs=free_attrs)
        for n in (name,) + tuple(aliases):
            if n in _OP_REGISTRY:
                raise MXNetError("duplicate op registration: %s" % n)
            _OP_REGISTRY[n] = op
        return fn

    return _wrap


def get_op(name):
    op = _OP_REGISTRY.get(name)
    if op is None:
        raise MXNetError("operator %r is not registered" % name)
    return op


def has_op(name):
    return name in _OP_REGISTRY


def list_ops():
    """All canonical op names (aliases excluded)."""
    return sorted({op.name for op in _OP_REGISTRY.values()})


# ---------------------------------------------------------------------------
# attr coercion: symbol JSON and user kwargs carry attrs as strings
# ("(2,2)", "True", "1e-3"); normalize to python values so op fns can use
# them directly.  Mirrors dmlc::Parameter string parsing behaviourally.
# ---------------------------------------------------------------------------
_BOOL = {"true": True, "false": False, "True": True, "False": False}


def _coerce(v):
    if not isinstance(v, str):
        return v
    if v in _BOOL:
        return _BOOL[v]
    if v == "None":
        return None
    try:
        return ast.literal_eval(v)
    except (ValueError, SyntaxError):
        return v


def coerce_attrs(attrs):
    return {k: _coerce(v) for k, v in attrs.items()}


def normalize_tuple(x, n=None):
    """'(2,2)' | 2 | (2,2) -> tuple; broadcast scalars to length n.
    None elements pass through (dmlc::optional<int> parity — reference
    slice begin/end/step accept per-axis None, matrix_op-inl.h)."""
    x = _coerce(x)
    if isinstance(x, (list, tuple)):
        t = tuple(None if i is None else int(i) for i in x)
    else:
        t = (int(x),)
    if n is not None and len(t) == 1:
        t = t * n
    return t
