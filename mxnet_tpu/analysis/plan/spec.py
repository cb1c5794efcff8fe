"""PlanSpec — the declarative description of one bound tensor program.

Everything graftplan analyzes arrives through this class: a pure-data
value (nested dicts/lists/ints/strings, json-roundtrippable) so the
analyzer, the checkers, and the seeded-misconfiguration test fixtures
never need a device, a mesh object, or an XLA compile.  The live
subsystems *export* their plan declaratively — ``ParallelTrainer.
plan_spec()``, ``ModelServer.plan_spec()``, ``Executor.program_plan()``
— and :meth:`PlanSpec.from_trainer` et al. just repackage those
exports.

Vocabulary:

- ``mesh``    — :class:`MeshSpec`: ordered ``(axis, size)`` pairs;
- ``params``  — one row per parameter: name, shape, dtype itemsize,
  trainable, partition spec (per-dim ``None`` or list of mesh axes —
  the serialized ``PartitionSpec``), and whether the param rides the
  fused bucket path;
- ``buckets`` — the gradient bucket plan (``parallel.collectives.
  build_bucket_plan`` serialized): names/shapes/sizes/offsets, the
  bucket's ``layout`` (``flat`` 1-D or ``native`` ``buffer_shape``) and the
  mesh-padded flat length;
- ``optimizer`` — the slot spec (``PureSGD.slot_spec()`` /
  ``PureAdam.slot_spec()``): per-param slot names plus scalar slots
  with their byte sizes;
- ``codec``   — gradient-compression wire model (name + params);
- ``graph`` / ``graph_inputs`` — optional symbol JSON + input shapes
  for activation-liveness analysis (:mod:`.shapes` / :mod:`.memory`);
- ``ladder``  — the serving shape-bucket ladder (serving specs);
- ``hbm_budget`` — optional per-chip byte budget this config must fit
  (defaults from ``MXNET_PLAN_HBM_BYTES`` at check time).
"""
from __future__ import annotations

import json

__all__ = ["MeshSpec", "PlanSpec", "normalize_pspec"]


class MeshSpec:
    """Ordered named mesh axes, as pure data."""

    __slots__ = ("axes",)

    def __init__(self, axes):
        # axes: mapping or iterable of (name, size); insertion order is
        # the mesh's axis order
        if hasattr(axes, "items"):
            axes = list(axes.items())
        self.axes = [(str(a), int(s)) for a, s in axes]

    @property
    def size(self):
        n = 1
        for _a, s in self.axes:
            n *= s
        return n

    @property
    def names(self):
        return tuple(a for a, _s in self.axes)

    def axis_size(self, name):
        for a, s in self.axes:
            if a == name:
                return s
        raise KeyError("mesh has no axis %r (axes: %s)"
                       % (name, list(self.names)))

    def factor(self, entry):
        """How many ways one PartitionSpec entry splits a dim: the
        product of its axis sizes (``None`` -> 1)."""
        if entry is None:
            return 1
        axes = entry if isinstance(entry, (list, tuple)) else (entry,)
        f = 1
        for a in axes:
            f *= self.axis_size(a)
        return f

    def to_dict(self):
        return {"axes": [[a, s] for a, s in self.axes]}

    @classmethod
    def from_dict(cls, d):
        return cls(d["axes"])

    def __repr__(self):
        return "MeshSpec(%s)" % ("x".join("%s=%d" % ax for ax in self.axes))


def normalize_pspec(spec, ndim):
    """Serialize a jax PartitionSpec (or an already-plain list) into
    ``ndim`` entries of ``None`` | ``[axis, ...]`` — THE one
    serialization rule; ``ParallelTrainer.plan_spec`` routes through
    here so captured and hand-built specs can never disagree."""
    entries = list(spec) if spec is not None else []
    out = []
    for i in range(ndim):
        e = entries[i] if i < len(entries) else None
        if e is None:
            out.append(None)
        elif isinstance(e, (list, tuple)):
            out.append([str(a) for a in e])
        else:
            out.append([str(e)])
    return out


class PlanSpec:
    """One bound program, declaratively.  See the module docstring for
    the field vocabulary; every field is plain data."""

    FIELDS = ("name", "kind", "origin", "mesh", "params", "zero",
              "optimizer", "buckets", "codec", "batch", "param_gather",
              "graph", "graph_inputs", "ladder", "manifest_ladders",
              "generative", "hbm_budget")

    def __init__(self, name, kind, origin, mesh=None, params=(),
                 zero=0, optimizer=None, buckets=(), codec=None,
                 batch=None, param_gather=True, graph=None,
                 graph_inputs=None, ladder=None, manifest_ladders=None,
                 generative=None, hbm_budget=None):
        self.name = str(name)
        self.kind = str(kind)          # trainer | serving | program
        self.origin = str(origin)      # repo-relative finding anchor
        self.mesh = mesh
        self.params = [dict(p) for p in params]
        self.zero = int(zero)
        self.optimizer = dict(optimizer or {"slots": [],
                                            "scalar_slots": []})
        self.buckets = [dict(b) for b in buckets]
        self.codec = dict(codec) if codec else None
        self.batch = dict(batch) if batch else None
        self.param_gather = bool(param_gather)
        self.graph = graph             # symbol-JSON dict or None
        self.graph_inputs = dict(graph_inputs or {})
        self.ladder = list(ladder) if ladder is not None else None
        # {tag: ladder} — the warmup manifest's recorded working sets,
        # each judged like the configured ladder (a restarted replica
        # warms THOSE buckets)
        self.manifest_ladders = {str(k): list(v) for k, v
                                 in (manifest_ladders or {}).items()}
        # {model: entry} — ModelServer.plan_spec()["generative"]: the
        # decode/prefill ladders and KV-cache geometry of generative
        # deployments, judged by contracts.generative_report
        self.generative = {str(k): dict(v) for k, v
                           in (generative or {}).items()}
        self.hbm_budget = None if hbm_budget is None else int(hbm_budget)

    # -- plain-data round trip (test fixtures ride this) --------------------
    def to_dict(self):
        d = {f: getattr(self, f) for f in self.FIELDS}
        d["mesh"] = self.mesh.to_dict() if self.mesh is not None else None
        return d

    @classmethod
    def from_dict(cls, d):
        d = dict(d)
        mesh = d.get("mesh")
        d["mesh"] = MeshSpec.from_dict(mesh) if mesh else None
        return cls(**{f: d.get(f) for f in cls.FIELDS
                      if d.get(f) is not None or f in ("mesh",)})

    def to_json(self):
        return json.dumps(self.to_dict(), indent=1, sort_keys=True)

    @classmethod
    def from_json(cls, s):
        return cls.from_dict(json.loads(s))

    # -- capture from live objects (lazy imports: the spec layer itself
    # -- stays importable in a tree whose jax is broken) ---------------------
    @classmethod
    def from_trainer(cls, trainer, name="trainer", graph=None,
                     graph_inputs=None, hbm_budget=None):
        """Capture a live :class:`~mxnet_tpu.parallel.ParallelTrainer`'s
        declarative plan (``trainer.plan_spec()``)."""
        d = trainer.plan_spec()
        return cls(name=name, kind="trainer",
                   origin="mxnet_tpu/parallel/trainer.py",
                   mesh=MeshSpec(d["mesh"]), params=d["params"],
                   zero=d["zero"], optimizer=d["optimizer"],
                   buckets=d["buckets"], codec=d["codec"],
                   batch=d.get("batch"), graph=graph,
                   graph_inputs=graph_inputs, hbm_budget=hbm_budget)

    @classmethod
    def from_server(cls, server, name="serving"):
        """Capture a :class:`~mxnet_tpu.serving.ModelServer`'s bucket
        ladder, the warmup manifest's recorded working sets, AND any
        generative deployments' decode/prefill ladders
        (``server.plan_spec()``) — bucket-plan-waste judges all of
        them, and the generative KV-cache bytes enter the memory
        model."""
        d = server.plan_spec()
        return cls(name=name, kind="serving",
                   origin="mxnet_tpu/serving/server.py",
                   ladder=d["ladder"],
                   manifest_ladders=d.get("manifest_ladders"),
                   generative=d.get("generative"))

    @classmethod
    def from_ladder(cls, ladder, name="serving/ladder",
                    origin="mxnet_tpu/serving/bucketing.py"):
        return cls(name=name, kind="serving", origin=origin,
                   ladder=list(ladder))

    @classmethod
    def from_executor(cls, exe, name="program", mesh=None,
                      hbm_budget=None):
        """Capture a bound :class:`~mxnet_tpu.executor.Executor`'s
        program (``exe.program_plan()``): symbol JSON + bound shapes."""
        d = exe.program_plan()
        return cls(name=name, kind="program",
                   origin="mxnet_tpu/executor.py", mesh=mesh,
                   params=d["params"], graph=d["graph"],
                   graph_inputs=d["inputs"], hbm_budget=hbm_budget)

    def __repr__(self):
        return ("PlanSpec(%s: %s, %d params, zero=%d, %d buckets%s)"
                % (self.kind, self.name, len(self.params), self.zero,
                   len(self.buckets),
                   ", ladder=%s" % self.ladder if self.ladder else ""))
