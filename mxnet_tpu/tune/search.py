"""grafttune search driver — seeded, resumable, statically pruned.

The proposal stream is a pure function of ``(seed, k)``: candidate 0
is the space's default (the incumbent must always be priced and
measured), an exploration prefix draws each knob independently from a
per-knob sha256 digest, and the remainder mutates the best candidate
seen so far one knob at a time (the random + mutation-neighborhood
schedule; no wall clock, no ``random`` module, no global state — the
same seed replays the same sweep on any machine).

Every proposal is journaled to one JSONL line *before* the next is
drawn, so a killed sweep resumes mid-stream: :func:`run_sweep` replays
the journal to rebuild its dedup set, prune histogram, cost frontier,
and best-so-far, then continues at the next ``k`` — already-judged
candidates are never re-judged, already-measured candidates never
re-measured.

Candidates flow propose -> static prune (:func:`~.prune.judge`; the
killing rules are journaled, nothing compiles) -> measure (injected
callable, typically :func:`~.measure.measure_candidate`) -> commit:
the winner's values are regrouped per tuning-DB program
(:meth:`~.space.TunableSpace.by_program`) and stored via :mod:`.db`
for ``config.tuned`` to resolve at bind time.

Counters: ``mxnet_tune_candidates_total{outcome=pruned|measured|won}``
and ``mxnet_tune_prune_rules_total{rule=...}`` — recorded
unconditionally (this is an offline loop, not a hot path).
"""
from __future__ import annotations

import hashlib
import json
import os

from .prune import judge
from .space import candidate_key

__all__ = ["propose", "run_sweep", "MESHED_PROGRAMS"]

# tuning-DB programs whose bind site keys by mesh shape (the trainer
# passes its live mesh to config.tuned); every other program binds
# mesh-less
MESHED_PROGRAMS = frozenset(("parallel-trainer",))


def _digest_int(seed, k, salt):
    h = hashlib.sha256(("%s:%d:%s" % (seed, k, salt)).encode()).digest()
    return int.from_bytes(h[:8], "big")


def propose(space, seed, k, best=None, explore=8):
    """Candidate ``k`` of the stream: 0 = the default, ``k < explore``
    (or no ``best`` yet) = independent per-knob random draw, else a
    single-knob mutation of ``best``."""
    if k == 0:
        return space.default_candidate()
    if best is None or k < int(explore):
        return {kn.name: kn.domain[_digest_int(seed, k, kn.name)
                                   % len(kn.domain)]
                for kn in space}
    cand = dict(best)
    names = space.names
    pick = names[_digest_int(seed, k, "knob") % len(names)]
    kn = space.knob(pick)
    idx = kn.domain.index(cand[pick]) if cand[pick] in kn.domain else 0
    step = 1 if _digest_int(seed, k, "dir") % 2 else -1
    cand[pick] = kn.domain[(idx + step) % len(kn.domain)]
    return cand


def _bump(name, help_, **labels):
    from .. import telemetry
    c = telemetry.counter(name, help_)
    (c.labels(**labels) if labels else c).inc()


def _count_candidate(outcome):
    _bump("mxnet_tune_candidates_total",
          "grafttune candidates by outcome: pruned (killed statically, "
          "never compiled/measured), measured (survived pruning and "
          "ran), won (committed to the tuning DB)", outcome=outcome)


def _count_rule(rule):
    _bump("mxnet_tune_prune_rules_total",
          "grafttune static prunes by the rule that killed the "
          "candidate (the prune-verdict histogram)", rule=rule)


def _append(journal, record):
    if journal is None:
        return
    with open(journal, "a") as f:
        f.write(json.dumps(record, sort_keys=True) + "\n")


def _replay(journal):
    """Rebuild sweep state from an existing journal (resume path).
    Malformed trailing lines (a sweep killed mid-write) are dropped —
    the next run re-proposes from the last complete record."""
    state = {"next_k": 0, "seen": set(), "records": [],
             "prune_rules": {}, "counts": {"proposed": 0, "pruned": 0,
                                           "measured": 0, "failed": 0,
                                           "duplicates": 0,
                                           "admissible": 0},
             "best_cost": None, "best_measured": None,
             "default_us": None, "good_bytes": 0}
    if not journal or not os.path.exists(journal):
        return state
    with open(journal, "rb") as f:
        for raw in f:
            line = raw.decode("utf-8", "replace").strip()
            if line:
                try:
                    rec = json.loads(line)
                except ValueError:
                    break
                _apply(state, rec)
            state["good_bytes"] += len(raw)
    return state


def _apply(state, rec):
    """Fold one journal record into the sweep state — used both when
    replaying an old journal and as each new record is written, so the
    two paths cannot disagree."""
    state["records"].append(rec)
    state["next_k"] = max(state["next_k"], int(rec["k"]) + 1)
    cand = rec.get("candidate") or {}
    outcome = rec["outcome"]
    c = state["counts"]
    c["proposed"] += 1
    if outcome == "duplicate":
        c["duplicates"] += 1
        return
    state["seen"].add(candidate_key(cand))
    if outcome == "pruned":
        c["pruned"] += 1
        for rule in rec.get("rules") or ():
            state["prune_rules"][rule] = \
                state["prune_rules"].get(rule, 0) + 1
        return
    cost = rec.get("static_cost")
    if cost is not None and (state["best_cost"] is None
                             or cost < state["best_cost"]):
        state["best_cost"] = cost
    if outcome == "admissible":
        c["admissible"] += 1
    elif outcome == "failed":
        c["failed"] += 1
    elif outcome == "measured":
        c["measured"] += 1
        us = float(rec["us_per_step"])
        if int(rec["k"]) == 0:
            state["default_us"] = us
        best = state["best_measured"]
        if best is None or us < best["us_per_step"]:
            state["best_measured"] = {"candidate": dict(cand),
                                      "us_per_step": us,
                                      "k": int(rec["k"]),
                                      "platform": rec.get("platform"),
                                      "interpret": rec.get("interpret")}


def _mutation_base(state):
    """What mutation candidates perturb: the best measured candidate,
    else (prune-only sweeps) the cheapest admissible one."""
    if state["best_measured"] is not None:
        return state["best_measured"]["candidate"]
    best = None
    for rec in state["records"]:
        if rec["outcome"] in ("admissible", "measured") \
                and rec.get("static_cost") is not None:
            if best is None or rec["static_cost"] < best[0]:
                best = (rec["static_cost"], rec["candidate"])
    return best[1] if best else None


def run_sweep(space, context, budget=None, seed=None, prune_only=None,
              journal=None, measure=None, db_dir=None, db_meta=None,
              explore=8):
    """Run (or resume) one tuning sweep.  Returns the sweep summary::

        {"proposed", "pruned", "measured", "failed", "duplicates",
         "admissible", "prune_rules": {rule: n},
         "default_us_per_step", "winner": {candidate, us_per_step, k},
         "stored": [entry paths], "budget", "seed", "resumed_records"}

    ``measure`` is ``measure_candidate``-shaped: ``f(candidate) ->
    {"ok", "us_per_step", ...}``.  ``prune_only`` (or no ``measure``)
    stops after the static verdicts — the sweep still journals
    admissible candidates and their static costs, so a later run can
    measure them.  A winner is committed to the tuning DB only when
    something was measured.
    """
    from .. import config as _config
    budget = int(_config.get("MXNET_TUNE_BUDGET")
                 if budget is None else budget)
    seed = int(_config.get("MXNET_TUNE_SEED") if seed is None else seed)
    if prune_only is None:
        prune_only = bool(_config.get("MXNET_TUNE_PRUNE_ONLY"))
    ratio = float(context.get("cost_floor_ratio") or 0)
    state = _replay(journal)
    resumed = len(state["records"])
    if journal and os.path.exists(journal) \
            and os.path.getsize(journal) > state["good_bytes"]:
        # a sweep killed mid-write left a torn tail; cut back to the
        # last complete record so new appends cannot fuse with it
        with open(journal, "r+b") as f:
            f.truncate(state["good_bytes"])
    for k in range(state["next_k"], budget):
        cand = propose(space, seed, k, best=_mutation_base(state),
                       explore=explore)
        rec = {"k": k, "candidate": cand}
        if candidate_key(cand) in state["seen"]:
            rec["outcome"] = "duplicate"
        else:
            floor = None
            if ratio and state["best_cost"] is not None:
                floor = ratio * state["best_cost"]
            verdict = judge(cand, context, cost_floor=floor)
            rec["static_cost"] = verdict["static_cost"]
            if verdict["pruned"]:
                rec["outcome"] = "pruned"
                rec["rules"] = sorted({r["rule"]
                                       for r in verdict["records"]})
                rec["messages"] = [r["message"]
                                   for r in verdict["records"]]
                _count_candidate("pruned")
                for rule in rec["rules"]:
                    _count_rule(rule)
            elif prune_only or measure is None:
                rec["outcome"] = "admissible"
            else:
                m = measure(cand)
                if m.get("ok"):
                    rec["outcome"] = "measured"
                    rec["us_per_step"] = float(m["us_per_step"])
                    for extra in ("parity", "recompiles", "platform",
                                  "interpret"):
                        if extra in m:
                            rec[extra] = m[extra]
                    _count_candidate("measured")
                else:
                    rec["outcome"] = "failed"
                    rec["error"] = str(m.get("error"))
        _append(journal, rec)
        _apply(state, rec)
    winner = state["best_measured"]
    stored = []
    if winner is not None:
        _count_candidate("won")
        mesh = [[str(a), int(s)] for a, s in context.get("mesh") or ()]
        from . import db as _db
        for program, values in sorted(
                space.by_program(winner["candidate"]).items()):
            stored.append(_db.store(
                program, values, dirpath=db_dir,
                mesh_shape=mesh if program in MESHED_PROGRAMS else None,
                meta=dict(db_meta or {},
                          us_per_step=winner["us_per_step"],
                          seed=seed, k=winner["k"])))
    out = dict(state["counts"])
    out.update({"prune_rules": dict(state["prune_rules"]),
                "default_us_per_step": state["default_us"],
                "winner": winner, "stored": stored, "budget": budget,
                "seed": seed, "resumed_records": resumed})
    return out
