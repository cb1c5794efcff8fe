"""grafttune measurement — survivors timed in a bounded subprocess.

Each admissible candidate runs in its own interpreter (the bench.py
rider pattern): the candidate's knob values are applied as environment
overrides so the production bind sites resolve them exactly the way a
real process would, a fused-Adam step over a flat bucket is jitted and
timed, and two guards run alongside the clock:

- **bit parity** — the fused sweep's outputs must equal the per-array
  reference expression bit-for-bit (``fused_adam``'s documented
  contract); a candidate that is fast but wrong is a failure, not a
  winner;
- **recompile flatness** — a Python-level trace counter must read
  exactly 1 after repeated same-shape steps; a block size that
  retraces per call would win the single-step clock and lose the
  training run.

The clock is the clock of whatever the child ran on — by default the
CPU with the kernels under the Pallas INTERPRETER (the hermetic
setting below) — so every result names it (``platform``,
``interpret``): ``us_per_step`` is a device metric only where that says
``tpu`` and ``False``.

The subprocess is bounded by a wall timeout and always leaves exactly
one JSON line on stdout; any other exit (crash, hang, parity miss,
retrace) degrades to ``{"ok": False, "error": ...}`` — the driver
journals the failure and moves on, it never aborts the sweep.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

__all__ = ["measure_candidate", "SPEC_ENV"]

SPEC_ENV = "MXNET_TUNE_MEASURE_SPEC"

_REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

# the subprocess body: argv[1] = repo root, spec rides SPEC_ENV.
# The oracle mirrors _adam_kernel's expressions AND grouping (incl.
# the host-side double 1-beta) — the same construction fused_adam's
# bit-parity contract rests on.
_MEASURE_SRC = r"""
import json, os, sys, time
sys.path.insert(0, sys.argv[1])
spec = json.loads(os.environ["MXNET_TUNE_MEASURE_SPEC"])
import numpy as np
import jax
import jax.numpy as jnp
from mxnet_tpu.ops import pallas_kernels as pk

n = int(spec.get("n", 65536))
steps = int(spec.get("steps", 10))
warmup = int(spec.get("warmup", 2))
rng = np.random.RandomState(int(spec.get("seed", 0)))
w = jnp.asarray(rng.randn(n).astype(np.float32))
g = jnp.asarray(rng.randn(n).astype(np.float32))
m = jnp.zeros((n,), jnp.float32)
v = jnp.zeros((n,), jnp.float32)
LR, B1, B2, EPS, WD = 1e-3, 0.9, 0.999, 1e-8, 0.01

traces = [0]
def step(w, g, m, v):
    traces[0] += 1
    return pk.fused_adam(w, g, m, v, lr_eff=LR, beta1=B1, beta2=B2,
                         epsilon=EPS, wd=WD, rescale=1.0)
jstep = jax.jit(step)

def oracle(w, g, m, v):
    g2 = g * 1.0 + WD * w
    nm = B1 * m + (1.0 - B1) * g2
    nv = B2 * v + (1.0 - B2) * jnp.square(g2)
    nw = w - LR * nm / (jnp.sqrt(nv) + EPS)
    return nw, nm, nv

fused = jax.block_until_ready(jstep(w, g, m, v))
ref = jax.block_until_ready(jax.jit(oracle)(w, g, m, v))
parity = all(np.asarray(a).tobytes() == np.asarray(b).tobytes()
             for a, b in zip(fused, ref))
for _ in range(max(warmup - 1, 0)):
    jax.block_until_ready(jstep(w, g, m, v))
t0 = time.perf_counter()
for _ in range(steps):
    out = jstep(w, g, m, v)
jax.block_until_ready(out)
us = (time.perf_counter() - t0) / max(steps, 1) * 1e6
for _ in range(3):
    jax.block_until_ready(jstep(w, g, m, v))
print(json.dumps({"us_per_step": us, "parity": bool(parity),
                  "recompiles": traces[0],
                  "platform": jax.devices()[0].platform,
                  "interpret": bool(pk._interpret())}))
"""


def measure_candidate(candidate, space=None, n=65536, steps=10,
                      warmup=2, timeout=240.0, extra_env=None):
    """Measure one candidate; returns ``{"ok", "us_per_step",
    "parity", "recompiles", "platform", "interpret", "error"}``.

    ``space`` (a :class:`~.space.TunableSpace`) maps the candidate's
    knob names onto config env vars for the subprocess; without it the
    candidate is assumed to already be ``{ENV_NAME: value}``.
    """
    env = dict(os.environ)
    overrides = (space.env_overrides(candidate) if space is not None
                 else {str(k): (None if v is None else str(v))
                       for k, v in candidate.items()})
    for key, val in overrides.items():
        if val is None:
            env.pop(key, None)
        else:
            env[key] = val
    env.update(extra_env or {})
    # hermetic measurement: CPU interpret mode with the fused family
    # forced on (how tier-1 exercises the kernels), and the tuning DB
    # disabled so the candidate's env is the ONLY knob source
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["MXNET_PALLAS_FUSED_OPT"] = "1"
    env["MXNET_TUNE"] = "0"
    env[SPEC_ENV] = json.dumps({"n": int(n), "steps": int(steps),
                                "warmup": int(warmup)})
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _MEASURE_SRC, _REPO],
            env=env, capture_output=True, text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        return {"ok": False, "us_per_step": None, "parity": None,
                "recompiles": None,
                "error": "timeout after %.0fs" % timeout}
    lines = [ln for ln in (proc.stdout or "").splitlines()
             if ln.strip()]
    if proc.returncode != 0 or not lines:
        return {"ok": False, "us_per_step": None, "parity": None,
                "recompiles": None,
                "error": "rc=%d stderr=%s" % (
                    proc.returncode, (proc.stderr or "")[-400:])}
    try:
        out = json.loads(lines[-1])
    except ValueError:
        return {"ok": False, "us_per_step": None, "parity": None,
                "recompiles": None,
                "error": "unparseable output %r" % lines[-1][:200]}
    ok = bool(out.get("parity")) and out.get("recompiles") == 1 \
        and float(out.get("us_per_step") or 0) > 0
    err = None
    if not out.get("parity"):
        err = "bit-parity failure vs the tree_map oracle"
    elif out.get("recompiles") != 1:
        err = "recompile count %s != 1 (retrace per step)" \
            % out.get("recompiles")
    return {"ok": ok, "us_per_step": out.get("us_per_step"),
            "parity": out.get("parity"),
            "recompiles": out.get("recompiles"),
            "platform": out.get("platform"),
            "interpret": out.get("interpret"), "error": err}
