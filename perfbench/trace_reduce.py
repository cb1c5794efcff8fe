"""From the jax profiler's xplane to numbers: device busy and idle time,
device time of operations by name, idle gaps by what the host was doing.

Read with ``jax.profiler.ProfileData`` alone.  On a TPU the file holds
one plane per chip (``/device:TPU:<n>``) whose line ``XLA Ops`` carries
one event per executed HLO operation (a Pallas kernel appears under the
``name=`` its ``pallas_call`` was given) and whose line ``XLA Modules``
carries one event per executed program; the host's threads are lines of
the plane ``/host:CPU`` and carry the benchmark's own annotations
(``pb.window``, ``pb.step``, ``pb.wait``, ``pb.block``) on the same clock.

Every per-layer metric reads the dictionary :func:`reduce` returns; the
metric files hold only their own event-name patterns and arithmetic.
"""
import glob
import os
import re
import shutil

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPANS = ("pb.wait", "pb.step", "pb.block")
WINDOW_SPAN = "pb.window"


class Recording:
    """``with Recording(dir) as rec:`` traces the block with the jax
    profiler into a fixed directory inside the checkout."""

    def __init__(self, directory, keep=False):
        self.dir, self.keep = directory, keep

    def __enter__(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        return False

    def xplane_path(self):
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise RuntimeError("the profiler wrote no xplane under %s"
                               % self.dir)
        return found[-1]

    def cleanup(self):
        if not self.keep:
            shutil.rmtree(self.dir, ignore_errors=True)


def merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_length(intervals):
    """Total length covered by [(start, end)] (any order, may nest)."""
    return sum(e - s for s, e in merged(intervals))


def _clip(events, lo, hi):
    out = []
    for name, s, e in events:
        s2, e2 = max(s, lo), min(e, hi)
        if e2 > s2:
            out.append((name, s2, e2))
    return out


_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(hlo, width=96):
    """An XLA op event is named by its whole HLO line; keep the op's
    name, its result type without layouts and its opcode."""
    if " = " not in hlo:
        return hlo[:width]
    name, rhs = hlo.split(" = ", 1)
    return (name.lstrip("%") + " " + _LAYOUT.sub("", rhs))[:width]


def opcode(hlo):
    """The HLO opcode of an op event's name (``fusion``, ``copy``,
    ``custom-call``, ``all-reduce-start`` …); fusions carry their kind
    (``fusion:kOutput`` holds a convolution or a matrix product,
    ``kInput`` a reduction, ``kLoop`` elementwise work) and custom
    calls the kernel's name where the op is named after it."""
    if " = " not in hlo:
        return hlo
    name, rhs = hlo.split(" = ", 1)
    if rhs.startswith("("):             # a tuple type: skip to its close
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rhs = rhs[i + 1:].lstrip()
                break
    else:
        rhs = rhs.split(" ", 1)[1] if " " in rhs else rhs
    op = rhs.split("(", 1)[0].strip()
    if op == "fusion":
        kind = re.search(r"kind=(k\w+)", hlo)
        return "fusion:" + (kind.group(1) if kind else "?")
    if op == "custom-call":
        kernel = re.search(r"(_[a-z0-9]+(?:_[a-z0-9]+)*_kernel)", name)
        return "custom-call:" + (kernel.group(1) if kernel else "other")
    return op


def read_planes(path):
    """{"devices": {idx: {"ops": [(name, s, e)], "modules": [...]}},
    "host": {span name: [(s, e)]}} in nanoseconds."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    dev[key].append((ev.name, s, s + int(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS or ev.name == WINDOW_SPAN:
                        s = int(ev.start_ns)
                        host.setdefault(ev.name, []).append(
                            (s, s + int(ev.duration_ns)))
    return {"devices": devices, "host": host}


def reduce(path, chips=1, top=10):
    """The traced window, reduced.  Times in seconds:

    ``window_s``  length of the traced window (the ``pb.window`` span);
    ``busy_s``    union of the device-op intervals, mean over the chips;
    ``busy_by_device``, ``ops_by_device`` ({dev: [(name, s, e)]}, ns),
    ``modules_by_device``, ``host`` — for the metric readers;
    ``breakdown`` {"device_ops": [[kind of op, s]], "idle_gaps":
    [[what the host was doing, s]]}, seconds over the whole window.
    """
    planes = read_planes(path)
    devs = planes["devices"]
    if not devs:
        raise RuntimeError("the trace holds no /device:TPU plane")
    if len(devs) < chips:
        raise RuntimeError("the trace holds %d device planes, the cell "
                           "uses %d chips" % (len(devs), chips))
    win = planes["host"].get(WINDOW_SPAN)
    if not win:
        raise RuntimeError("the trace holds no %s span" % WINDOW_SPAN)
    lo, hi = win[0]
    used = sorted(devs)[:chips]
    ops = {d: _clip(devs[d]["ops"], lo, hi) for d in used}
    mods = {d: _clip(devs[d]["modules"], lo, hi) for d in used}
    busy = {d: union_length([(s, e) for _n, s, e in ops[d]]) for d in used}
    if not any(busy.values()):
        raise RuntimeError("no operation ran on the device inside the "
                           "traced window")

    # device time by kind of operation (opcode; fusions by their kind,
    # Pallas kernels by name), mean over the chips; each row names its
    # heaviest single operation
    kinds = {}
    for d in used:
        for name, s, e in ops[d]:
            k = kinds.setdefault(opcode(name), {"t": 0, "n": set(), "ops": {}})
            k["t"] += e - s
            k["n"].add(name)
            k["ops"][name] = k["ops"].get(name, 0) + (e - s)
    device_ops = []
    for kind, k in sorted(kinds.items(), key=lambda kv: -kv[1]["t"])[:top]:
        heavy = max(k["ops"], key=k["ops"].get)
        label = "%s (%d ops; heaviest %s, %.0f%% of the kind)" % (
            kind, len(k["n"]), short_name(heavy, 60),
            100.0 * k["ops"][heavy] / k["t"])
        device_ops.append((label, k["t"]))

    # idle gaps on the first chip, by what the host was doing then
    first = used[0]
    gaps, cursor = [], lo
    for s, e in merged([(s, e) for _n, s, e in ops[first]]):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    spans = [(s, e, n) for n in HOST_SPANS
             for s, e in planes["host"].get(n, [])]
    idle = {}
    for gs, ge in gaps:
        mid = (gs + ge) / 2.0
        what = "host: outside the benchmark's spans"
        for s, e, n in spans:
            if s <= mid <= e:
                what = "host: in " + n
                break
        idle[what] = idle.get(what, 0) + (ge - gs)
    idle_gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    longest = max((ge - gs for gs, ge in gaps), default=0)

    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(busy.values()) / len(used) * ns,
        "busy_by_device": {d: busy[d] * ns for d in used},
        "ops_by_device": ops, "modules_by_device": mods,
        "host": planes["host"], "window_ns": (lo, hi),
        "longest_gap_s": longest * ns,
        "breakdown": {
            "device_ops": [[n, t * ns / len(used)] for n, t in device_ops],
            "idle_gaps": [[n, t * ns] for n, t in idle_gaps]},
    }


def event_seconds(reduced, pattern, device=None):
    """Summed device time (s) of the ops whose name matches ``pattern``
    (a compiled regex), on one device (default: the first), and how
    many events matched."""
    ops = reduced["ops_by_device"]
    d = sorted(ops)[0] if device is None else device
    total = n = 0
    for name, s, e in ops[d]:
        if pattern.search(name):
            total += e - s
            n += 1
    return total * 1e-9, n


def dump(path, out, limit=60):
    """What one trace holds, for a reader's eye: planes, lines, and the
    commonest event names of each line."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    with open(out, "w") as f:
        for plane in pd.planes:
            f.write("PLANE %s\n" % plane.name)
            for line in plane.lines:
                evs = list(line.events)
                f.write("  LINE %r events=%d\n" % (line.name, len(evs)))
                agg = {}
                for ev in evs:
                    a = agg.setdefault(ev.name, [0, 0, None])
                    a[0] += 1
                    a[1] += int(ev.duration_ns)
                    if a[2] is None:
                        a[2] = int(ev.start_ns)
                for name, (n, dur, s0) in sorted(
                        agg.items(), key=lambda kv: -kv[1][1])[:limit]:
                    f.write("    %9d x %12.3f us  first@%d  %s\n"
                            % (n, dur / 1e3, s0, name[:140]))
