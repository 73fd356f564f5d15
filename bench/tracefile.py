"""Reduce a profiler trace of the traced window to device numbers.

The harness wraps the traced rounds in one host annotation,
``bench.traced``, and every host phase inside it in its own
``bench.<phase>`` annotation.  From the ``.xplane.pb`` this reads:

* the window: the ``bench.traced`` span, in the trace's own clock;
* device busy time: the union of the intervals of the ``XLA Ops`` line
  of each ``/device:<platform>:<n>`` plane, clipped to the window and
  averaged over the device planes that ran anything;
* program executions: the ``XLA Modules`` line's events by name (a
  jitted program's module is named after its Python function, e.g.
  ``jit_run``), each with its device duration;
* the device ops that took most time, summed by name over leaf ops
  (an op that encloses others on the line, such as a ``while`` loop,
  is left out so that nothing counts twice), the name cut to the HLO
  instruction's own (``%fusion.12``);
* the idle gaps of the first device, each named after the host phase
  that overlaps it most.

Only the process that holds the chip can trace it, so the trace is
always of this run.
"""
from __future__ import annotations

import glob
import os

WINDOW_SPAN = "bench.traced"
HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name \
        and "SparseCore" not in name


def reduce(profile, top: int = 10) -> dict:
    """``profile`` is a ``jax.profiler.ProfileData``.  Times in seconds."""
    host, planes = [], []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
        elif _is_device_plane(plane.name):
            planes.append(plane)
    spans = [h for h in host if h[0] == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(spans)}")
    w0, w1 = spans[0][1], spans[0][2]

    busy, modules, ops, first_union = [], {}, {}, None
    for plane in planes:
        ivs = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                s = max(ev.start_ns, w0)
                e = min(ev.start_ns + ev.duration_ns, w1)
                if e <= s:
                    continue
                if line.name == OPS_LINE:
                    ivs.append((s, e, ev.name))
                else:
                    modules.setdefault(ev.name, []).append(
                        ev.duration_ns * 1e-9)
        ivs.sort(key=lambda x: (x[0], -x[1]))
        for i, (s, e, name) in enumerate(ivs):
            if i + 1 < len(ivs) and ivs[i + 1][0] < e:
                continue                  # encloses the next op: not a leaf
            key = name.split(" = ")[0]
            ops[key] = ops.get(key, 0.0) + (e - s)
        ivs = [(s, e) for s, e, _ in ivs]
        if not ivs:
            continue
        u = _union(ivs)
        busy.append(sum(e - s for s, e in u))
        if first_union is None:
            first_union = u

    gaps = []
    if first_union is not None:
        edges = [w0] + [x for iv in first_union for x in iv] + [w1]
        phases = [h for h in host if h[0] != WINDOW_SPAN]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            best, name = 0, "untraced host time"
            for pname, ps, pe in phases:
                ov = min(e, pe) - max(s, ps)
                if ov > best:
                    best, name = ov, pname
            gaps.append([name, (e - s) * 1e-9])
    gaps.sort(key=lambda g: -g[1])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return dict(
        window_s=(w1 - w0) * 1e-9,
        busy_s=(sum(busy) / len(busy) * 1e-9) if busy else 0.0,
        n_devices=len(busy),
        modules=modules,
        device_ops=[[k, v * 1e-9] for k, v in top_ops],
        idle_gaps=gaps[:top],
    )

