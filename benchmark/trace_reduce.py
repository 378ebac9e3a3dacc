"""From a profiler trace (`.xplane.pb`) to device busy time, kernel time
and the `breakdown` lists.  Read with `jax.profiler.ProfileData` alone.

Planes named `/device:TPU:<n>` are chips; their `XLA Ops` line holds one
event per device operation and `XLA Modules` one per compiled program.
`/host:CPU` lines hold the host's annotated spans (`TraceAnnotation`
from the benchmark's client, the runtime's own `TraceMe`s).

* busy: the union of the op intervals of one chip, averaged over chips;
* kernel time: the module events whose name holds a registered kernel's
  name, averaged over chips;
* idle gaps: the spaces between busy intervals on the first chip, each
  given to the host span that overlaps it most (the client's own waiting
  spans only when nothing else does).
"""
from __future__ import annotations

import glob
import os

DEVICE_PREFIXES = ("/device:TPU:",)
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_WAITING = ("bench.wait", "bench.idle")


def find_trace(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _events(line):
    """(name, start, end) in ns; an op's name is its HLO instruction up to
    the `=` (`%while.67`), not the whole instruction text."""
    return [(e.name.split(" = ")[0][:64], float(e.start_ns),
             float(e.start_ns + e.duration_ns))
            for e in line.events if e.duration_ns > 0]


def reduce_trace(path: str, kernels: dict) -> dict:
    """`kernels`: {key: substring of the compiled program's name}.
    Returns busy_s, chips, kernel_s per key, device_ops and idle_gaps
    (each at most 10 [name, seconds] pairs)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    chips, host = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIXES):
            lines = {ln.name: ln for ln in plane.lines}
            chips.append((_events(lines[OPS_LINE]) if OPS_LINE in lines
                          else [],
                          _events(lines[MODULES_LINE])
                          if MODULES_LINE in lines else []))
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                host.extend(_events(ln))
    if not chips:
        return {"chips": 0, "busy_s": 0.0, "kernel_s": {},
                "device_ops": [], "idle_gaps": []}
    n = len(chips)
    busy, per_op, kernel = 0.0, {}, {k: 0.0 for k in kernels}
    for ops, modules in chips:
        busy += sum(e - s for s, e in _union([(s, e) for _, s, e in ops]))
        for name, s, e in ops:
            per_op[name] = per_op.get(name, 0.0) + (e - s)
        for name, s, e in modules:
            for key, needle in kernels.items():
                if needle in name:
                    kernel[key] += e - s
    ops0 = _union([(s, e) for _, s, e in chips[0][0]])
    gaps = [(a[1], b[0]) for a, b in zip(ops0, ops0[1:]) if b[0] > a[1]]
    gaps.sort(key=lambda g: g[0] - g[1])
    blame: dict = {}
    host.sort(key=lambda ev: ev[1])
    host = [ev for ev in host if ev[2] - ev[1] >= 20_000.0]
    for gs, ge in gaps[:200]:
        best, second = {}, {}
        for name, s, e in host:
            if s >= ge:
                break
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                d = second if name.startswith(_WAITING) else best
                d[name] = d.get(name, 0.0) + ov
        pick = best or second
        who = max(pick, key=pick.get) if pick else "no host span"
        blame[who] = blame.get(who, 0.0) + (ge - gs)
    def top(d: dict) -> list:
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"chips": n, "busy_s": busy / n / 1e9,
            "kernel_s": {k: v / n / 1e9 for k, v in kernel.items()},
            "device_ops": top({k: v / n for k, v in per_op.items()}),
            "idle_gaps": top(blame)}
