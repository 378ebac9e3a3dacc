"""From a profiler trace (`.xplane.pb`) to device busy time, kernel time
and the `breakdown` lists.  Read with `jax.profiler.ProfileData` alone.

Planes named `/device:TPU:<n>` are chips; their `XLA Ops` line holds one
event per device operation and `XLA Modules` one per compiled program.
`/host:CPU` lines hold the host's annotated spans (`TraceAnnotation`
from the benchmark's client, the runtime's own `TraceMe`s).

* busy: the union of the op intervals of one chip, averaged over chips;
* kernel time: the module events whose name holds a registered kernel's
  name, averaged over chips;
* idle gaps: the spaces between busy intervals on the first chip, cut
  where a host event starts or ends; each slice is shared by the host
  threads that have an event open in it (`blame`), so the list adds up
  to the gaps' own length.
"""
from __future__ import annotations

import glob
import os

DEVICE_PREFIXES = ("/device:TPU:",)
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# whose claim on an idle slice counts, in this order: a program or
# runtime event; the benchmark client's own spans; the client waiting
_CLIENT = "bench."
_WAITING = ("bench.wait", "bench.idle")
NO_SPAN = "no host span"


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


def _rank(name: str) -> int:
    if not name.startswith(_CLIENT):
        return 0
    return 2 if name.startswith(_WAITING) else 1


def blame(gaps: list, events_by_line: dict) -> dict:
    """{name: time} of the idle `gaps` ((start, end), disjoint) by what the
    host was doing.  `events_by_line`: {line (a thread): [(name, start,
    end)]}.  Every gap is cut at the starts and ends of the events inside
    it.  In a slice a thread claims by its innermost open event, and the
    slice's length is split evenly among the threads whose claim is no
    `bench.*` name; where there is none, among the `bench.*` claims, the
    waiting ones (`bench.wait`, `bench.idle`) last; where no thread has
    an event open, it goes to `no host span`.  The values add up to the
    gaps' length.  One sweep over the sorted event edges."""
    edges = []
    for line, events in events_by_line.items():
        for i, (name, s, e) in enumerate(events):
            if e > s:
                edges.append((s, 1, line, i, name))
                edges.append((e, 0, line, i, name))
    edges.sort(key=lambda x: x[:2])       # at one instant: ends, then starts
    open_on = {line: {} for line in events_by_line}   # line: {i: name}
    claims = [{}, {}, {}]                 # by rank: {name: threads claiming}
    out: dict = {}

    def move(line, sign):
        held = open_on[line]
        if held:
            # events of a thread nest, so the last opened is the innermost
            name = held[next(reversed(held))]
            by_name = claims[_rank(name)]
            n = by_name.get(name, 0) + sign
            if n:
                by_name[name] = n
            else:
                del by_name[name]

    def give(t0, t1):
        if t1 <= t0:
            return
        share = next((c for c in claims if c), None)
        if share is None:
            out[NO_SPAN] = out.get(NO_SPAN, 0.0) + (t1 - t0)
            return
        each = (t1 - t0) / sum(share.values())
        for name, threads in share.items():
            out[name] = out.get(name, 0.0) + each * threads

    k = 0
    for gs, ge in sorted(gaps):
        at = gs
        while k < len(edges) and edges[k][0] < ge:
            t, start, line, i, name = edges[k]
            k += 1
            if t > at:
                give(at, t)
                at = t
            move(line, -1)
            if start:
                open_on[line][i] = name
            else:
                open_on[line].pop(i, None)
            move(line, +1)
        give(at, ge)
    return out


def reduce_trace(path: str, kernels: dict) -> dict:
    """`kernels`: {key: substring of the compiled program's name}.
    Returns busy_s, chips, kernel_s per key, device_ops and idle_gaps
    (each at most 10 [name, seconds] pairs)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    chips, host = [], {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIXES):
            lines = {ln.name: ln for ln in plane.lines}
            chips.append((_events(lines[OPS_LINE]) if OPS_LINE in lines
                          else [],
                          _events(lines[MODULES_LINE])
                          if MODULES_LINE in lines else []))
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                host[f"{ln.name}#{len(host)}"] = _events(ln)
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

    def top(d: dict) -> list:
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"chips": n, "busy_s": busy / n / 1e9,
            "kernel_s": {k: v / n / 1e9 for k, v in kernel.items()},
            "device_ops": top({k: v / n for k, v in per_op.items()}),
            "idle_gaps": top(blame(gaps, host))}
