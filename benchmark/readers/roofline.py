"""A placement program's share of its memory roofline: the least bytes
its steps must move (a function of the shapes, `benchmark/roofline.py`)
over the chip's HBM bandwidth (`benchmark/peaks.json`, keyed by
device_kind; an unknown kind is an error), over the program's device
time in the trace.  No device time or no steps: None, never 0."""
import json
import os

from benchmark import roofline

_PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def read(facts: dict, spec: dict):
    kernel_s = facts.get(f"trace.kernel_s.{spec['kernel']}")
    steps = sum(facts.get(k, 0.0) for k in spec["steps"])
    if not kernel_s or steps <= 0:
        return None
    with open(_PEAKS) as f:
        peaks = json.load(f)
    kind = facts["device.kind"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    least = getattr(roofline, spec["bytes"])(
        int(facts["shape.rows"]), int(facts["shape.resource_dims"]))
    floor_s = steps * least / peaks[kind]["hbm_bytes_per_s"]
    return 100.0 * floor_s / kernel_s
