"""The one load generator: reads a traffic file, sends jobs through
`ApiClient.jobs.register` over HTTP, and sees them placed.

A traffic file (`benchmark/traffic/<name>.json`) gives the arrivals
(`closed` with a number of clients, or `open` at a fixed rate with its
`spacing`, "even" or "poisson"), the job shapes, the order of shapes as
a repeating block, the tenants, and what to warm.  Every seed sends the
same multiset of shapes (the block, permuted) and, in an open loop, the
same number of arrivals.

Seeing a job placed: one `/v1/event/stream` subscription on the
Evaluation topic wakes the client that owns the job when an eval of that
job reaches a final status; the client then reads the job's allocation
list once and stops its clock if every group is at its count.  No
polling loop sits between the scheduler's commit and the client's clock.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
_FINAL = {"complete", "failed", "canceled", "blocked"}
_FALLBACK_S = 2.0      # re-read a job this long after its last wake-up


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def shape_order(mix: dict, seed: int):
    """Endless (shape name, tenant) pairs: the block, permuted per pass."""
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    k = 0
    while True:
        for name in rng.permutation(mix["block"]):
            yield str(name), mix["tenants"][k % len(mix["tenants"])]
            k += 1


def arrivals(mix: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times of an open loop, `rate_per_s` x `seconds` of them, as
    the file's `spacing` says.  "even": one every 1/rate seconds.
    "poisson": a Poisson process conditioned on its count (sorted
    uniforms drawn from the seed)."""
    n = int(round(mix["rate_per_s"] * seconds))
    if mix["spacing"] == "even":
        return (np.arange(n) + 0.5) * (seconds / n)
    if mix["spacing"] == "poisson":
        rng = np.random.default_rng([int(seed), 0xA221])
        return np.sort(rng.uniform(0.0, seconds, n))
    raise ValueError(f"unknown spacing {mix['spacing']!r}")


class Record:
    __slots__ = ("spec", "phase", "due", "sent", "rtt", "done", "failed",
                 "stubs", "wake")

    def __init__(self, spec, phase, due):
        self.spec, self.phase, self.due = spec, phase, due
        self.sent = self.rtt = self.done = None
        self.failed = None
        self.stubs = []
        self.wake = threading.Event()


class Driver:
    """Sends jobs and observes them through ApiClient only.  `job_spec`
    (the reference's record of a job sent) and `build` (shape -> the
    program's Job) are the configuration's own, handed in by the harness."""

    def __init__(self, address: str, mix: dict, seed: int, job_spec, build,
                 span=None):
        from nomad_tpu.api.client import ApiClient
        self.mix = mix
        self.job_spec, self.build = job_spec, build
        self.apis = {ns: ApiClient(address, namespace=ns, timeout=120.0)
                     for ns in mix["tenants"]}
        self.order = shape_order(mix, seed)
        self.lock = threading.Lock()
        self.records: dict = {}
        self.count = 0
        self.deadline = float("inf")
        self.span = span or (lambda name: contextlib.nullcontext())
        self._watch = threading.Thread(target=self._observe, daemon=True)
        self._stop = False
        self._watch.start()

    # ------------------------------------------------------- observing

    def _observe(self) -> None:
        api = next(iter(self.apis.values()))
        while not self._stop:
            try:
                for frame in api.system.event_stream(
                        topics=["Evaluation"], timeout=600.0):
                    for ev in frame.get("Events", ()):
                        p = ev.get("Payload") or {}
                        if p.get("status") in _FINAL:
                            rec = self.records.get(p.get("job_id"))
                            if rec is not None:
                                if p["status"] == "failed":
                                    rec.failed = (p.get("status_description")
                                                  or "eval failed")
                                rec.wake.set()
                    if self._stop:
                        return
            except OSError:
                if self._stop:
                    return
                time.sleep(0.05)

    def close(self) -> None:
        self._stop = True

    # --------------------------------------------------------- sending

    def next_job(self, phase: str, due: float, name: str | None = None
                 ) -> Record:
        """The next job's record, registered for the observer before the
        job is sent.  `name` picks the shape (the warm pass); else the
        shape and tenant come from the mix's order."""
        with self.lock:
            self.count += 1
            if name is None:
                name, ns = next(self.order)
            else:
                ns = self.mix["tenants"][self.count % len(self.mix["tenants"])]
            job_id = f"{phase}{self.count:05d}-{name}"
            rec = Record(self.job_spec(job_id, ns, self.mix["shapes"][name]),
                         phase, due)
            self.records[job_id] = rec
        return rec

    def run_job(self, rec: Record) -> None:
        """Register, then wait until every group is at its count, the
        job's eval failed, or the drain deadline passed."""
        spec = rec.spec
        api = self.apis[spec.namespace]
        job = self.build(spec.shape, spec.id, spec.namespace)
        rec.sent = time.monotonic()
        try:
            with self.span("bench.register"):
                spec.registered = api.jobs.register(job)["JobModifyIndex"]
        except Exception as e:            # noqa: BLE001 - refused: counted
            rec.failed = f"register: {type(e).__name__}: {e}"
            return
        rec.rtt = time.monotonic() - rec.sent
        while rec.failed is None:
            left = self.deadline - time.monotonic()
            if left <= 0:
                rec.failed = "unfinished at the end of the drain"
                return
            with self.span("bench.wait"):
                rec.wake.wait(min(_FALLBACK_S, left))
            rec.wake.clear()
            with self.span("bench.confirm"):
                stubs = api.jobs.allocations(spec.id)
            got: dict = {}
            for s in stubs:
                if s["DesiredStatus"] == "run":
                    got[s["TaskGroup"]] = got.get(s["TaskGroup"], 0) + 1
            rec.stubs = stubs
            if got == spec.groups:
                rec.done = time.monotonic()
                return

    def warm_pass(self, names: list, clients: int) -> list:
        """The cell's own shapes run to completion before the window,
        `clients` at a time, through the window's own path."""
        recs = []
        todo = list(names)

        def worker():
            while True:
                with self.lock:
                    if not todo:
                        return
                    name = todo.pop(0)
                rec = self.next_job("warm", time.monotonic(), name)
                recs.append(rec)
                self.run_job(rec)

        self._threads(worker, clients)
        return recs

    @staticmethod
    def _threads(fn, n: int) -> None:
        ts = [threading.Thread(target=fn, daemon=True) for _ in range(n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()

    def window(self, seed: int, seconds: float) -> dict:
        """Offer the mix for `seconds`, then let what is in flight finish
        for at most `drain_s`.  Returns the window's start and end on the
        monotonic clock and its records."""
        t0 = time.monotonic()
        end = t0 + seconds
        self.deadline = end + self.mix["drain_s"]
        recs: list = []
        if self.mix["arrivals"] == "closed":
            def client():
                while time.monotonic() < end:
                    rec = self.next_job("w", time.monotonic())
                    recs.append(rec)
                    self.run_job(rec)
            self._threads(client, self.mix["clients"])
        else:
            threads = []
            for t in arrivals(self.mix, seed, seconds):
                rec = self.next_job("w", t0 + float(t))
                recs.append(rec)
                with self.span("bench.idle"):
                    time.sleep(max(0.0, rec.due - time.monotonic()))
                th = threading.Thread(target=self.run_job, args=(rec,),
                                      daemon=True)
                th.start()
                threads.append(th)
            time.sleep(max(0.0, end - time.monotonic()))
            for th in threads:
                th.join()
        return {"t0": t0, "end": end, "drained": time.monotonic(),
                "records": recs}
