"""Drives a whole run on the CPU at a small size, skipping only the
harness's look for a chip, with the timed path broken underneath, and
sees `correct` come out false; the same run unbroken has to come out
true.  Two faults, each planted where the placements are produced:

* the bulk placements' nodes shuffled, so that every allocation carries
  the score of another node (`unexplained_jobs_share`, or a violation);
* the better half of the nodes hidden from the program's argmax, so that
  every reported score is right and the choice is not
  (`misplaced_jobs_share`, and no other number).
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("NOMAD_TPU_JAX_CACHE", "0")

N_NODES = 1024


def _run(seed):
    from benchmark import harness
    return harness.run_cell("c2m-10k.backlog", seed, 3.0, False,
                            time.monotonic(), n_nodes=N_NODES,
                            require_tpu=False)


def _break_in_window(monkeypatch, plant):
    """Sound through set-up and the warm pass; `plant()` runs as the
    window opens."""
    from benchmark import traffic
    real_window = traffic.Driver.window

    def window(self, seed, seconds):
        plant()
        return real_window(self, seed, seconds)

    monkeypatch.setattr(traffic.Driver, "window", window)


def test_sound_run_is_correct():
    line = _run(21)
    assert line["correct"] is True, line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "compared"


def test_altered_placements_are_not_correct(monkeypatch):
    from nomad_tpu.scheduler import generic
    real = generic.materialize_bulk_allocs

    def shuffled(job, tg, names, rows, scores, *rest):
        return real(job, tg, names, np.roll(rows, 1), scores, *rest)

    _break_in_window(monkeypatch, lambda: monkeypatch.setattr(
        generic, "materialize_bulk_allocs", shuffled))
    line = _run(22)
    assert line["correct"] is False, line
    c = line["compared"]
    assert (c["unexplained_jobs_share"]["value"] > c["unexplained_jobs_share"]["limit"]
            or c["violations"]["value"] > 0), line


def test_argmax_over_half_the_nodes_is_not_correct(monkeypatch):
    from benchmark import cluster, harness, reference, traffic
    from nomad_tpu.scheduler.stack import DenseStack
    seed = 23
    cl = cluster.Cluster(harness.load_config("c2m-10k"), seed, N_NODES)
    shapes = traffic.load("backlog")["shapes"]
    hidden_ids = {}               # demand -> ids of the better half
    for shape in shapes.values():
        spec = reference.JobSpec("x", "default", shape)
        hidden_ids[tuple(spec.demand)] = {
            cl.node_ids[r] for r in np.flatnonzero(
                reference.better_half(cl, spec))}
    real = DenseStack.compile_group

    def half_blind(self, job, tg):
        g = real(self, job, tg)
        res = tg.tasks[0].resources
        hide = hidden_ids[(float(res.cpu), float(res.memory_mb))]
        g.feasible = g.feasible & np.array(
            [nid not in hide for nid in self.cm.node_ids])
        return g

    _break_in_window(monkeypatch, lambda: monkeypatch.setattr(
        DenseStack, "compile_group", half_blind))
    line = _run(seed)
    assert line["correct"] is False, line
    c = line["compared"]
    assert c["misplaced_jobs_share"]["value"] > c["misplaced_jobs_share"]["limit"], line
    assert c["unexplained_jobs_share"]["value"] <= c["unexplained_jobs_share"]["limit"], line
    assert c["violations"]["value"] == 0, line
