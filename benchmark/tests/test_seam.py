"""The seam by which a configuration brings its own world: the harness
finds a deployment's reference, cluster and job shapes by the names the
configuration's file gives them, and hands the reference what its
`readback` read.

* every key's default is the module `c2m-10k` runs; a dotted name
  resolves; a name that does not exist is refused before an agent starts;
* a second world under `halfworld/` (half the nodes carry one more
  attribute, its jobs are constrained to it, its reference reads one
  node's allocation list back) goes through `run_cell` on the CPU and is
  `correct`, and is not when its job module drops the constraint;
* the accepted world is what it was: `Cluster` and the first 50 job
  specs of each accepted mix against digests recorded before the seam.
"""
import copy
import hashlib
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("NOMAD_TPU_JAX_CACHE", "0")

from benchmark import harness, traffic                  # noqa: E402

KEYS = ("reference", "cluster", "jobs")
SEEDS = (3, 7, 2147483659)
CELL = {"name": "halfworld.trickle", "config": "halfworld",
        "traffic": "halfworld", "chips": 1, "why": "a test's"}


def _halfworld(name: str) -> dict:
    with open(os.path.join(HERE, "halfworld", f"{name}.json")) as f:
        return json.load(f)


def _config() -> dict:
    """The second world's configuration: `c2m-10k`'s sizes under the
    keys its own file gives."""
    return {**harness.load_config("c2m-10k"), **_halfworld("config")}


@pytest.fixture
def second_world(monkeypatch):
    """`run_cell` finds the test's cell, configuration and mix."""
    bench = copy.deepcopy(harness.load_benchmark())
    bench["workloads"].append(CELL)
    monkeypatch.setattr(harness, "load_benchmark", lambda: bench)
    cfg = _config()
    monkeypatch.setattr(harness, "load_config", lambda _name: cfg)
    monkeypatch.setattr(traffic, "load", lambda _name: _halfworld("traffic"))


@pytest.mark.parametrize("key", KEYS)
def test_default_is_the_module_c2m_10k_runs(key):
    cfg = harness.load_config("c2m-10k")
    assert set(cfg) & set(KEYS) == {"reference"}
    assert harness.world_module(cfg, key).__name__ == f"benchmark.{key}"


def test_reference_has_no_default():
    with pytest.raises(harness.Refused, match="reference"):
        harness.world_module({"name": "nameless"}, "reference")


@pytest.mark.parametrize("key", KEYS)
def test_dotted_name_resolves(key):
    assert harness.world_module(_halfworld("config"), key).__name__ \
        == f"benchmark.tests.halfworld.{key}"


@pytest.mark.parametrize("key", KEYS)
def test_unknown_module_is_refused_before_the_agent_starts(
        key, monkeypatch, second_world):
    from nomad_tpu.agent import agent
    cfg = {**_config(), key: "tests.halfworld.no_such"}
    monkeypatch.setattr(harness, "load_config", lambda _name: cfg)
    monkeypatch.setattr(agent, "Agent", None)     # calling it would raise
    with pytest.raises(harness.Refused, match="no_such"):
        harness.run_cell(CELL["name"], 1, 1.0, False, time.monotonic(),
                         require_tpu=False)


@pytest.mark.parametrize("constrained", [True, False])
def test_second_world_through_run_cell(constrained, monkeypatch,
                                       second_world):
    from benchmark import jobs as c2m_jobs
    from benchmark.tests.halfworld import jobs, reference
    calls = []
    real = reference.readback
    monkeypatch.setattr(reference, "readback",
                        lambda get, recs: calls.append(1) or real(get, recs))
    if not constrained:
        monkeypatch.setattr(jobs, "build", c2m_jobs.build)
    line = harness.run_cell(CELL["name"], 41 + constrained, 3.0, False,
                            time.monotonic(), n_nodes=256,
                            require_tpu=False)
    assert line["attempted"] > 0 and line["failed"] == 0, line
    assert calls == [1]
    assert set(line["compared"]) == set(reference.LIMITS)
    assert line["correct"] is constrained, line
    assert (line["compared"]["violations"]["value"] == 0) is constrained


def _digest(*parts) -> str:
    m = hashlib.sha256()
    for p in parts:
        if isinstance(p, np.ndarray):
            m.update(f"{p.dtype}{p.shape}".encode())
            m.update(np.ascontiguousarray(p).tobytes())
        else:
            m.update(json.dumps(p, sort_keys=True).encode())
    return m.hexdigest()[:16]


def _plain(v):
    if isinstance(v, np.ndarray):
        return v.tolist()
    return sorted(v) if isinstance(v, set) else v


def world_digests(seed: int) -> dict:
    """The cluster of `c2m-10k` at its full size and the first 50 job
    specs of each accepted mix, by the modules the seam resolves."""
    cfg = harness.load_config("c2m-10k")
    reference, cluster = (harness.world_module(cfg, k) for k in KEYS[:2])
    cl = cluster.Cluster(cfg, seed)
    out = {"cluster": _digest(cl.node_ids, cl.cap, cl.dc, cl.rack, cl.used0,
                              cl.pre_node, cl.pre_shape, cl.pre_ids)}
    for name in ("backlog", "spread-steady"):
        mix = traffic.load(name)
        order = traffic.shape_order(mix, seed)
        specs = []
        for k in range(50):
            shape, ns = next(order)
            spec = reference.JobSpec(f"w{k + 1:05d}-{shape}", ns,
                                     mix["shapes"][shape])
            specs.append({a: _plain(v) for a, v in vars(spec).items()})
        out[name] = _digest(specs)
    return out


@pytest.mark.parametrize("seed", SEEDS)
def test_accepted_world_is_what_it_was(seed):
    with open(os.path.join(os.path.dirname(HERE), "testdata",
                           "world_digests.json")) as f:
        recorded = json.load(f)
    assert world_digests(seed) == recorded[str(seed)]
