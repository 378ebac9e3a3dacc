"""The deployment a configuration file describes, made from the seed.

`Cluster` is what the benchmark knows about the world without asking the
program: node capacities, datacenters and racks, and the preload's usage,
all as float64 numpy drawn from `--seed`.  `install` writes the same world
into a started agent in bulk (nodes the way `bench._fill_nodes` does,
allocations with `store.upsert_allocs`), so the plain reference and the
program start from the same state and neither takes it from the other.

Every seed gets the same multiset of node shapes and the same number of
preloaded allocations; the seed decides which node has which shape and
where the preload lands.

A configuration whose file names no `cluster` module gets this one; one
that needs other nodes names its own (PERF.md section 4: the seam).
"""
from __future__ import annotations

import numpy as np


def _uuids(rng: np.random.Generator, n: int) -> list:
    raw = rng.integers(0, 256, size=(n, 16), dtype=np.uint8)
    out = []
    for row in raw:
        h = row.tobytes().hex()
        out.append(f"{h[:8]}-{h[8:12]}-{h[12:16]}-{h[16:20]}-{h[20:]}")
    return out


def _apportion(shares, n: int) -> np.ndarray:
    """Integer counts that sum to n, proportional to `shares`."""
    shares = np.asarray(shares, np.float64)
    counts = np.floor(shares / shares.sum() * n).astype(np.int64)
    counts[: n - counts.sum()] += 1
    return counts


class Cluster:
    """Nodes and preload of one configuration under one seed."""

    def __init__(self, cfg: dict, seed: int, n_nodes: int | None = None):
        self.cfg = cfg
        rng = np.random.default_rng([int(seed), 0xC1A5])
        n = int(n_nodes or cfg["nodes"])
        scale = n / cfg["nodes"]
        self.n = n
        self.node_ids = _uuids(rng, n)
        self.index = {nid: i for i, nid in enumerate(self.node_ids)}

        shapes = cfg["node_shapes"]
        shape_of = np.repeat(np.arange(len(shapes)),
                             _apportion([s["share"] for s in shapes], n))
        rng.shuffle(shape_of)
        self.cap = np.array([[shapes[s]["cpu"], shapes[s]["memory_mb"]]
                             for s in shape_of], np.float64)
        dcs = list(cfg["datacenters"])
        dc_of = np.repeat(np.arange(len(dcs)),
                          _apportion(list(cfg["datacenters"].values()), n))
        rng.shuffle(dc_of)
        self.dc = np.array(dcs)[dc_of]
        self.rack = np.arange(n) % cfg["racks"]

        pre = cfg["preload"]
        self.pre_shapes = pre["shapes"]
        self.pre_node, self.pre_shape = self._preload(
            rng, int(round(pre["allocs"] * scale)), pre)
        self.pre_ids = _uuids(rng, len(self.pre_node))
        dem = np.array([[s["cpu"], s["memory_mb"]] for s in self.pre_shapes],
                       np.float64)
        self.used0 = np.zeros_like(self.cap)
        np.add.at(self.used0, self.pre_node, dem[self.pre_shape])

    def _preload(self, rng, total: int, pre: dict):
        """Round by round every node with room under its own fill target
        takes one allocation of a random shape; the targets are uneven
        (uniform between `min_fill` and `max_fill` of capacity), so
        neighbouring nodes end at different usage."""
        dem = np.array([[s["cpu"], s["memory_mb"]] for s in pre["shapes"]],
                       np.float64)
        p = np.array([s["share"] for s in pre["shapes"]], np.float64)
        p /= p.sum()
        target = rng.uniform(pre["min_fill"], pre["max_fill"], self.n)
        budget = self.cap * target[:, None]
        used = np.zeros_like(self.cap)
        nodes, kinds = [], []
        have = 0
        for _ in range(400):
            pick = rng.choice(len(p), size=self.n, p=p)
            ok = np.flatnonzero(((used + dem[pick]) <= budget).all(axis=1))
            if ok.size == 0:
                budget = np.minimum(budget * 1.1, self.cap * pre["max_fill"])
                continue
            if have + ok.size > total:
                ok = rng.permutation(ok)[: total - have]
            used[ok] += dem[pick[ok]]
            nodes.append(ok)
            kinds.append(pick[ok])
            have += ok.size
            if have >= total:
                break
        if have != total:
            raise RuntimeError(f"preload placed {have} of {total}")
        return np.concatenate(nodes), np.concatenate(kinds)

    # --------------------------------------------------------- install

    def make_nodes(self) -> list:
        """Node structs for the agent: `chip_smoke.make_nodes` with the
        seed's shapes, datacenters and racks (fields as `mock.node`)."""
        from nomad_tpu.structs import Node, NodeStatus
        from nomad_tpu.structs.node import (
            NodeCpuResources, NodeResources, compute_node_class)
        from nomad_tpu.structs.resources import NetworkResource
        out = []
        for i in range(self.n):
            n = Node(
                id=self.node_ids[i], name=f"node-{i}",
                datacenter=str(self.dc[i]),
                attributes={"kernel.name": "linux", "arch": "x86",
                            "nomad.version": "0.5.0", "driver.exec": "1",
                            "driver.mock_driver": "1",
                            "unique.hostname": f"node-{i}",
                            "rack": f"r{self.rack[i]}"},
                node_resources=NodeResources(
                    cpu=NodeCpuResources(
                        cpu_shares=int(self.cap[i, 0]), total_core_count=4,
                        reservable_cores=[0, 1, 2, 3]),
                    memory_mb=int(self.cap[i, 1]), disk_mb=100 * 1024,
                    networks=[NetworkResource(
                        device="eth0", cidr="192.168.0.100/32", mbits=1000)]),
                drivers={"exec": {"detected": True, "healthy": True},
                         "mock_driver": {"detected": True, "healthy": True}},
                status=NodeStatus.READY)
            n.computed_class = compute_node_class(n)
            out.append(n)
        return out

    def install(self, agent) -> dict:
        """Write namespaces, nodes, preload jobs and preload allocations
        into the state store of an agent that has not started yet (as a
        restored snapshot would be there; with the agent's threads
        running the same writes take three times as long).  The preload
        bypasses raft and the scheduler (the configuration's file says
        so); the window does not.  Returns what was written."""
        from nomad_tpu.structs import (
            Allocation, AllocClientStatus, AllocDesiredStatus)
        from nomad_tpu.structs.alloc import (
            AllocatedResources, AllocatedTaskResources)
        from benchmark.harness import world_module
        jobshapes = world_module(self.cfg, "jobs")
        server = agent.server
        store = server.store
        for ns in self.cfg["namespaces"]:
            if ns != "default":
                store.upsert_namespace(server.next_index(), ns)
        for n in self.make_nodes():
            store.upsert_node(server.next_index(), n)

        spaces = self.cfg["namespaces"]
        per_job: dict = {}
        batch = []
        for i, (row, k) in enumerate(zip(self.pre_node, self.pre_shape)):
            ns = spaces[i % len(spaces)]
            key = (int(k), ns)
            slot = per_job.setdefault(key, [None, 0])
            slot[1] += 1
            batch.append((i, int(row), key, slot[1] - 1))
        allocs = []
        for (k, ns), slot in per_job.items():
            sh = self.pre_shapes[k]
            job = jobshapes.build({
                "kind": "batch", "groups": 1, "count": slot[1],
                "cpu": sh["cpu"], "memory_mb": sh["memory_mb"],
                "datacenters": list(self.cfg["datacenters"])},
                job_id=f"preload-{k}-{ns}", namespace=ns)
            store.upsert_job(server.next_index(), job)
            slot[0] = (job, AllocatedResources(tasks={"web": AllocatedTaskResources(
                cpu_shares=sh["cpu"], memory_mb=sh["memory_mb"])}))
        for i, row, key, idx in batch:
            job, res = per_job[key][0]
            allocs.append(Allocation(
                id=self.pre_ids[i], namespace=job.namespace,
                name=f"{job.id}.g0[{idx}]", node_id=self.node_ids[row],
                node_name=f"node-{row}", job_id=job.id, job=job,
                task_group="g0", allocated_resources=res,
                desired_status=AllocDesiredStatus.RUN,
                client_status=AllocClientStatus.RUNNING))
        store.upsert_allocs(server.next_index(), allocs)
        return {"nodes": self.n, "preload_allocs": len(allocs),
                "preload_jobs": len(per_job)}
