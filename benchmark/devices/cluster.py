"""The `devices-10k` deployment: `c2m-10k`'s cluster (the same nodes,
datacenters, racks and preload under the same seed) with a mixed GPU
fleet on every second node, a third of it in use at the start.

What the plain reference knows of the fleet, all drawn from `--seed`:
which node carries which group (`model`, -1 for none), how many instances
(`instances`), their ids (`instance_id`) and which of them the preload
holds (`held`).  `install` writes the same fleet into the agent: the
groups on the node structs, and the held instances as allocations of two
more preloaded batch jobs (one per tenant) whose cpu and memory are part
of `used0`, so `check_preload` holds for them too.
"""
from __future__ import annotations

import numpy as np

from benchmark import cluster as c2m


class Cluster(c2m.Cluster):

    def __init__(self, cfg: dict, seed: int, n_nodes: int | None = None):
        super().__init__(cfg, seed, n_nodes)
        fleet = cfg["fleet"]
        self.groups = fleet["groups"]
        rng = np.random.default_rng([int(seed), 0x6D0F1EE7])
        carriers = np.arange(0, self.n, fleet["every"])
        model_of = np.repeat(
            np.arange(len(self.groups)),
            c2m._apportion([g["share"] for g in self.groups], len(carriers)))
        rng.shuffle(model_of)
        self.model = np.full(self.n, -1, np.int64)
        self.model[carriers] = model_of
        per_group = np.array([g["instances"] for g in self.groups] + [0])
        self.instances = per_group[self.model]
        width = int(per_group.max())
        self.held = (rng.random((self.n, width)) < fleet["held"]) \
            & (np.arange(width)[None, :] < self.instances[:, None])
        # the holders: per node up to two allocations, one of each
        # tenant's job, sharing the node's held instances alternately
        spaces = cfg["namespaces"]
        self.holders = []          # (row, namespace index, [instance index])
        for row in np.flatnonzero(self.held.any(axis=1)):
            idx = np.flatnonzero(self.held[row])
            for k in range(min(len(spaces), len(idx))):
                self.holders.append((int(row), k,
                                     idx[k::len(spaces)].tolist()))
        self.holder_ids = c2m._uuids(rng, len(self.holders))
        hold = np.array([fleet["holder"]["cpu"],
                         fleet["holder"]["memory_mb"]], np.float64)
        for row, _k, _idx in self.holders:
            self.used0[row] += hold
        if (self.used0 > self.cap).any():
            raise RuntimeError("the holders of the held instances put a "
                               "node over its capacity")

    def instance_id(self, row: int, k: int) -> str:
        return f"GPU-{self.node_ids[row][:8]}-{k}"

    def group_id(self, row: int) -> str:
        g = self.groups[self.model[row]]
        return f"{g['vendor']}/{g['type']}/{g['model']}"

    # --------------------------------------------------------- install

    def make_nodes(self) -> list:
        from nomad_tpu.structs.node import compute_node_class
        from nomad_tpu.structs.resources import NodeDevice
        nodes = super().make_nodes()
        for row in np.flatnonzero(self.model >= 0):
            g = self.groups[self.model[row]]
            node = nodes[row]
            node.node_resources.devices = [NodeDevice(
                vendor=g["vendor"], type=g["type"], name=g["model"],
                instance_ids=[self.instance_id(row, k)
                              for k in range(g["instances"])],
                attributes=dict(g["attributes"]))]
            node.computed_class = compute_node_class(node)
        return nodes

    def refuse_a_program_that_cannot_run_this(self) -> None:
        """One card, asked for through the program's own feasibility
        mask: a `device "<vendor>/<type>"` ask with a constraint the card
        passes has to find it.  A program that finds no card at all (one
        that reads a two-part name as type/model, as the tree before
        PR 30 did) would block every job of the warm pass, which waits
        without a deadline: it is refused here, before an agent starts.
        One that finds the card and ignores the constraint runs, and is
        not `correct`."""
        from benchmark.harness import Refused
        from nomad_tpu import mock
        from nomad_tpu.encode import ClusterMatrix
        from nomad_tpu.scheduler import feasible
        from nomad_tpu.structs.job import Constraint
        from nomad_tpu.structs.resources import DeviceRequest, NodeDevice
        g = self.groups[0]
        node = mock.node()
        node.node_resources.devices = [NodeDevice(
            vendor=g["vendor"], type=g["type"], name=g["model"],
            instance_ids=["probe-0"], attributes=dict(g["attributes"]))]
        cm = ClusterMatrix()
        row = cm.upsert_node(node)
        name = f"{g['vendor']}/{g['type']}"
        ask = DeviceRequest(name=name, count=1, constraints=[
            Constraint("${device.attr.memory}", "1 GiB", ">=")])
        if not feasible.device_mask(cm, [ask], include_usage=False)[row]:
            raise Refused(f"the program finds no {g['model']} for a "
                          f"`device \"{name}\"` ask with memory >= 1 GiB: "
                          f"it cannot place this configuration's jobs")

    def install(self, agent) -> dict:
        self.refuse_a_program_that_cannot_run_this()
        from nomad_tpu.structs import (
            Allocation, AllocClientStatus, AllocDesiredStatus)
        from nomad_tpu.structs.alloc import (
            AllocatedResources, AllocatedTaskResources)
        from benchmark.devices import jobs
        wrote = super().install(agent)
        server = agent.server
        store = server.store
        hold = self.cfg["fleet"]["holder"]
        spaces = self.cfg["namespaces"]
        count = [sum(1 for h in self.holders if h[1] == k)
                 for k in range(len(spaces))]
        held_jobs = []
        for k, ns in enumerate(spaces):
            job = jobs.build({
                "kind": "batch", "groups": 1, "count": count[k],
                "cpu": hold["cpu"], "memory_mb": hold["memory_mb"],
                "datacenters": list(self.cfg["datacenters"]),
                "device": {"name": "nvidia/gpu", "count": 1}},
                job_id=f"preload-gpu-{ns}", namespace=ns)
            store.upsert_job(server.next_index(), job)
            held_jobs.append(job)
        allocs = []
        seen = [0] * len(spaces)
        for (row, k, idx), aid in zip(self.holders, self.holder_ids):
            g = self.groups[self.model[row]]
            job = held_jobs[k]
            allocs.append(Allocation(
                id=aid, namespace=job.namespace,
                name=f"{job.id}.g0[{seen[k]}]", node_id=self.node_ids[row],
                node_name=f"node-{row}", job_id=job.id, job=job,
                task_group="g0",
                allocated_resources=AllocatedResources(tasks={
                    "web": AllocatedTaskResources(
                        cpu_shares=hold["cpu"], memory_mb=hold["memory_mb"],
                        devices=[{"vendor": g["vendor"], "type": g["type"],
                                  "name": g["model"],
                                  "device_ids": [self.instance_id(row, i)
                                                 for i in idx]}])}),
                desired_status=AllocDesiredStatus.RUN,
                client_status=AllocClientStatus.RUNNING))
            seen[k] += 1
        store.upsert_allocs(server.next_index(), allocs)
        return dict(wrote, gpu_nodes=int((self.model >= 0).sum()),
                    instances=int(self.instances.sum()),
                    held=int(self.held.sum()), holders=len(allocs))
