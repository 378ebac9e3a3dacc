"""The `preempt-10k` deployment: a cluster kept full with low-priority
batch work.  Every node holds `per_node` fillers of one size (94.5% of
its cpu), and the fillers belong to hundreds of small batch jobs in
three priority tiers, two of which a service at Nomad's default
priority may evict and one of which it may not.

What the plain reference knows, all drawn from `--seed`: which slot of
which node belongs to which job (`pre_job`), so to which tier
(`pre_prio`), and each filler's id (`pre_ids`; `filler` maps an id back
to its slot).  Slot `s` lies on node `s // per_node`.  `install` writes
the same world into the agent, the fillers inside `used0`, so that
`check_preload` holds.
"""
from __future__ import annotations

import numpy as np

from benchmark import cluster as c2m


class Cluster(c2m.Cluster):

    def __init__(self, cfg: dict, seed: int, n_nodes: int | None = None):
        super().__init__(cfg, seed, n_nodes)
        pre = cfg["preload"]
        rng = np.random.default_rng([int(seed), 0x7E125])
        slots = len(self.pre_node)
        self.per_node = pre["per_node"]
        self.n_jobs = max(len(pre["tiers"]), slots // pre["allocs_per_job"])
        # jobs in tier order, by the tiers' shares; tenants alternate
        per_tier = c2m._apportion([t["share"] for t in pre["tiers"]],
                                  self.n_jobs)
        self.job_prio = np.repeat([t["priority"] for t in pre["tiers"]],
                                  per_tier)
        self.job_ns = [cfg["namespaces"][j % len(cfg["namespaces"])]
                       for j in range(self.n_jobs)]
        self.job_ids = [f"filler-p{p}-{j:04d}"
                        for j, p in enumerate(self.job_prio)]
        # every job the same number of slots (to within one), which
        # slots drawn from the seed
        self.pre_job = rng.permutation(np.arange(slots) % self.n_jobs)
        self.pre_prio = self.job_prio[self.pre_job]
        self.filler = {aid: s for s, aid in enumerate(self.pre_ids)}
        shape = pre["shapes"][0]
        self.filler_demand = np.array([shape["cpu"], shape["memory_mb"]],
                                      np.float64)

    def _preload(self, rng, total: int, pre: dict):
        """`per_node` fillers on every node, of the one shape."""
        nodes = np.repeat(np.arange(self.n), pre["per_node"])
        return nodes, np.zeros(len(nodes), np.int64)

    # --------------------------------------------------------- install

    def refuse_a_program_that_cannot_run_this(self) -> None:
        """One full node, one filler ten below the service, through the
        program's own search (no agent, no engine): it has to find the
        eviction, and to say what it ranked the node by with it, which
        is what a preempting placement reports in `score_meta`.  A program
        that finds no eviction would block every job of the warm pass,
        which waits without a deadline; one that reports no score (the
        tree before PR 34) leaves `correct` with nothing to hold the
        placement to.  Both are refused here, before an agent starts."""
        from benchmark.harness import Refused
        from nomad_tpu import mock
        from nomad_tpu.scheduler.preemption import Preemptor
        from nomad_tpu.scheduler.testing import Harness
        h = Harness()
        node = mock.node()
        h.store.upsert_node(h.next_index(), node)
        low = mock.job(priority=self.cfg["preload"]["tiers"][0]["priority"])
        h.store.upsert_job(h.next_index(), low)
        filler = mock.alloc_for(low, node.id)
        cpu = float(node.node_resources.cpu.cpu_shares)
        (task,) = filler.allocated_resources.tasks.values()
        task.cpu_shares = int(cpu)
        h.store.upsert_allocs(h.next_index(), [filler])
        snap = h.store.snapshot()
        cm = snap.matrix
        row = cm.row_of[node.id]
        demand = np.zeros(cm.capacity.shape[1], np.float32)
        demand[0] = cpu / 2
        feasible = np.zeros(cm.n_rows, bool)
        feasible[row] = True
        search = Preemptor(snap, 50)
        found = search.find_many(feasible, demand, cm.used.copy(), 1)
        if [(f[0], [a.id for a in f[1]]) for f in found] \
                != [(row, [filler.id])]:
            raise Refused("the program's Preemptor.find_many does not evict "
                          "a priority-20 filler for a priority-50 ask: it "
                          "cannot place this configuration's jobs")
        if not hasattr(found[0], "score"):
            raise Refused("the program's Preemptor.find_many returns no "
                          "score with an eviction: a preempting placement "
                          "reports none in score_meta, so `correct` cannot "
                          "be decided")

    def install(self, agent) -> dict:
        self.refuse_a_program_that_cannot_run_this()
        from nomad_tpu.structs import (
            Allocation, AllocClientStatus, AllocDesiredStatus)
        from nomad_tpu.structs.alloc import (
            AllocatedResources, AllocatedTaskResources)
        from benchmark.harness import world_module
        build = world_module(self.cfg, "jobs").build
        server = agent.server
        store = server.store
        for ns in self.cfg["namespaces"]:
            if ns != "default":
                store.upsert_namespace(server.next_index(), ns)
        for n in self.make_nodes():
            store.upsert_node(server.next_index(), n)
        shape = self.cfg["preload"]["shapes"][0]
        counts = np.bincount(self.pre_job, minlength=self.n_jobs)
        jobs = []
        for j in range(self.n_jobs):
            job = build({"kind": "batch", "groups": 1,
                         "count": int(counts[j]), "cpu": shape["cpu"],
                         "memory_mb": shape["memory_mb"],
                         "priority": int(self.job_prio[j]),
                         "datacenters": list(self.cfg["datacenters"])},
                        job_id=self.job_ids[j], namespace=self.job_ns[j])
            store.upsert_job(server.next_index(), job)
            jobs.append(job)
        res = AllocatedResources(tasks={"web": AllocatedTaskResources(
            cpu_shares=shape["cpu"], memory_mb=shape["memory_mb"])})
        seen = [0] * self.n_jobs
        allocs = []
        for s, (row, j) in enumerate(zip(self.pre_node, self.pre_job)):
            job = jobs[j]
            allocs.append(Allocation(
                id=self.pre_ids[s], namespace=job.namespace,
                name=f"{job.id}.g0[{seen[j]}]", node_id=self.node_ids[row],
                node_name=f"node-{row}", job_id=job.id, job=job,
                task_group="g0", allocated_resources=res,
                desired_status=AllocDesiredStatus.RUN,
                client_status=AllocClientStatus.RUNNING))
            seen[j] += 1
        store.upsert_allocs(server.next_index(), allocs)
        return {"nodes": self.n, "preload_allocs": len(allocs),
                "preload_jobs": self.n_jobs}
