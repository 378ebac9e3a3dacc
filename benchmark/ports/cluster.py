"""The `ports-10k` deployment: `c2m-10k`'s cluster (the same nodes,
datacenters, racks and preload under the same seed) whose running
services hold ports, on nodes that reserve one and of which a tenth
offer a narrow dynamic range.

What the plain reference knows of the ports, all drawn from `--seed`:
each node's own dynamic range (`lo`, `hi`; `narrow` marks the tenth
behind an operator's firewall window) and reserved ports (`reserved`),
which ports each preloaded allocation holds (`pre_ports`: [(label, value,
static)]) and so which values every node starts with taken (`held`), as
arrays which of the well-known static values (`static_held`) and how
many of its dynamic range (`dyn_free0` are left).  `install` writes the
same world into the agent: the ranges and reserved ports on the node
structs, the ports on the preloaded allocations as a group `network`
block leaves them (`shared_networks` and `shared_ports`).  Cpu and memory
are `c2m-10k`'s, so `used0` and `check_preload` are unchanged.
"""
from __future__ import annotations

import numpy as np

from benchmark import cluster as c2m
from benchmark.ports import jobs

KINDS = ("two_dynamic", "static_and_dynamic", "none")
_TRIES = 4           # seeded candidates a dynamic draw tries before a walk


class Cluster(c2m.Cluster):

    def __init__(self, cfg: dict, seed: int, n_nodes: int | None = None):
        super().__init__(cfg, seed, n_nodes)
        pc = cfg["ports"]
        rng = np.random.default_rng([int(seed), 0x9027])
        self.reserved = sorted(int(p) for p in pc["reserved_ports"])
        self.statics = [int(p) for p in pc["static_values"]]
        narrow = pc["narrowed"]
        self.narrow = np.zeros(self.n, bool)
        self.narrow[rng.permutation(self.n)[
            : int(round(narrow["share"] * self.n))]] = True
        self.lo = np.where(self.narrow, narrow["range"][0],
                           pc["dynamic_range"][0]).astype(np.int64)
        self.hi = np.where(self.narrow, narrow["range"][1],
                           pc["dynamic_range"][1]).astype(np.int64)
        # the preload's kinds: the same multiset every seed
        total = len(self.pre_node)
        kind = np.repeat(np.arange(len(KINDS)), c2m._apportion(
            [pc["preload"][k] for k in KINDS], total))
        rng.shuffle(kind)
        first = rng.integers(0, len(self.statics), total)
        tries = rng.integers(0, 1 << 30, (total, 2 * _TRIES))
        self.held = [set(self.reserved) for _ in range(self.n)]
        self.pre_ports: list = []
        room = self.hi - self.lo + 1 - np.array(
            [sum(lo <= p <= hi for p in self.reserved)
             for lo, hi in zip(self.lo, self.hi)])
        for i, row in enumerate(self.pre_node):
            row = int(row)
            ports = []
            want = (2, 1, 0)[kind[i]]
            if self.narrow[row] and room[row] - want < narrow["keep_free"]:
                want = 0          # the window is nearly full: no ports
            if want == 1:
                # the first of the well-known values, from a seeded start,
                # that the node still has free; none: the dynamic alone
                for k in range(len(self.statics)):
                    p = self.statics[(first[i] + k) % len(self.statics)]
                    if p not in self.held[row]:
                        ports.append(("https", p, True))
                        self.held[row].add(p)
                        break
            labels = ("http", "metrics") if want == 2 else ("admin",)
            for j in range(want):
                p = self._draw(row, tries[i, j * _TRIES:(j + 1) * _TRIES])
                ports.append((labels[j], p, False))
                self.held[row].add(p)
                room[row] -= 1
            self.pre_ports.append(ports)
        self.dyn_free0 = room
        self.static_held = np.array(
            [[p in h for p in self.statics] for h in self.held], bool)

    def _draw(self, row: int, candidates) -> int:
        """A free value of the node's own range: the first of the seeded
        candidates that is free, else the lowest free one."""
        lo, hi = int(self.lo[row]), int(self.hi[row])
        for c in candidates:
            p = lo + int(c) % (hi - lo + 1)
            if p not in self.held[row]:
                return p
        return next(p for p in range(lo, hi + 1) if p not in self.held[row])

    # --------------------------------------------------------- install

    def make_nodes(self) -> list:
        nodes = super().make_nodes()
        for row, node in enumerate(nodes):
            node.node_resources.min_dynamic_port = int(self.lo[row])
            node.node_resources.max_dynamic_port = int(self.hi[row])
            node.reserved_resources.reserved_ports = list(self.reserved)
        return nodes

    def refuse_a_program_that_cannot_run_this(self) -> None:
        """One allocation that holds the two ports of a group `network`
        block, on one empty node, through the program's own port
        assignment and plan applier (no agent, no engine): the applier
        has to take it.  A program whose applier counts a group-level
        port twice (the tree before PR 46: once in `shared_networks`,
        once in `shared_ports`) finds the allocation colliding with
        itself and refuses every node of every plan, so every job of the
        warm pass, which waits without a deadline, would block: it is
        refused here, before an agent starts."""
        from benchmark.harness import Refused
        from nomad_tpu import mock
        from nomad_tpu.core.plan_apply import PlanApplier
        from nomad_tpu.scheduler.placement import PortClaims, build_allocation
        from nomad_tpu.state import StateStore
        from nomad_tpu.structs.alloc import AllocMetric
        from nomad_tpu.structs.plan import Plan
        store = StateStore()
        node = mock.node()
        store.upsert_node(1, node)
        job = jobs.build({
            "kind": "service", "groups": 1, "count": 1, "cpu": 100,
            "memory_mb": 64, "datacenters": [node.datacenter],
            "network": {"ports": [{"label": "http"}, {"label": "metrics"}]}},
            job_id="probe")
        store.upsert_job(2, job)
        cm = store.matrix
        alloc = build_allocation(
            job=job, tg=job.task_groups[0], name="probe.g0[0]",
            node_id=node.id, node_name=node.name, eval_id="probe",
            row=cm.row_of[node.id], ports=PortClaims(cm), freed_ports=set(),
            metric=AllocMetric())
        if alloc is None:
            raise Refused("the program assigns no two dynamic ports of a "
                          "group `network` block on an empty node")
        plan = Plan(eval_id="probe", job=job)
        plan.append_alloc(alloc, None)
        result = PlanApplier(store).apply(plan)
        if node.id not in result.node_allocation:
            raise Refused(
                "the program's plan applier refuses an allocation that "
                "holds the two ports of a group `network` block on an empty "
                "node (it counts a group-level port twice): it cannot "
                "place this configuration's jobs")

    def install(self, agent) -> dict:
        self.refuse_a_program_that_cannot_run_this()
        from nomad_tpu.structs import (
            Allocation, AllocClientStatus, AllocDesiredStatus)
        from nomad_tpu.structs.alloc import (
            AllocatedResources, AllocatedTaskResources)
        from nomad_tpu.structs.resources import NetworkPort, NetworkResource
        server = agent.server
        store = server.store
        for ns in self.cfg["namespaces"]:
            if ns != "default":
                store.upsert_namespace(server.next_index(), ns)
        for n in self.make_nodes():
            store.upsert_node(server.next_index(), n)

        # a preload job per size, tenant and `network` block, so that
        # every allocation holds what its own job's group asks
        spaces = self.cfg["namespaces"]
        members: dict = {}
        for i, (k, ports) in enumerate(zip(self.pre_shape, self.pre_ports)):
            block = tuple((label, value if static else 0)
                          for label, value, static in ports)
            members.setdefault(
                (int(k), spaces[i % len(spaces)], block), []).append(i)
        allocs = []
        for (k, ns, block), which in members.items():
            sh = self.pre_shapes[k]
            tag = "".join(f"-{label}{value or ''}" for label, value in block)
            job = jobs.build({
                "kind": "batch", "groups": 1, "count": len(which),
                "cpu": sh["cpu"], "memory_mb": sh["memory_mb"],
                "datacenters": list(self.cfg["datacenters"]),
                "network": {"ports": [
                    {"label": label, "static": value} if value
                    else {"label": label} for label, value in block]}},
                job_id=f"preload-{k}-{ns}{tag}", namespace=ns)
            store.upsert_job(server.next_index(), job)
            task = AllocatedTaskResources(cpu_shares=sh["cpu"],
                                          memory_mb=sh["memory_mb"])
            for idx, i in enumerate(which):
                row = int(self.pre_node[i])
                res = AllocatedResources(tasks={"web": task})
                if self.pre_ports[i]:
                    net = NetworkResource(
                        reserved_ports=[NetworkPort(label=label, value=p)
                                        for label, p, static
                                        in self.pre_ports[i] if static],
                        dynamic_ports=[NetworkPort(label=label, value=p)
                                       for label, p, static
                                       in self.pre_ports[i] if not static])
                    res.shared_networks = [net]
                    res.shared_ports = net.reserved_ports + net.dynamic_ports
                allocs.append(Allocation(
                    id=self.pre_ids[i], namespace=ns,
                    name=f"{job.id}.g0[{idx}]", node_id=self.node_ids[row],
                    node_name=f"node-{row}", job_id=job.id, job=job,
                    task_group="g0", allocated_resources=res,
                    desired_status=AllocDesiredStatus.RUN,
                    client_status=AllocClientStatus.RUNNING))
        store.upsert_allocs(server.next_index(), allocs)
        return {
            "nodes": self.n, "preload_allocs": len(allocs),
            "preload_jobs": len(members),
            "ports_held": int(sum(len(p) for p in self.pre_ports)),
            "nodes_holding": {str(p): int(self.static_held[:, k].sum())
                              for k, p in enumerate(self.statics)},
            "narrowed": int(self.narrow.sum()),
            "narrowed_under_2_free": int(
                (self.dyn_free0[self.narrow] < 2).sum())}
