"""The `distinct-10k` deployment: `c2m-10k`'s cluster (the same nodes,
datacenters, racks and preload under the same seed) whose nodes carry
what a `constraint` block selects by: a class, a name made of it, a
kernel version and a set of features.

What the plain reference knows of them, all drawn from `--seed` after
`c2m-10k`'s own draws (which are unchanged, so `used0` and
`check_preload` are): `node_class`, `name`, `kernel`, `features`, one
string a node each, every seed the same multiset.  `make_nodes` writes
the same onto the node structs: `${node.class}`, `${node.unique.name}`,
`${attr.kernel.version}`, `${meta.features}`; the rack is `c2m-10k`'s
`${attr.rack}`.
"""
from __future__ import annotations

import numpy as np

from benchmark import cluster as c2m
from benchmark.distinct import jobs


class Cluster(c2m.Cluster):

    def __init__(self, cfg: dict, seed: int, n_nodes: int | None = None):
        super().__init__(cfg, seed, n_nodes)
        at = cfg["attributes"]
        rng = np.random.default_rng([int(seed), 0xD157])

        def dealt(shares: dict) -> np.ndarray:
            """One key a node: the shares' own counts, shuffled."""
            keys = np.repeat(np.array(list(shares)), c2m._apportion(
                list(shares.values()), self.n))
            rng.shuffle(keys)
            return keys

        self.node_class = dealt(at["node_class"])
        self.name = [f"{c}-{i:05d}" for i, c in enumerate(self.node_class)]
        self.kernel = dealt(at["kernel_version"])
        self.features = np.empty(self.n, object)
        for storage in (True, False):
            rows = rng.permutation(np.flatnonzero(
                (self.node_class == "storage") == storage))
            first, second = at["features"]["storage" if storage else "other"]
            self.features[rows[: len(rows) // 2]] = first
            self.features[rows[len(rows) // 2:]] = second

    # --------------------------------------------------------- install

    def make_nodes(self) -> list:
        from nomad_tpu.structs.node import compute_node_class
        nodes = super().make_nodes()
        for i, node in enumerate(nodes):
            node.name = self.name[i]
            node.node_class = str(self.node_class[i])
            node.attributes["unique.hostname"] = self.name[i]
            node.attributes["kernel.version"] = str(self.kernel[i])
            node.meta["features"] = str(self.features[i])
            node.computed_class = compute_node_class(node)
        return nodes

    def refuse_a_program_that_cannot_run_this(self) -> None:
        """Two evals through the program's scheduler, engine and plan
        applier (`scheduler.testing.Harness`: no agent), each of one
        group whose slots the constraint has to part inside the eval:
        two nodes of one rack and a count of 2 under `distinct_property
        ${attr.rack}` "1" place one and fail one; two nodes and a count
        of 3 under `distinct_hosts` place two.  A program whose scan step
        carries neither from one slot of an eval to the next (the tree
        before PR 50: its masks know the job's existing allocations only)
        places them all, and would run the window and place it wrongly:
        it is refused here, by name, before an agent starts."""
        from benchmark.harness import Refused
        from nomad_tpu import mock
        from nomad_tpu.scheduler.testing import Harness
        for name, constraint, count, want in (
                ("distinct_property", {"attribute": "${attr.rack}",
                                       "operator": "distinct_property",
                                       "value": "1"}, 2, 1),
                ("distinct_hosts", {"operator": "distinct_hosts"}, 3, 2)):
            h = Harness()
            for _ in range(2):
                node = mock.node()
                node.attributes["rack"] = "r0"
                h.store.upsert_node(h.next_index(), node)
            job = jobs.build({
                "kind": "service", "datacenters": ["dc1"],
                "task_groups": [{"name": "probe", "count": count, "cpu": 100,
                                 "memory_mb": 64,
                                 "constraints": [constraint]}]},
                job_id=f"probe-{name}")
            h.store.upsert_job(h.next_index(), job)
            ev = mock.eval(job_id=job.id, type=job.type,
                           priority=job.priority)
            h.store.upsert_evals(h.next_index(), [ev])
            h.process(job.type, ev)
            got = sum(1 for a in h.store.allocs_by_job(job.namespace, job.id)
                      if not a.terminal_status())
            if got != want:
                raise Refused(
                    f"the program placed {got} of {count} allocations of a "
                    f"group under {name} on two nodes of one rack in one "
                    f"eval, where the constraint allows {want}: its scan "
                    f"step does not carry {name} from one slot of an eval "
                    "to the next, and it cannot place this configuration's "
                    "jobs")

    def install(self, agent) -> dict:
        self.refuse_a_program_that_cannot_run_this()
        wrote = super().install(agent)
        wrote["classes"] = {str(k): int((self.node_class == k).sum())
                            for k in self.cfg["attributes"]["node_class"]}
        return wrote
