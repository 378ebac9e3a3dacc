"""The `system-10k` deployment: `preempt-10k`'s full cluster under the
same seed, met by a job that wants one allocation on every node.

One thing is added to what `preempt-10k` draws: every node holds at
least `MIN_EVICTABLE` fillers that a job at Nomad's default priority may
evict (tiers 20 and 35).  Where the seed's draw leaves a node with fewer
(3 to 9 nodes in 10,000 on the seeds tried: eight or nine of its
fillers at tier 45), slots are swapped with nodes that have spare, one
slot a donor: every job keeps its size, every node its nine fillers and
its `used0`.  So on every seed every node in a system job's scope can
answer, twice (the warm pass's rack job, then the fleet's), and a job's
count is its scope's node count.

`Cluster.made` is the cluster made last in this process: the seam hands
a `JobSpec` its shape and no cluster, and a system job's count is its
scope's size, which only the cluster knows.
"""
from __future__ import annotations

import numpy as np

from benchmark.preempt import cluster as preempt

MIN_EVICTABLE = 2


class Cluster(preempt.Cluster):
    made = None

    def __init__(self, cfg: dict, seed: int, n_nodes: int | None = None):
        super().__init__(cfg, seed, n_nodes)
        self.seed = int(seed)
        self.may_go_below = cfg["job_priority"] - cfg["priority_delta"]
        self.swapped = self._repair(np.random.default_rng([int(seed), 0x5E5]))
        Cluster.made = self

    def _repair(self, rng) -> int:
        """Swap slots until every node holds `MIN_EVICTABLE` fillers that
        may go.  Returns the number of swaps."""
        per = self.per_node
        have = (self.pre_prio <= self.may_go_below).reshape(self.n, per) \
            .sum(axis=1)
        donors = [int(r) for r in rng.permutation(
            np.flatnonzero(have > MIN_EVICTABLE))]
        swaps = 0
        for row in np.flatnonzero(have < MIN_EVICTABLE):
            for _ in range(MIN_EVICTABLE - have[row]):
                if not donors:
                    raise RuntimeError("no node has an evictable filler "
                                       "to spare")
                mine = row * per + np.flatnonzero(
                    self.pre_prio[row * per:(row + 1) * per]
                    > self.may_go_below)[0]
                d = donors.pop()
                theirs = d * per + np.flatnonzero(
                    self.pre_prio[d * per:(d + 1) * per]
                    <= self.may_go_below)[0]
                self.pre_job[[mine, theirs]] = self.pre_job[[theirs, mine]]
                self.pre_prio[[mine, theirs]] = self.pre_prio[[theirs, mine]]
                swaps += 1
        return swaps

    def refuse_a_program_that_cannot_run_this(self) -> None:
        """`preempt-10k`'s check of the search, and the system scheduler
        itself on one full node: a system job has to take its room from a
        filler ten below it, and to report what it scored the node by.  A
        program that does not evict would leave the warm pass, which has
        no deadline, waiting for a job that cannot complete; one that
        reports no score (the tree before PR 38) leaves `correct` with
        nothing to hold the placement's arithmetic to."""
        super().refuse_a_program_that_cannot_run_this()
        from benchmark.harness import Refused, world_module
        from nomad_tpu import mock
        from nomad_tpu.scheduler.testing import Harness
        h = Harness()
        node = mock.node()
        h.store.upsert_node(h.next_index(), node)
        low = mock.job(priority=self.may_go_below)
        h.store.upsert_job(h.next_index(), low)
        filler = mock.alloc_for(low, node.id)
        cpu = int(node.node_resources.cpu.cpu_shares)
        (task,) = filler.allocated_resources.tasks.values()
        task.cpu_shares = cpu
        h.store.upsert_allocs(h.next_index(), [filler])
        job = world_module(self.cfg, "jobs").build(
            {"kind": "system", "cpu": cpu // 2, "memory_mb": 64,
             "priority": self.cfg["job_priority"],
             "datacenters": [node.datacenter]}, "refusal-probe")
        h.store.upsert_job(h.next_index(), job)
        h.process("system", mock.eval(job_id=job.id, type="system",
                                      priority=job.priority))
        placed = [a for a in h.store.allocs_by_job(job.namespace, job.id)
                  if a.desired_status == "run"]
        gone = h.store.alloc_by_id(filler.id)
        if len(placed) != 1 or gone.desired_status != "evict" \
                or gone.preempted_by_allocation != placed[0].id:
            raise Refused("the program's SystemScheduler does not place a "
                          "system job by evicting a filler ten below it: it "
                          "cannot place this configuration's jobs")
        if not [m for m in placed[0].metrics.score_meta
                if m.get("node_id") == node.id
                and "preemption" in m.get("scores", {})]:
            raise Refused("the program's SystemScheduler reports no score "
                          "with a placement (score_meta is empty): "
                          "`correct` cannot be decided")
