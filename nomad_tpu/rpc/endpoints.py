"""RPC endpoint registry (reference: the per-struct endpoints registered in
nomad/server.go:264+ — Job/Node/Eval/Alloc/Plan/Deployment/Operator/Status
— with handler names like "Job.Register" nomad/job_endpoint.go:81,
"Eval.Dequeue" eval_endpoint.go:104, "Plan.Submit" plan_endpoint.go:23).

Handlers take an args dict and return plain values; writes on a follower
raise RpcError("not_leader") carrying the leader hint so the caller can
forward (reference: structs.ErrNoLeader / forwardLeader, nomad/rpc.go).
"""
from __future__ import annotations

import time as _time
from typing import Callable, Dict, Optional

from nomad_tpu import deadline, tracing
from nomad_tpu.raft import MessageType, NotLeaderError
from nomad_tpu.structs import Evaluation, EvalStatus
from nomad_tpu.structs.evaluation import EvalTrigger


class RpcError(Exception):
    def __init__(self, kind: str, detail: str = "",
                 leader: Optional[str] = None,
                 retry_after: Optional[float] = None):
        super().__init__(f"{kind}: {detail}")
        self.kind = kind
        self.detail = detail
        self.leader = leader
        # overload refusals (admission_denied/brownout) carry the
        # client's Retry-After hint through the RPC layer to HTTP
        self.retry_after = retry_after


class _DryRunPlanner:
    """Planner that records plans without committing (reference: the
    Job.Plan path runs the scheduler with a no-op planner capturing the
    plan for annotation output)."""

    def __init__(self, store):
        self.store = store
        self.plans = []
        self.evals = []

    def submit_plan(self, plan):
        from nomad_tpu.structs.plan import PlanResult
        self.plans.append(plan)
        return PlanResult(node_update=plan.node_update,
                          node_allocation=plan.node_allocation,
                          node_preemptions=plan.node_preemptions,
                          deployment=plan.deployment,
                          alloc_index=self.store.latest_index)

    def create_evals(self, evals):
        self.evals.extend(evals)

    def update_eval(self, ev):
        pass

    def reblock_eval(self, ev):
        pass

    def refresh_snapshot(self, min_index: int = 0):
        return self.store.snapshot()


class Endpoints:
    def __init__(self, server):
        self.server = server
        self._methods: Dict[str, Callable] = {}
        for name in dir(self):
            if name.startswith("rpc_"):
                method = name[4:].replace("__", ".")
                self._methods[method] = getattr(self, name)

    # ------------------------------------------------------------- dispatch

    def handle(self, method: str, args: dict):
        # cross-region forwarding (reference nomad/rpc.go:21
        # forwardRegion): an explicit region that is not ours routes to
        # that region's servers before any local processing.  The
        # forwarded copy KEEPS the region field — a server whose WAN view
        # is stale may hand the request to the wrong region, and the
        # receiver must be able to forward it on — with a hop counter so
        # two regions with mutually-stale views can't ping-pong forever.
        region = (args or {}).get("region")
        if region and region != self.server.region:
            from nomad_tpu.federation import MAX_FORWARD_HOPS
            fwd = dict(args)
            hops = int(fwd.pop("_forward_hops", 0)) + 1
            if hops > MAX_FORWARD_HOPS:
                raise RpcError(
                    "forward_loop",
                    f"{method} for region {region!r} exceeded "
                    f"{MAX_FORWARD_HOPS} forwarding hops")
            fwd["_forward_hops"] = hops
            # decrement the deadline budget across the hop: decode what
            # the sender gave us, refuse if already spent, and re-encode
            # whatever remains for the next region
            if deadline.DEADLINE_KEY in fwd:
                dprev = deadline.bind(
                    deadline.from_wire(fwd[deadline.DEADLINE_KEY]))
                try:
                    if deadline.check("rpc.forward"):
                        raise RpcError(
                            "deadline_exceeded",
                            f"{method}: budget exhausted before the "
                            f"forward to region {region!r}")
                    fwd[deadline.DEADLINE_KEY] = deadline.to_wire()
                    return self.server.rpc_region(region, method, fwd)
                finally:
                    deadline.bind(dprev)
            return self.server.rpc_region(region, method, fwd)
        fn = self._methods.get(method)
        if fn is None:
            raise RpcError("unknown_method", method)
        # copy before stripping routing fields — the CALLER's dict must
        # come back unchanged (it may retry against another server)
        args = dict(args) if args else {}
        args.pop("region", None)
        args.pop("_forward_hops", None)
        # sampled trace context (absent = unsampled): bind it to this
        # thread for the duration of the dispatch so downstream code —
        # plan enqueue, raft apply — can attach child spans
        tctx = args.pop(tracing.TRACE_KEY, None)
        with tracing.span(f"rpc.{method}", ctx=tctx,
                          node=self.server.name):
            # per-request consistency on read RPCs (reference QueryOptions
            # riding every RPC): establish the read point before dispatch so
            # the handler's plain store reads serve at it
            mode = args.pop("consistency", None)
            # a read point the HTTP tier already established rides along as
            # `_read_mode`: it classifies the request for brownout shedding
            # (stale sheds last) without triggering a second begin_read
            shed_mode = args.pop("_read_mode", None) or mode
            # request deadline (absent = unbounded): decode the relative
            # wire budget into a local monotonic deadline and bind it for
            # the dispatch so every queueing stage downstream can check it
            dwire = args.pop(deadline.DEADLINE_KEY, None)
            dprev = None
            dbound = dwire is not None
            if dbound:
                dprev = deadline.bind(deadline.from_wire(dwire))
            try:
                if deadline.check("rpc"):
                    raise RpcError(
                        "deadline_exceeded",
                        f"{method}: budget exhausted before dispatch")
                # leader brownout: refuse sheddable classes with an honest
                # 503 before any queueing or raft work happens for them
                brownout = getattr(self.server, "brownout", None)
                if brownout is not None:
                    retry = brownout.shed(method, shed_mode or "default")
                    if retry is not None:
                        raise RpcError(
                            "brownout",
                            f"{method}: leader shedding load",
                            retry_after=retry)
                if mode is not None:
                    from nomad_tpu.serving.gate import READ_METHODS
                    if method in READ_METHODS:
                        # the read gate is a queueing stage: a bound request
                        # budget caps how long establishing the read point
                        # may retry across vacant leadership (the gate's own
                        # 5s cap otherwise outlives a 1s request many times)
                        rem = deadline.remaining()
                        try:
                            if rem is not None:
                                self.server.serving_gate.begin_read(
                                    mode, timeout=min(5.0, max(0.05, rem)))
                            else:
                                self.server.serving_gate.begin_read(mode)
                        except TimeoutError:
                            if deadline.check("read_gate"):
                                raise RpcError(
                                    "deadline_exceeded",
                                    f"{method}: read point not established "
                                    f"inside the request budget")
                            raise
                return fn(args)
            except NotLeaderError as e:
                raise RpcError("not_leader", leader=e.leader)
            finally:
                if dbound:
                    deadline.bind(dprev)

    def methods(self):
        return sorted(self._methods)

    # ------------------------------------------------------------- status

    def rpc_Status__Ping(self, args):
        return {"ok": True, "server": self.server.name}

    def rpc_Status__Leader(self, args):
        s = self.server
        if s.raft is None:
            return s.name if s.leader else None
        return s.raft.leader_id

    def rpc_Status__Members(self, args):
        """Serf-style member listing (reference nomad/serf.go members)."""
        s = self.server
        if s.membership is not None:
            return s.membership.member_list()
        peers = [s.name] if s.raft is None else [s.name] + list(s.raft.peers)
        return [{"name": n, "addr": None, "incarnation": 0,
                 "status": "alive"} for n in sorted(set(peers))]

    def rpc_Status__Peers(self, args):
        s = self.server
        if s.raft is None:
            return [s.name]
        return [s.name] + list(s.raft.peers)

    # ------------------------------------------------------------- raft

    def rpc_Raft__Apply(self, args):
        """Leader-side apply for writes forwarded from followers."""
        return self.server.apply_local(args["msg_type"], args["payload"])

    def rpc_Raft__ReadIndex(self, args):
        """Leader half of a follower read (Raft §6.4): confirm leadership
        and return the commit index the follower must apply up to before
        serving locally.  `lease=True` (the default consistency mode)
        serves from a still-valid leader lease with zero quorum rounds;
        `lease=False` (`?consistent`) always pays the heartbeat round."""
        s = self.server
        if s.raft is None:
            return {"index": s.store.latest_index}
        idx = s.raft.read_index(
            timeout=float(args.get("timeout", 5.0)),
            lease_ok=bool(args.get("lease", True)))
        return {"index": idx}

    # ------------------------------------------------------------- jobs

    def rpc_Job__Register(self, args):
        ev = self.server.register_job(args["job"])
        return {"eval_id": ev.id, "job_modify_index":
                args["job"].job_modify_index}

    def rpc_Job__Deregister(self, args):
        ev = self.server.deregister_job(
            args.get("namespace", "default"), args["job_id"],
            purge=args.get("purge", False))
        return {"eval_id": ev.id if ev else None}

    def rpc_Job__GetJob(self, args):
        return self.server.store.job_by_id(
            args.get("namespace", "default"), args["job_id"])

    def rpc_Job__List(self, args):
        ns = args.get("namespace")
        jobs = self.server.store.jobs()
        if ns and ns != "*":        # "*" = all namespaces (wildcard list)
            jobs = [j for j in jobs if j.namespace == ns]
        if args.get("federated"):
            jobs = list(jobs) + self._federated_job_list(ns)
        return jobs

    def _federated_job_list(self, ns):
        """Fan the listing out to every known remote region's leader.
        Dark regions are skipped, not fatal — a federated listing is a
        best-effort union (reference nomad's per-region API: the CLI
        queries regions independently and tolerates missing ones)."""
        from nomad_tpu.raft.transport import Unreachable

        remote = []
        for region in self.server.regions():
            if region == self.server.region:
                continue
            try:
                part = self.server.rpc_region(region, "Job.List", {
                    **({"namespace": ns} if ns else {})})
            except (Unreachable, RpcError):
                continue
            remote.extend(part or [])
        return remote

    def rpc_Job__Plan(self, args):
        """Dry-run scheduling (reference Job.Plan, nomad/job_endpoint.go:
        the scheduler runs against a snapshot with a CapturingPlanner and
        nothing commits; annotations carry the per-group diff)."""
        from nomad_tpu.scheduler import factory as sched_factory
        from nomad_tpu.structs import Evaluation
        import copy as _copy
        job = args["job"]
        server = self.server
        # store.snapshot() may return a shared memoized snapshot — shallow
        # copy before overlaying the hypothetical job so concurrent
        # workers never see the dry-run state
        snap = _copy.copy(server.store.snapshot())
        planner = _DryRunPlanner(server.store)
        snap.jobs = dict(snap.jobs.items())
        snap.jobs[(job.namespace, job.id)] = job
        ev = Evaluation(
            namespace=job.namespace, priority=job.priority, type=job.type,
            job_id=job.id, triggered_by=EvalTrigger.JOB_REGISTER,
            status=EvalStatus.PENDING, annotate_plan=True)
        sched = sched_factory.new_scheduler(
            job.type if job.type in ("service", "batch", "system",
                                     "sysbatch") else "service",
            snap, planner)
        sched.process(ev)
        plan = planner.plans[-1] if planner.plans else None
        ann = plan.annotations if plan is not None else None
        return {
            "annotations": ann,
            "failed_tg_allocs": getattr(sched, "failed_tg_allocs", None),
            "placements": sum(len(v) for v in
                              plan.node_allocation.values()) if plan else 0,
            "preemptions": sum(len(v) for v in
                               plan.node_preemptions.values()) if plan else 0,
            "job_modify_index": job.job_modify_index,
        }

    def rpc_Job__Dispatch(self, args):
        """Dispatch a parameterized job instance (reference Job.Dispatch):
        materialize a child job carrying the payload/meta."""
        import time as _t
        import uuid as _uuid
        ns = args.get("namespace", "default")
        parent = self.server.store.job_by_id(ns, args["job_id"])
        if parent is None:
            raise RpcError("not_found", args["job_id"])
        if not parent.is_parameterized():
            raise RpcError("bad_request",
                           f"job {args['job_id']} is not parameterized")
        cfg = parent.parameterized
        payload = args.get("payload") or ""
        if cfg.payload == "forbidden" and payload:
            raise RpcError("bad_request", "payload forbidden")
        if cfg.payload == "required" and not payload:
            raise RpcError("bad_request", "payload required")
        meta = dict(args.get("meta") or {})
        missing = [k for k in cfg.meta_required if k not in meta]
        if missing:
            raise RpcError("bad_request", f"missing meta: {missing}")
        unknown = [k for k in meta if k not in cfg.meta_required
                   and k not in cfg.meta_optional]
        if unknown:
            raise RpcError("bad_request", f"unknown meta: {unknown}")
        child = parent.copy()
        child.parent_id = parent.id
        child.id = (f"{parent.id}/dispatch-{int(_t.time())}-"
                    f"{str(_uuid.uuid4())[:8]}")
        child.name = child.id
        child.parameterized = None
        # wire payloads are base64 (matching the reference's []byte JSON
        # encoding); store the decoded bytes
        if isinstance(payload, str):
            import base64 as _b64
            try:
                child.payload = _b64.b64decode(payload, validate=True)
            except Exception:              # noqa: BLE001
                raise RpcError("bad_request", "payload must be base64")
        else:
            child.payload = payload
        child.meta = {**(parent.meta or {}), **meta}
        ev = self.server.register_job(child)
        return {"dispatched_job_id": child.id, "eval_id": ev.id}

    def rpc_Job__Revert(self, args):
        """Revert to a prior version (reference Job.Revert): re-register
        the stored version's job."""
        ns = args.get("namespace", "default")
        prior = self.server.store.job_version(
            ns, args["job_id"], args["version"])
        if prior is None:
            raise RpcError(
                "not_found",
                f"job {args['job_id']} version {args['version']}")
        current = self.server.store.job_by_id(ns, args["job_id"])
        if current is not None and current.version == prior.version:
            raise RpcError("bad_request",
                           "cannot revert to the current version")
        j = prior.copy()
        ev = self.server.register_job(j)
        return {"eval_id": ev.id, "job_version": j.version}

    def rpc_Job__Stability(self, args):
        self.server.set_job_stability(
            args.get("namespace", "default"), args["job_id"],
            args["version"], args["stable"])
        return {}

    def rpc_Job__Summary(self, args):
        return self.server.store.job_summary(
            args.get("namespace", "default"), args["job_id"])

    def rpc_Job__Allocations(self, args):
        return self.server.store.allocs_by_job(
            args.get("namespace", "default"), args["job_id"])

    def rpc_Job__Evaluations(self, args):
        return self.server.store.evals_by_job(
            args.get("namespace", "default"), args["job_id"])

    # ------------------------------------------------------------- nodes

    def rpc_Node__Register(self, args):
        self.server.register_node(args["node"])
        return {"heartbeat_ttl": self.server.config.heartbeat_ttl}

    def rpc_Node__UpdateStatus(self, args):
        """Heartbeats reset the TTL; an explicit status is a real
        transition (init->ready included) that triggers node evals
        (reference Node.UpdateStatus, node_endpoint.go:396)."""
        if args.get("heartbeat") and not args.get("status"):
            ttl = self.server.node_heartbeat(args["node_id"])
            return {"heartbeat_ttl": ttl}
        node = self.server.store.node_by_id(args["node_id"])
        status = args["status"]
        if node is not None and node.status == status:
            # no-op transition; still counts as liveness
            ttl = self.server.node_heartbeat(args["node_id"])
            return {"heartbeat_ttl": ttl, "eval_ids": []}
        evals = self.server.update_node_status(args["node_id"], status)
        ttl = self.server.heartbeats.heartbeat(args["node_id"]) \
            if self.server.leader else self.server.config.heartbeat_ttl
        return {"eval_ids": [e.id for e in evals], "heartbeat_ttl": ttl}

    def rpc_Node__UpdateFingerprint(self, args):
        """Device/attribute re-fingerprint DELTA: coalesces through the
        leader's heartbeat batcher as one NodeFingerprintBatch entry per
        flush instead of a full Node.Register per change.  Returns
        known=False for an unregistered node so the client falls back
        to Node.Register."""
        update = {k: args[k] for k in ("devices", "attributes")
                  if k in args}
        return self.server.node_update_fingerprint(args["node_id"],
                                                   update)

    def rpc_Node__BatchHeartbeat(self, args):
        """Fleet-scale liveness: one RPC re-arms many node TTLs through
        the real heartbeat path (the 10K-agent drivers' steady state —
        the leader coalesces any implied status writes into one
        NodeHeartbeatBatch entry per flush tick)."""
        ttl = self.server.node_heartbeats(args["node_ids"])
        return {"heartbeat_ttl": ttl}

    @staticmethod
    def _redact_node(node):
        """Strip the node secret before it leaves the servers (reference
        node_endpoint.go GetNode clears Node.SecretID)."""
        if node is None or not getattr(node, "secret_id", ""):
            return node
        import copy
        node = copy.copy(node)
        node.secret_id = ""
        return node

    def rpc_Node__List(self, args):
        return [self._redact_node(n) for n in self.server.store.nodes()]

    def rpc_Node__GetNode(self, args):
        return self._redact_node(
            self.server.store.node_by_id(args["node_id"]))

    def rpc_Node__GetAllocs(self, args):
        return self.server.store.allocs_by_node(args["node_id"])

    def rpc_Node__UpdateDrain(self, args):
        self.server.drainer.drain_node(
            args["node_id"], deadline_s=args.get("deadline_s", 3600.0),
            ignore_system_jobs=args.get("ignore_system_jobs", False))
        return {}

    def rpc_Node__CancelDrain(self, args):
        self.server.drainer.cancel_drain(args["node_id"])
        return {}

    def rpc_Node__UpdateEligibility(self, args):
        self.server.apply(MessageType.NODE_UPDATE_ELIGIBILITY,
                          {"node_id": args["node_id"],
                           "eligibility": args["eligibility"]})
        return {}

    def rpc_Node__UpdateAlloc(self, args):
        """Client pushes task/alloc state (reference Node.UpdateAlloc,
        node_endpoint.go:1073: failed allocs trigger reschedule evals)."""
        updates = args["allocs"]
        self.server.apply(MessageType.ALLOC_CLIENT_UPDATE,
                          {"allocs": updates})
        evals = []
        seen_jobs = set()
        for u in updates:
            # terminal allocs lose their secrets leases (vault.go
            # RevokeTokens on alloc stop/GC)
            if u.client_status in ("complete", "failed", "lost"):
                self.server.secrets.revoke_for_alloc(u.id)
            if u.client_status != "failed":
                continue
            stored = self.server.store.alloc_by_id(u.id)
            if stored is None:
                continue
            key = (stored.namespace, stored.job_id)
            if key in seen_jobs:
                continue
            seen_jobs.add(key)
            job = stored.job or self.server.store.job_by_id(*key)
            if job is None or job.stopped():
                continue
            evals.append(Evaluation(
                namespace=stored.namespace, priority=job.priority,
                type=job.type, job_id=job.id,
                triggered_by=EvalTrigger.RETRY_FAILED_ALLOC,
                status=EvalStatus.PENDING))
        if evals:
            self.server.create_evals(evals)
        return {"eval_ids": [e.id for e in evals]}

    def rpc_Node__GetClientAllocs(self, args):
        """Blocking query for a node's allocations (reference
        Node.GetClientAllocs, node_endpoint.go: clients long-poll with
        their last seen index)."""
        store = self.server.store
        min_index = args.get("min_index", 0)
        timeout = min(args.get("timeout", 2.0), 30.0)
        # the long-poll park must not outlive the request budget: a
        # deadline-bound caller gets at most its remaining slice, then
        # the current state (long-poll semantics, not an error)
        rem = deadline.remaining()
        if rem is not None:
            timeout = min(timeout, rem)
        store.wait_for_index(min_index + 1, timeout=timeout)
        return {"index": store.latest_index,
                "allocs": store.allocs_by_node(args["node_id"])}

    def rpc_Node__Deregister(self, args):
        self.server.apply(MessageType.NODE_DEREGISTER,
                          {"node_id": args["node_id"]})
        return {}

    # ------------------------------------------------------------- evals

    def rpc_Eval__GetEval(self, args):
        return self.server.store.eval_by_id(args["eval_id"])

    def rpc_Eval__List(self, args):
        ns = args.get("namespace")
        evals = self.server.store.evals()
        if ns and ns != "*":
            evals = [e for e in evals if e.namespace == ns]
        return evals

    def rpc_Eval__Dequeue(self, args):
        """Worker dequeue with lease token (eval_endpoint.go:104); only the
        leader's broker has evals."""
        gate = getattr(self.server, "admission", None)
        ns = args.get("namespace", "default")
        if gate is not None and gate.enabled:
            # deny-by-503 before touching the broker: an over-limit
            # dequeue flood must not contend the broker lock either
            retry = gate.try_acquire(ns)
            if retry is not None:
                raise RpcError(
                    "admission_denied",
                    f"Eval.Dequeue over limit for namespace {ns!r}",
                    retry_after=retry)
        try:
            ev, token = self.server.broker.dequeue(
                args["schedulers"], timeout=args.get("timeout", 0.1))
        finally:
            if gate is not None and gate.enabled:
                gate.release(ns)
        if ev is None:
            return None
        # wait_index: the leader's store index at dequeue time.  A
        # redelivered eval may already have had a plan committed for it
        # (nack after crash-after-commit, lease expiry, failover); a
        # follower worker scheduling from a snapshot older than this
        # index would not see those allocs and double-place the job
        # (reference eval_endpoint.go Dequeue GetWaitIndex).
        resp = {"eval": ev, "token": token,
                "wait_index": self.server.store.latest_index}
        # hand the eval's sampled trace context (re-noted by the broker
        # at dequeue, after the queue-wait span) to the remote worker so
        # scheduling spans join the trace
        ctx = tracing.take_eval_ctx(ev.id)
        if ctx is not None:
            resp["trace"] = ctx
        return resp

    def rpc_Eval__Ack(self, args):
        return {"ok": self.server.broker.ack(args["eval_id"], args["token"])}

    def rpc_Eval__Nack(self, args):
        return {"ok": self.server.broker.nack(args["eval_id"], args["token"])}

    def rpc_Eval__Update(self, args):
        self.server.update_eval(args["eval"])
        return {}

    def rpc_Eval__Create(self, args):
        self.server.create_evals(args["evals"])
        return {}

    def rpc_Eval__Reblock(self, args):
        self.server.blocked_evals.block(args["eval"])
        return {}

    # ------------------------------------------------------------- allocs

    def rpc_Alloc__GetAlloc(self, args):
        return self.server.store.alloc_by_id(args["alloc_id"])

    def rpc_Alloc__List(self, args):
        ns = args.get("namespace")
        allocs = self.server.store.allocs()
        if ns and ns != "*":
            allocs = [a for a in allocs if a.namespace == ns]
        return allocs

    def rpc_Alloc__Stop(self, args):
        """Stop a single allocation and reschedule-evaluate its job."""
        a = self.server.store.alloc_by_id(args["alloc_id"])
        if a is None:
            raise RpcError("not_found", args["alloc_id"])
        u = a.copy()
        u.desired_status = "stop"
        u.desired_description = "alloc stopped by user"
        self.server.apply(MessageType.ALLOC_UPDATE, {"allocs": [u]})
        job = a.job or self.server.store.job_by_id(a.namespace, a.job_id)
        ev = Evaluation(
            namespace=a.namespace, priority=job.priority if job else 50,
            type=job.type if job else "service", job_id=a.job_id,
            triggered_by=EvalTrigger.ALLOC_STOP, status=EvalStatus.PENDING)
        self.server.create_evals([ev])
        return {"eval_id": ev.id}

    # ------------------------------------------------------------- plans

    def rpc_Plan__Submit(self, args):
        """Leader-side plan submission (plan_endpoint.go:23): enqueue
        (gated on the submitter's eval lease still being live) and block
        for the applier's result."""
        plan = args["plan"]
        gate = getattr(self.server, "admission", None)
        ns = (plan.job.namespace or "default") if plan.job else "default"
        if gate is not None and gate.enabled:
            # per-namespace bucket keyed on the PLAN's tenant: an
            # abusive tenant's submissions shed here before its load
            # reaches the applier and starves victim tenants
            retry = gate.try_acquire(ns)
            if retry is not None:
                raise RpcError(
                    "admission_denied",
                    f"Plan.Submit over limit for namespace {ns!r}",
                    retry_after=retry)
        try:
            # shed before enqueue: an already-expired submission would
            # only burn an applier slot to produce an unwanted result
            if deadline.check("plan.submit"):
                raise RpcError(
                    "deadline_exceeded",
                    "plan.submit: deadline expired before enqueue")
            pending = self.server.enqueue_plan(plan)
            # clamp the applier wait to the remaining budget so a
            # deadline-bound submitter never parks the full 30 s
            timeout = 30.0
            rem = deadline.remaining()
            if rem is not None:
                timeout = min(timeout, rem)
            return pending.future.result(timeout=timeout)
        finally:
            if gate is not None and gate.enabled:
                gate.release(ns)

    # ------------------------------------------------------------- deploys

    def rpc_Deployment__List(self, args):
        ns = args.get("namespace")
        deps = self.server.store.deployments()
        if ns and ns != "*":
            deps = [d for d in deps if d.namespace == ns]
        return deps

    def rpc_Deployment__GetDeployment(self, args):
        return self.server.store.deployment_by_id(args["deployment_id"])

    def rpc_Deployment__Promote(self, args):
        ok = self.server.deployment_watcher.promote(
            args["deployment_id"], groups=args.get("groups"))
        return {"ok": ok}

    def rpc_Deployment__Fail(self, args):
        return {"ok": self.server.deployment_watcher.fail(
            args["deployment_id"])}

    def rpc_Deployment__Pause(self, args):
        return {"ok": self.server.deployment_watcher.pause(
            args["deployment_id"], args.get("pause", True))}

    def rpc_Deployment__MultiregionFail(self, args):
        """Cross-region failure propagation target: a peer region's
        multiregion deployment failed, fail/revert ours.  Safe on a
        follower — the resulting writes forward to our leader via
        apply()."""
        return {"ok": self.server.deployment_watcher.multiregion_fail(
            args.get("namespace", "default"), args["job_id"],
            args.get("rollout", ""))}

    # ------------------------------------------------------------- operator

    # --- CSI volumes / plugins (reference nomad/csi_endpoint.go)

    def rpc_CSIVolume__List(self, args):
        ns = args.get("namespace")
        return [v.stub() for v in self.server.store.csi_volumes(ns)]

    def rpc_CSIVolume__Get(self, args):
        vol = self.server.store.csi_volume_by_id(
            args.get("namespace", "default"), args["volume_id"])
        if vol is None:
            raise RpcError(f"volume {args['volume_id']} not found")
        return vol

    def rpc_CSIVolume__Register(self, args):
        from nomad_tpu.raft.fsm import MessageType as MT
        self.server.apply(MT.CSI_VOLUME_REGISTER, {"volume": args["volume"]})
        return {}

    def rpc_CSIVolume__Deregister(self, args):
        from nomad_tpu.raft.fsm import MessageType as MT
        self.server.apply(MT.CSI_VOLUME_DEREGISTER, {
            "namespace": args.get("namespace", "default"),
            "volume_id": args["volume_id"],
            "force": args.get("force", False)})
        return {}

    def rpc_CSIVolume__Claim(self, args):
        from nomad_tpu.raft.fsm import MessageType as MT
        self.server.apply(MT.CSI_VOLUME_CLAIM, {
            "namespace": args.get("namespace", "default"),
            "volume_id": args["volume_id"],
            "claim": args["claim"]})
        return {}

    def rpc_CSIPlugin__List(self, args):
        return [p.stub() for p in self.server.store.csi_plugins()]

    def rpc_CSIPlugin__Get(self, args):
        plug = self.server.store.csi_plugin_by_id(args["plugin_id"])
        if plug is None:
            raise RpcError(f"plugin {args['plugin_id']} not found")
        return plug

    def rpc_Operator__SchedulerGetConfiguration(self, args):
        return self.server.store.scheduler_config

    def rpc_Operator__SchedulerSetConfiguration(self, args):
        self.server.apply(MessageType.SCHEDULER_CONFIG,
                          {"config": args["config"]})
        return {}

    def rpc_Operator__RaftGetConfiguration(self, args):
        """The replicated raft membership (reference
        `/v1/operator/raft/configuration`).  Served from the LOCAL node:
        the configuration is replicated state, and an operator debugging
        a split wants each server's own view."""
        s = self.server
        if s.raft is None:
            return {"voters": [s.name], "nonvoters": [], "index": 0,
                    "leader": s.name if s.leader else None, "term": 0}
        return s.raft.configuration()

    def rpc_Operator__RaftRemovePeer(self, args):
        """Force-remove a (possibly dead) server from the raft
        configuration (reference `nomad operator raft remove-peer`)."""
        s = self.server
        if s.raft is None:
            raise RpcError("no_raft", "dev mode has no raft peers")
        try:
            index = s.raft.remove_server(args["name"],
                                         timeout=args.get("timeout", 10.0))
        except NotLeaderError:
            # incl. the transfer-then-demote hop: removing the leader
            # itself transfers leadership first, then the successor
            # performs the removal
            return s.rpc_leader("Operator.RaftRemovePeer", args)
        return {"index": index}

    def rpc_Operator__TransferLeadership(self, args):
        """Graceful leadership handoff (reference `nomad operator
        transfer-leadership`): optional explicit target, else the most
        caught-up voter."""
        s = self.server
        if s.raft is None:
            raise RpcError("no_raft", "dev mode has no raft peers")
        try:
            ok = s.raft.transfer_leadership(args.get("name"))
        except NotLeaderError:
            return s.rpc_leader("Operator.TransferLeadership", args)
        return {"transferred": ok, "leader": s.raft.leader_id}

    def rpc_Operator__Integrity(self, args):
        """Replica-integrity plane view (reference shape:
        `/v1/operator/autopilot/health`): THIS server's last checkpoint
        digest, quarantine state and repair counters — the leader's view
        includes the per-peer report table the majority vote runs over.
        Served locally on purpose: an operator debugging divergence
        wants each replica's own digest, and a quarantined replica must
        still answer."""
        s = self.server
        if s.raft is None:
            return {"server": s.name, "quarantined": False,
                    "quarantine_reason": "", "last": None, "peers": {},
                    "counters": {}, "leader": True}
        view = s.raft.integrity.operator_view()
        view["leader"] = s.raft.is_leader
        return view

    def rpc_Operator__SnapshotSave(self, args):
        if self.server.raft is not None:
            self.server.raft.force_snapshot()
            return {"ok": True}
        path = args.get("path")
        if path:
            self.server.save_snapshot(path)
        return {"ok": True}

    # ------------------------------------------------------------- search

    def rpc_Search__PrefixSearch(self, args):
        """Server-side prefix search across contexts (reference
        nomad/search_endpoint.go:518 PrefixSearch; 20-match truncation
        per context like truncateLimit).  `namespaces`: optional
        visibility filter computed by the agent from the caller's ACL."""
        prefix = args.get("prefix", "")
        context = args.get("context", "all")
        visible = args.get("namespaces")   # None = all namespaces
        store = self.server.store

        def ns_ok(ns):
            return visible is None or ns in visible

        out, trunc = {}, {}

        def add(name, ids):
            matches = sorted(i for i in ids if i.startswith(prefix))
            trunc[name] = len(matches) > 20
            out[name] = matches[:20]

        if context in ("all", "jobs"):
            add("jobs", [j.id for j in store.jobs() if ns_ok(j.namespace)])
        if context in ("all", "nodes"):
            add("nodes", [n.id for n in store.nodes()])
        if context in ("all", "evals"):
            add("evals", [e.id for e in store.evals()
                          if ns_ok(e.namespace)])
        if context in ("all", "allocs"):
            add("allocs", [a.id for a in store.allocs()
                           if ns_ok(a.namespace)])
        if context in ("all", "deployment"):
            add("deployment", [d.id for d in store.deployments()
                               if ns_ok(d.namespace)])
        if context in ("all", "plugins"):
            add("plugins", [p.get("id", "") if isinstance(p, dict) else p.id
                            for p in store.csi_plugins()])
        if context in ("all", "volumes"):
            add("volumes", [v.id for v in store.csi_volumes()
                            if ns_ok(v.namespace)])
        if context in ("all", "namespaces"):
            add("namespaces", [ns.name for ns in store.namespaces()])
        return {"matches": out, "truncations": trunc}

    # ------------------------------------------------------------- namespaces

    def rpc_Namespace__List(self, args):
        return self.server.namespaces()

    def rpc_Namespace__Upsert(self, args):
        try:
            self.server.upsert_namespace(
                args["name"], args.get("description", ""),
                args.get("quota", ""))
        except ValueError as e:
            raise RpcError("bad_request", str(e))
        return {}

    def rpc_Namespace__Delete(self, args):
        try:
            self.server.delete_namespace(args["name"])
        except ValueError as e:
            raise RpcError("bad_request", str(e))
        return {}

    # ------------------------------------------------------------- quotas

    def rpc_Quota__List(self, args):
        return self.server.quota_specs()

    def rpc_Quota__GetQuota(self, args):
        spec = self.server.quota_spec(args["name"])
        if spec is None:
            raise RpcError("not_found", args["name"])
        return spec

    def rpc_Quota__Upsert(self, args):
        self.server.upsert_quota_spec(args["spec"])
        return {}

    def rpc_Quota__Delete(self, args):
        try:
            self.server.delete_quota_spec(args["name"])
        except ValueError as e:
            raise RpcError("bad_request", str(e))
        return {}

    def rpc_Quota__Usage(self, args):
        ns = args.get("namespace")
        if ns and ns != "*":
            return {ns: self.server.quota_usage(ns)}
        return self.server.quota_usages()

    # ------------------------------------------------------------- scaling

    def rpc_Job__Scale(self, args):
        try:
            ev = self.server.scale_job(
                args.get("namespace", "default"), args["job_id"],
                args["group"], count=args.get("count"),
                message=args.get("message", ""),
                error=bool(args.get("error", False)),
                meta=args.get("meta"))
        except ValueError as e:
            raise RpcError("bad_request", str(e))
        return {"eval_id": ev.id if ev is not None else None}

    def rpc_Job__ScaleStatus(self, args):
        st = self.server.job_scale_status(
            args.get("namespace", "default"), args["job_id"])
        if st is None:
            raise RpcError("not_found", args["job_id"])
        return st

    def rpc_Scaling__ListPolicies(self, args):
        """reference nomad/scaling_endpoint.go ListPolicies: one row per
        (job, group) scaling stanza."""
        out = []
        for job, group, pol in self.server.store.scaling_policies(
                args.get("namespace")):
            out.append({
                "id": f"{job.namespace}/{job.id}/{group}",
                "namespace": job.namespace,
                "target": {"Namespace": job.namespace, "Job": job.id,
                           "Group": group},
                "min": pol.min, "max": pol.max, "enabled": pol.enabled,
            })
        return out

    def rpc_Scaling__GetPolicy(self, args):
        pid = args["id"]
        for job, group, pol in self.server.store.scaling_policies(None):
            if f"{job.namespace}/{job.id}/{group}" == pid:
                return {"id": pid, "namespace": job.namespace,
                        "target": {"Namespace": job.namespace,
                                   "Job": job.id, "Group": group},
                        "min": pol.min, "max": pol.max,
                        "enabled": pol.enabled, "policy": pol.policy}
        raise RpcError("not_found", pid)

    # ------------------------------------------------------------- services

    def rpc_Service__Upsert(self, args):
        self.server.apply(MessageType.SERVICE_REGISTER,
                          {"services": args["services"]})
        return {}

    def rpc_Service__DeleteByAlloc(self, args):
        self.server.apply(MessageType.SERVICE_DEREGISTER,
                          {"alloc_id": args["alloc_id"]})
        return {}

    def rpc_Service__Delete(self, args):
        self.server.apply(MessageType.SERVICE_DEREGISTER,
                          {"ids": [args["id"]]})
        return {}

    def rpc_Service__List(self, args):
        """Grouped {service_name: count} listing (reference
        nomad/service_registration_endpoint.go List)."""
        svcs = self.server.store.services(args.get("namespace"))
        names = {}
        for s in svcs:
            names.setdefault((s.namespace, s.service_name), 0)
            names[(s.namespace, s.service_name)] += 1
        return [{"namespace": ns, "service_name": n, "instances": c}
                for (ns, n), c in sorted(names.items())]

    def rpc_Service__GetService(self, args):
        return self.server.store.services_by_name(
            args.get("namespace", "default"), args["service_name"])

    # ------------------------------------------------------------- secrets

    def _require_leader(self):
        s = self.server
        if s.raft is not None and not s.leader:
            raise NotLeaderError(s.raft.leader_id)

    def rpc_Secrets__Put(self, args):
        """Admin write into the embedded KV (the stand-in for seeding
        Vault; reference operators do this against Vault directly).
        With ACLs on, only a management token may seed secrets."""
        self._require_leader()
        if self.server.acl_enabled:
            acl = self.server.resolve_token(args.get("token", ""))
            if acl is None or not acl.management:
                raise RpcError("permission_denied",
                               "Secrets.Put requires a management token")
        return {"version": self.server.secrets.put(
            args["path"], dict(args.get("data") or {}))}

    def rpc_Secrets__Derive(self, args):
        """Per-task token derivation (reference nomad/vault.go
        CreateToken via client_endpoint DeriveVaultToken): policies come
        from the task's vault stanza in the server's own state, never
        from the caller.  The caller must prove it IS the node the alloc
        runs on — node id + node secret (node_endpoint.go
        deriveVaultToken NodeSecretID check) — so a compromised alloc
        cannot mint tokens for tasks on other machines."""
        self._require_leader()
        import hmac
        node = self.server.store.node_by_id(args.get("node_id", ""))
        secret = args.get("node_secret_id", "")
        if (node is None or not node.secret_id or not secret
                or not hmac.compare_digest(node.secret_id, secret)):
            raise RpcError("permission_denied", "node secret mismatch")
        alloc = self.server.store.alloc_by_id(args["alloc_id"])
        if alloc is None or alloc.job is None:
            raise RpcError("not_found", "alloc or its job")
        if alloc.node_id != node.id:
            raise RpcError("permission_denied",
                           "alloc does not run on the requesting node")
        if alloc.terminal_status() or alloc.client_terminal_status():
            # revocation on stop must not be bypassed by a re-derive
            raise RpcError("invalid", "alloc is terminal")
        tg = alloc.job.lookup_task_group(alloc.task_group)
        task = next((t for t in (tg.tasks if tg else [])
                     if t.name == args["task"]), None)
        if task is None or not task.vault:
            raise RpcError("invalid", "task has no vault stanza")
        policies = list(task.vault.get("policies") or [])
        ttl = task.vault.get("ttl_s")
        return self.server.secrets.derive_token(
            alloc.id, task.name, policies,
            float(ttl) if ttl else None)

    def rpc_Secrets__Renew(self, args):
        self._require_leader()
        try:
            return self.server.secrets.renew(args["token"])
        except Exception as e:                       # noqa: BLE001
            raise RpcError("invalid", str(e))

    def rpc_Secrets__Read(self, args):
        self._require_leader()
        try:
            data, version = self.server.secrets.read(
                args["path"], args.get("token", ""))
        except Exception as e:                       # noqa: BLE001
            raise RpcError("invalid", str(e))
        return {"data": data, "version": version}

    def rpc_Secrets__Version(self, args):
        self._require_leader()
        try:
            return {"version": self.server.secrets.version(
                args["path"], args.get("token", ""))}
        except Exception as e:                       # noqa: BLE001
            raise RpcError("invalid", str(e))

    # ------------------------------------------------------------- regions

    def rpc_Status__Regions(self, args):
        return self.server.regions()
