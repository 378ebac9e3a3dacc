"""CLI command tree (reference: command/ — one module per subcommand
there; one dispatcher here).  Address/token resolution mirrors the
reference: -address / NOMAD_ADDR, -token / NOMAD_TOKEN.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import List, Optional

from nomad_tpu.api import ApiClient, ApiError


def _fmt_table(rows: List[List[str]], header: List[str]) -> str:
    cols = [header] + [[str(c) for c in r] for r in rows]
    widths = [max(len(r[i]) for r in cols) for i in range(len(header))]
    lines = []
    for r in cols:
        lines.append("  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip())
    return "\n".join(lines)


def _short(id_: str) -> str:
    return id_[:8] if id_ else ""


def _ago(ts: float) -> str:
    if not ts:
        return "-"
    d = time.time() - ts
    for unit, div in (("s", 1), ("m", 60), ("h", 3600), ("d", 86400)):
        if d < div * 100 or unit == "d":
            return f"{d/div:.0f}{unit} ago"
    return "-"


class Cli:
    def __init__(self, api: ApiClient, out=sys.stdout):
        self.api = api
        self.out = out

    def p(self, *args, end: str = "\n") -> None:
        print(*args, file=self.out, end=end)
        if end != "\n":
            self.out.flush()

    # ------------------------------------------------------------- agent

    def cmd_agent(self, args) -> int:
        from nomad_tpu.agent import Agent, AgentConfig
        if getattr(args, "config_file", ""):
            # reference merge order (command/agent/config.go): config
            # files first, CLI flags override the merged result
            from nomad_tpu.agent.config_file import load_config_file
            cfg = load_config_file(args.config_file)
            flag_overrides = {
                "name": ("name", "agent-1"),
                "bind": ("http_host", "127.0.0.1"),
                "port": ("http_port", 4646),
                "num_schedulers": ("num_schedulers", 4),
            }
            for flag, (attr, default) in flag_overrides.items():
                v = getattr(args, flag)
                if v != default:
                    setattr(cfg, attr, v)
            if args.dev:
                cfg.dev_mode = True
                cfg.server_enabled = cfg.client_enabled = True
            if args.server:
                cfg.server_enabled = True
            if args.client:
                cfg.client_enabled = True
            if args.acl_enabled:
                cfg.acl_enabled = True
            if args.data_dir:
                cfg.data_dir = args.data_dir
        else:
            cfg = AgentConfig(
                name=args.name,
                dev_mode=args.dev,
                server_enabled=args.dev or args.server,
                client_enabled=args.dev or args.client,
                http_host=args.bind,
                http_port=args.port,
                num_schedulers=args.num_schedulers,
                acl_enabled=args.acl_enabled,
                data_dir=args.data_dir or None,
            )
        agent = Agent(cfg)
        agent.start()
        self.p(f"==> nomad-tpu agent started: http={agent.http_addr} "
               f"server={cfg.server_enabled} client={cfg.client_enabled}")
        self.p("==> Ctrl-C to exit")
        try:
            while True:
                time.sleep(1.0)
        except KeyboardInterrupt:
            self.p("==> caught interrupt, shutting down")
            agent.stop()
        return 0

    # ------------------------------------------------------------- job

    @staticmethod
    def _job_vars(args) -> dict:
        """-var NAME=VALUE + -var-file files (HCL `name = value` lines,
        reference jobspec2 VarFiles/Vars)."""
        out = {}
        for path in getattr(args, "var_file", None) or []:
            from nomad_tpu.jobspec.expr import evaluate
            from nomad_tpu.jobspec.hcl import parse_hcl
            root = parse_hcl(open(path).read())
            evaluate(root)        # var files may use functions/locals
            out.update(root.attrs)
        for kv in getattr(args, "var", None) or []:
            name, _, value = kv.partition("=")
            out[name] = value
        return out

    def cmd_job_run(self, args) -> int:
        from nomad_tpu.api.codec import from_wire
        from nomad_tpu.jobspec import parse_job_file
        from nomad_tpu.structs import Job
        job = parse_job_file(args.file, self._job_vars(args))
        if args.check_index is not None:
            job.job_modify_index = args.check_index
        from nomad_tpu.api.codec import to_wire
        resp = self.api.jobs.register(job)
        self.p(f"==> Evaluation \"{_short(resp['EvalID'])}\" created")
        if args.detach:
            return 0
        return self._monitor_eval(resp["EvalID"])

    def _monitor_eval(self, eval_id: str, timeout: float = 60.0) -> int:
        deadline = time.time() + timeout
        last_status = ""
        while time.time() < deadline:
            ev = self.api.evaluations.info(eval_id)
            if ev.status != last_status:
                self.p(f"    Evaluation status: {ev.status}")
                last_status = ev.status
            if ev.status in ("complete", "failed", "canceled"):
                if ev.status == "complete":
                    allocs = self.api.evaluations.allocations(eval_id)
                    for a in allocs:
                        self.p(f"    Allocation \"{_short(a.id)}\" created "
                               f"on node \"{_short(a.node_id)}\"")
                self.p(f"==> Evaluation \"{_short(eval_id)}\" finished "
                       f"with status \"{ev.status}\"")
                return 0 if ev.status == "complete" else 2
            time.sleep(0.3)
        self.p("==> Timed out waiting for evaluation")
        return 1

    def cmd_job_status(self, args) -> int:
        if not args.job_id:
            jobs = self.api.jobs.list()
            if not jobs:
                self.p("No running jobs")
                return 0
            self.p(_fmt_table(
                [[j["ID"], j["Type"], str(j["Priority"]), j["Status"]]
                 for j in jobs],
                ["ID", "Type", "Priority", "Status"]))
            return 0
        job = self.api.jobs.info(args.job_id)
        self.p(f"ID            = {job.id}")
        self.p(f"Name          = {job.name}")
        self.p(f"Type          = {job.type}")
        self.p(f"Priority      = {job.priority}")
        self.p(f"Datacenters   = {','.join(job.datacenters)}")
        self.p(f"Namespace     = {job.namespace}")
        self.p(f"Status        = {job.status}")
        self.p(f"Version       = {job.version}")
        self.p("")
        summary = self.api.jobs.summary(job.id)
        if summary:
            self.p("Summary")
            rows = []
            for tg, counts in (summary.get("summary") or {}).items():
                rows.append([tg] + [str(counts.get(k, 0)) for k in
                                    ("queued", "starting", "running",
                                     "complete", "failed", "lost")])
            self.p(_fmt_table(rows, ["Task Group", "Queued", "Starting",
                                     "Running", "Complete", "Failed",
                                     "Lost"]))
            self.p("")
        allocs = self.api.jobs.allocations(args.job_id)
        if allocs:
            self.p("Allocations")
            self.p(_fmt_table(
                [[_short(a["ID"]), _short(a["NodeID"]), a["TaskGroup"],
                  a["DesiredStatus"], a["ClientStatus"]] for a in allocs],
                ["ID", "Node ID", "Task Group", "Desired", "Status"]))
        return 0

    def cmd_job_stop(self, args) -> int:
        resp = self.api.jobs.deregister(args.job_id, purge=args.purge)
        self.p(f"==> Evaluation \"{_short(resp['EvalID'] or '')}\" created")
        if args.detach or not resp["EvalID"]:
            return 0
        return self._monitor_eval(resp["EvalID"])

    def cmd_job_plan(self, args) -> int:
        from nomad_tpu.jobspec import parse_job_file
        job = parse_job_file(args.file, self._job_vars(args))
        resp = self.api.jobs.plan(job)
        ann = resp.get("annotations") or {}
        tg_updates = (ann.get("desired_tg_updates") or {})
        for tg, upd in tg_updates.items():
            self.p(f"Task Group: \"{tg}\"")
            for field in ("place", "stop", "migrate", "in_place_update",
                          "destructive_update", "canary", "ignore"):
                v = upd.get(field, 0) if isinstance(upd, dict) else \
                    getattr(upd, field, 0)
                if v:
                    self.p(f"  {field}: {v}")
        self.p(f"==> Placements: {resp['placements']}  "
               f"Preemptions: {resp['preemptions']}")
        failed = resp.get("failed_tg_allocs")
        if failed:
            self.p(f"==> WARNING: failed placements: {list(failed)}")
        self.p("Job Modify Index: "
               f"{resp.get('job_modify_index', 0)}")
        return 0

    def cmd_job_inspect(self, args) -> int:
        from nomad_tpu.api.codec import to_wire
        job = self.api.jobs.info(args.job_id)
        self.p(json.dumps(to_wire(job), indent=2, sort_keys=True))
        return 0

    def cmd_job_dispatch(self, args) -> int:
        import base64
        payload = ""
        if args.payload_file:
            with open(args.payload_file, "rb") as fh:
                payload = base64.b64encode(fh.read()).decode()
        meta = dict(kv.split("=", 1) for kv in args.meta or [])
        resp = self.api.jobs.dispatch(args.job_id, payload=payload,
                                      meta=meta)
        self.p(f"Dispatched Job ID = {resp['dispatched_job_id']}")
        self.p(f"Evaluation ID     = {_short(resp['eval_id'])}")
        return 0

    def cmd_job_history(self, args) -> int:
        for v in self.api.jobs.versions(args.job_id):
            self.p(f"Version     = {v['version']}")
            self.p(f"Stable      = {v['stable']}")
            self.p(f"Submit Date = {_ago(v.get('submit_time', 0))}")
            self.p("")
        return 0

    def cmd_job_revert(self, args) -> int:
        resp = self.api.jobs.revert(args.job_id, args.version)
        self.p(f"==> Reverted to version {resp['job_version']}; "
               f"evaluation \"{_short(resp['eval_id'])}\" created")
        return 0

    def cmd_job_periodic_force(self, args) -> int:
        resp = self.api.jobs.periodic_force(args.job_id)
        self.p(f"Dispatched Job ID = {resp['DispatchedJobID']}")
        return 0

    def cmd_job_validate(self, args) -> int:
        from nomad_tpu.jobspec import parse_job_file
        try:
            job = parse_job_file(args.file, self._job_vars(args))
        except Exception as e:                      # noqa: BLE001
            self.p(f"Job validation errors: {e}")
            return 1
        if not job.task_groups:
            self.p("Job validation errors: no task groups")
            return 1
        self.p("Job validation successful")
        return 0

    # ------------------------------------------------------------- node

    def cmd_node_status(self, args) -> int:
        if not args.node_id:
            nodes = self.api.nodes.list()
            self.p(_fmt_table(
                [[_short(n["ID"]), n["Name"], n["Datacenter"],
                  n["NodeClass"] or "<none>",
                  "true" if n["Drain"] else "false",
                  n["SchedulingEligibility"], n["Status"]] for n in nodes],
                ["ID", "Name", "DC", "Class", "Drain", "Eligibility",
                 "Status"]))
            return 0
        n = self.api.nodes.info(args.node_id)
        self.p(f"ID           = {n.id}")
        self.p(f"Name         = {n.name}")
        self.p(f"Datacenter   = {n.datacenter}")
        self.p(f"Class        = {n.node_class or '<none>'}")
        self.p(f"Status       = {n.status}")
        self.p(f"Eligibility  = {n.scheduling_eligibility}")
        self.p(f"Drain        = {n.drain_strategy is not None}")
        res = n.node_resources
        self.p(f"Resources    = cpu {res.cpu.cpu_shares} MHz, "
               f"mem {res.memory_mb} MiB, disk {res.disk_mb} MiB")
        allocs = self.api.nodes.allocations(n.id)
        live = [a for a in allocs if not a.terminal_status()]
        self.p(f"Allocations  = {len(live)} non-terminal")
        return 0

    def cmd_node_drain(self, args) -> int:
        if args.disable:
            self.api.nodes.drain_disable(args.node_id)
            self.p(f"Node \"{_short(args.node_id)}\" drain disabled")
        else:
            self.api.nodes.drain(args.node_id, deadline_s=args.deadline)
            self.p(f"Node \"{_short(args.node_id)}\" draining "
                   f"(deadline {args.deadline}s)")
        return 0

    def cmd_node_eligibility(self, args) -> int:
        self.api.nodes.eligibility(args.node_id, args.enable)
        state = "eligible" if args.enable else "ineligible"
        self.p(f"Node \"{_short(args.node_id)}\" marked {state}")
        return 0

    # ------------------------------------------------------------- eval/alloc

    def cmd_eval_status(self, args) -> int:
        ev = self.api.evaluations.info(args.eval_id)
        self.p(f"ID            = {_short(ev.id)}")
        self.p(f"Status        = {ev.status}")
        self.p(f"Type          = {ev.type}")
        self.p(f"TriggeredBy   = {ev.triggered_by}")
        self.p(f"Job ID        = {ev.job_id}")
        self.p(f"Priority      = {ev.priority}")
        if ev.status_description:
            self.p(f"Description   = {ev.status_description}")
        if ev.queued_allocations:
            self.p(f"Queued Allocs = {dict(ev.queued_allocations)}")
        return 0

    def cmd_eval_list(self, args) -> int:
        evs = self.api.evaluations.list()
        self.p(_fmt_table(
            [[_short(e.id), str(e.priority), e.triggered_by, e.job_id,
              e.status] for e in evs[:50]],
            ["ID", "Priority", "Triggered By", "Job ID", "Status"]))
        return 0

    def cmd_alloc_status(self, args) -> int:
        a = self.api.allocations.info(args.alloc_id)
        self.p(f"ID            = {_short(a.id)}")
        self.p(f"Name          = {a.name}")
        self.p(f"Node ID       = {_short(a.node_id)}")
        self.p(f"Job ID        = {a.job_id}")
        self.p(f"Client Status = {a.client_status}")
        self.p(f"Desired       = {a.desired_status}")
        if args.verbose and a.metrics:
            m = a.metrics
            self.p("")
            self.p("Placement Metrics")
            self.p(f"  Nodes Evaluated = {m.nodes_evaluated}")
            self.p(f"  Nodes Filtered  = {m.nodes_filtered}")
            self.p(f"  Nodes Exhausted = {m.nodes_exhausted}")
            for sm in m.score_meta or []:
                self.p(f"  {_short(sm.get('node_id', ''))} "
                       f"norm={sm.get('norm_score', 0):.3f}" + "".join(
                           f" {k}={v:.3f}"
                           for k, v in sm.get("scores", {}).items()))
        for name, ts in (a.task_states or {}).items():
            self.p("")
            self.p(f"Task \"{name}\" is \"{ts.state}\"")
            for e in ts.events[-5:]:
                self.p(f"  {e.get('type')}: {e.get('detail', '')}")
        return 0

    def cmd_alloc_stop(self, args) -> int:
        resp = self.api.allocations.stop(args.alloc_id)
        self.p(f"==> Evaluation \"{_short(resp['eval_id'])}\" created")
        return 0

    def _resolve_task(self, alloc_id: str, task: str) -> str:
        if task:
            return task
        a = self.api.allocations.info(alloc_id)
        names = sorted((a.task_states or {}).keys())
        if len(names) != 1:
            raise SystemExit(
                f"allocation has {len(names)} tasks; pass one of "
                f"{names}")
        return names[0]

    def cmd_alloc_logs(self, args) -> int:
        """alloc logs [-stderr] [-f] <alloc_id> [task] (reference
        command/alloc_logs.go over client/fs_endpoint.go)."""
        kind = "stderr" if args.stderr else "stdout"
        task = self._resolve_task(args.alloc_id, args.task)
        if not args.follow:
            data = self.api.allocations.logs(args.alloc_id, task, kind)
            self.p(data.decode(errors="replace"), end="")
            return 0
        try:
            for chunk in self.api.allocations.logs_follow(
                    args.alloc_id, task, kind,
                    timeout=args.follow_timeout):
                self.p(chunk.decode(errors="replace"), end="")
        except KeyboardInterrupt:
            pass
        return 0

    def cmd_alloc_fs(self, args) -> int:
        """alloc fs <alloc_id> [path] — ls for dirs, cat for files."""
        path = args.path or "/"
        st = self.api.allocations.fs_stat(args.alloc_id, path)
        if st.get("IsDir"):
            for e in self.api.allocations.fs_list(args.alloc_id, path):
                kind = "dir " if e.get("IsDir") else "file"
                self.p(f"{kind}  {e.get('Size', 0):>10}  {e['Name']}")
        else:
            data = self.api.allocations.fs_cat(args.alloc_id, path)
            self.p(data.decode(errors="replace"), end="")
        return 0

    # ------------------------------------------------------------- deployment

    def cmd_deployment_list(self, args) -> int:
        deps = self.api.deployments.list()
        self.p(_fmt_table(
            [[_short(d.id), d.job_id, str(d.job_version), d.status]
             for d in deps],
            ["ID", "Job ID", "Job Version", "Status"]))
        return 0

    def cmd_deployment_status(self, args) -> int:
        d = self.api.deployments.info(args.deployment_id)
        self.p(f"ID          = {_short(d.id)}")
        self.p(f"Job ID      = {d.job_id}")
        self.p(f"Job Version = {d.job_version}")
        self.p(f"Status      = {d.status}")
        self.p(f"Description = {d.status_description}")
        rows = []
        for tg, st in (d.task_groups or {}).items():
            rows.append([tg, str(st.desired_total), str(st.placed_allocs),
                         str(st.healthy_allocs), str(st.unhealthy_allocs)])
        if rows:
            self.p("")
            self.p(_fmt_table(rows, ["Task Group", "Desired", "Placed",
                                     "Healthy", "Unhealthy"]))
        return 0

    def cmd_deployment_promote(self, args) -> int:
        self.api.deployments.promote(args.deployment_id)
        self.p("Deployment promoted")
        return 0

    def cmd_deployment_fail(self, args) -> int:
        self.api.deployments.fail(args.deployment_id)
        self.p("Deployment marked failed")
        return 0

    def cmd_deployment_pause(self, args) -> int:
        self.api.deployments.pause(args.deployment_id, not args.resume)
        self.p("Deployment " + ("resumed" if args.resume else "paused"))
        return 0

    # ------------------------------------------------------------- misc

    def cmd_server_members(self, args) -> int:
        members = self.api.system.members()  # analysis: allow(lock-discipline) — SystemApi.members is an HTTP client method, not Membership's lock-protected table
        leader = self.api.system.leader()
        rows = [[m["Name"], "leader" if m["Name"] == leader else "follower"]
                for m in members["Members"]]
        self.p(_fmt_table(rows, ["Name", "Raft Status"]))
        return 0

    def cmd_job_scale(self, args) -> int:
        resp = self.api.jobs.scale(args.job_id, args.group,
                                   count=args.count)
        self.p(f"Evaluation ID: {resp.get('eval_id')}")
        return 0

    def cmd_job_scale_status(self, args) -> int:
        st = self.api.jobs.scale_status(args.job_id)
        rows = [[g, d["desired"], d["placed"], d["running"], d["healthy"]]
                for g, d in sorted(st["task_groups"].items())]
        self.p(_fmt_table(rows, ["Group", "Desired", "Placed", "Running",
                                 "Healthy"]))
        return 0

    def cmd_service_list(self, args) -> int:
        rows = [[s["service_name"], s["namespace"], s["instances"]]
                for s in self.api.services.list()]
        self.p(_fmt_table(rows, ["Service", "Namespace", "Instances"]))
        return 0

    def cmd_service_info(self, args) -> int:
        rows = [[s.id, s.alloc_id[:8], s.address, s.port, s.health]
                for s in self.api.services.get(args.name)]
        self.p(_fmt_table(rows, ["ID", "Alloc", "Address", "Port",
                                 "Health"]))
        return 0

    def cmd_status(self, args) -> int:
        if getattr(args, "prefix", None):
            # server-side prefix search across contexts
            m = self.api.system.search(args.prefix)["Matches"]
            for ctx in sorted(m):
                for i in m[ctx]:
                    self.p(f"{ctx[:-1] if ctx.endswith('s') else ctx}\t{i}")
            return 0
        return self.cmd_job_status(args)

    def cmd_operator_scheduler_get(self, args) -> int:
        cfg = self.api.operator.scheduler_get_configuration()
        self.p(f"Scheduler Algorithm        = {cfg.scheduler_algorithm}")
        self.p(f"Memory Oversubscription    = "
               f"{cfg.memory_oversubscription_enabled}")
        self.p(f"Preemption (system jobs)   = "
               f"{cfg.preemption_config.system_scheduler_enabled}")
        self.p(f"Preemption (service jobs)  = "
               f"{cfg.preemption_config.service_scheduler_enabled}")
        self.p(f"Preemption (batch jobs)    = "
               f"{cfg.preemption_config.batch_scheduler_enabled}")
        self.p(f"Fair Dequeue               = {cfg.fair_dequeue_enabled}")
        self.p(f"Default Namespace Weight   = "
               f"{cfg.default_namespace_weight}")
        for ns, w in sorted((cfg.namespace_weights or {}).items()):
            self.p(f"Namespace Weight           = {ns}={w}")
        return 0

    def cmd_operator_scheduler_set(self, args) -> int:
        cfg = self.api.operator.scheduler_get_configuration()
        if args.scheduler_algorithm:
            cfg.scheduler_algorithm = args.scheduler_algorithm
        if args.memory_oversubscription is not None:
            cfg.memory_oversubscription_enabled = \
                args.memory_oversubscription == "true"
        if args.fair_dequeue is not None:
            cfg.fair_dequeue_enabled = args.fair_dequeue == "true"
        if args.default_namespace_weight is not None:
            cfg.default_namespace_weight = args.default_namespace_weight
        for kv in args.namespace_weight or []:
            ns, _, w = kv.partition("=")
            cfg.namespace_weights[ns] = int(w)
        self.api.operator.scheduler_set_configuration(cfg)
        self.p("Scheduler configuration updated!")
        return 0

    def cmd_operator_raft_list_peers(self, args) -> int:
        cfg = self.api.operator.raft_get_configuration()
        rows = [[s["ID"],
                 "leader" if s.get("Leader") else "follower",
                 "voter" if s.get("Voter") else "non-voter"]
                for s in cfg["Servers"]]
        self.p(_fmt_table(rows, ["Node", "State", "Voter"]))
        return 0

    def cmd_operator_raft_remove_peer(self, args) -> int:
        out = self.api.operator.raft_remove_peer(args.peer_id)
        self.p(f"Removed peer {args.peer_id} "
               f"(configuration index {out['Index']})")
        return 0

    def cmd_operator_transfer_leadership(self, args) -> int:
        out = self.api.operator.raft_transfer_leadership(
            getattr(args, "peer_id", None))
        if out.get("Transferred"):
            self.p(f"Leadership transferred to {out['Leader']}")
            return 0
        self.p("Leadership transfer did not complete")
        return 1

    def cmd_operator_integrity(self, args) -> int:
        v = self.api.operator.integrity()
        last = v.get("last") or {}
        self.p(f"Server              = {v.get('server')}"
               f"{' (leader)' if v.get('leader') else ''}")
        self.p(f"Quarantined         = {v.get('quarantined')}"
               + (f" ({v['quarantine_reason']})"
                  if v.get("quarantine_reason") else ""))
        self.p(f"Last Checkpoint     = "
               + (f"index {last['index']}  digest {last['digest']}  "
                  f"{'full' if last.get('full') else 'incremental'}"
                  if last else "<none>"))
        c = v.get("counters") or {}
        self.p(f"Checkpoints         = {c.get('checkpoints', 0)} "
               f"({c.get('full_walks', 0)} full walks)")
        self.p(f"Alarms / Repairs    = {c.get('alarms', 0)} alarms, "
               f"{c.get('repairs_started', 0)} repairs started, "
               f"{c.get('repairs_verified', 0)} verified")
        peers = v.get("peers") or {}
        if peers:
            rows = []
            for name in sorted(peers):
                p = peers[name]
                rows.append([
                    name,
                    str(p.get("index")) if p.get("index") is not None
                    else "-",
                    p.get("digest") or "-",
                    str(p.get("lag")) if p.get("lag") is not None
                    else "-",
                    p.get("divergent") or "",
                    str(p.get("unverified_acks", 0))])
            self.p(_fmt_table(rows, ["Peer", "Index", "Digest", "Lag",
                                     "Divergent", "Unverified"]))
        return 1 if v.get("quarantined") else 0

    def cmd_operator_trace(self, args) -> int:
        if not getattr(args, "trace_id", None):
            traces = self.api.operator.traces()
            if not traces:
                self.p("No traces sampled (is NOMAD_TPU_TRACE=1 set "
                       "on the agent?)")
                return 0
            rows = [[t["trace_id"], t["root"],
                     f"{t['duration'] * 1000.0:.2f}ms",
                     str(t["spans"]), ",".join(t["nodes"])]
                    for t in traces]
            self.p(_fmt_table(
                rows, ["Trace ID", "Root", "Duration", "Spans",
                       "Nodes"]))
            return 0
        if getattr(args, "chrome_out", None):
            doc = self.api.operator.trace_chrome(args.trace_id)
            with open(args.chrome_out, "w") as f:
                json.dump(doc, f)
            self.p(f"Wrote {len(doc['traceEvents'])} events to "
                   f"{args.chrome_out} (open in Perfetto / "
                   f"chrome://tracing)")
            return 0
        out = self.api.operator.trace(args.trace_id)
        spans = out["spans"]
        if not spans:
            self.p(f"No spans for trace {args.trace_id}")
            return 1
        t0 = min(sp["start"] for sp in spans)
        rows = [[f"+{(sp['start'] - t0) * 1000.0:.2f}ms",
                 f"{sp['duration'] * 1000.0:.2f}ms",
                 sp["node"], sp["name"],
                 "" if not sp["parent_id"] else sp["parent_id"][:8]]
                for sp in spans]
        self.p(f"Trace {args.trace_id} ({len(spans)} spans)")
        self.p(_fmt_table(rows, ["Start", "Duration", "Node", "Span",
                                 "Parent"]))
        return 0

    def cmd_acl_bootstrap(self, args) -> int:
        t = self.api.acl.bootstrap()
        self.p(f"Accessor ID = {t['AccessorID']}")
        self.p(f"Secret ID   = {t['SecretID']}")
        self.p(f"Type        = {t['Type']}")
        return 0

    def cmd_acl_policy_apply(self, args) -> int:
        with open(args.file) as fh:
            rules = fh.read()
        self.api.acl.upsert_policy(args.name, rules,
                                   args.description or "")
        self.p(f"Successfully wrote \"{args.name}\" ACL policy!")
        return 0

    def cmd_acl_token_create(self, args) -> int:
        t = self.api.acl.create_token(
            name=args.name or "", type_=args.type,
            policies=args.policy or [])
        self.p(f"Accessor ID = {t['AccessorID']}")
        self.p(f"Secret ID   = {t['SecretID']}")
        return 0

    def cmd_namespace_list(self, args) -> int:
        for ns in self.api.namespaces.list():
            self.p(f"{ns['name']}\t{ns.get('quota', '') or '<none>'}\t"
                   f"{ns.get('description', '')}")
        return 0

    def cmd_namespace_apply(self, args) -> int:
        self.api.namespaces.register(args.name, args.description or "",
                                     quota=args.quota or "")
        self.p(f"Successfully applied namespace \"{args.name}\"!")
        return 0

    def cmd_namespace_delete(self, args) -> int:
        self.api.namespaces.delete(args.name)
        self.p(f"Successfully deleted namespace \"{args.name}\"!")
        return 0

    # ------------------------------------------------------------- quota

    @staticmethod
    def _fmt_limit(v) -> str:
        return "-" if v is None else str(v)

    def cmd_quota_list(self, args) -> int:
        rows = [[s["name"], self._fmt_limit(s.get("cpu")),
                 self._fmt_limit(s.get("memory_mb")),
                 self._fmt_limit(s.get("devices")),
                 self._fmt_limit(s.get("allocs")),
                 s.get("description", "")]
                for s in self.api.quotas.list()]
        self.p(_fmt_table(rows, ["Name", "CPU", "Memory MiB", "Devices",
                                 "Allocs", "Description"]))
        return 0

    def cmd_quota_apply(self, args) -> int:
        spec = {"name": args.name, "description": args.description or ""}
        for dim in ("cpu", "memory_mb", "devices", "allocs"):
            v = getattr(args, dim)
            if v is not None:
                spec[dim] = v
        self.api.quotas.register(spec)
        self.p(f"Successfully applied quota specification \"{args.name}\"!")
        return 0

    def cmd_quota_delete(self, args) -> int:
        self.api.quotas.delete(args.name)
        self.p(f"Successfully deleted quota \"{args.name}\"!")
        return 0

    def cmd_quota_usage(self, args) -> int:
        if args.usage_ns:
            usages = {args.usage_ns: self.api.quotas.usage(
                args.usage_ns).get("Usage") or {}}
        else:
            usages = self.api.quotas.usages()
        rows = [[ns, str(u.get("cpu", 0)), str(u.get("memory_mb", 0)),
                 str(u.get("devices", 0)), str(u.get("allocs", 0))]
                for ns, u in sorted(usages.items())]
        self.p(_fmt_table(rows, ["Namespace", "CPU", "Memory MiB",
                                 "Devices", "Allocs"]))
        return 0

    def cmd_volume_register(self, args) -> int:
        import json as _json
        with open(args.file) as f:
            text = f.read()
        try:
            vol = _json.loads(text)
        except ValueError:
            from nomad_tpu.jobspec.hcl import parse_hcl
            body = parse_hcl(text)
            b = body.first("volume") or body
            vol = {
                "ID": b.get("id", ""), "Name": b.get("name", ""),
                "PluginID": b.get("plugin_id", ""),
                "AccessMode": b.get("access_mode", ""),
                "AttachmentMode": b.get("attachment_mode", ""),
            }
        self.api.volumes.register(vol, namespace=args.namespace)
        self.p(f"Successfully registered volume \"{vol.get('ID', '')}\"!")
        return 0

    def cmd_volume_status(self, args) -> int:
        if args.vol_id:
            v = self.api.volumes.info(args.vol_id, namespace=args.namespace)
            for k in ("ID", "Name", "PluginID", "AccessMode", "Schedulable",
                      "CurrentReaders", "CurrentWriters", "NodesHealthy",
                      "NodesExpected"):
                self.p(f"{k:<18} = {v.get(k)}")
        else:
            self.p("ID\tPlugin\tSchedulable\tAccess")
            for v in self.api.volumes.list(namespace=args.namespace):
                self.p(f"{v['ID']}\t{v['PluginID']}\t"
                       f"{v['Schedulable']}\t{v['AccessMode'] or '<none>'}")
        return 0

    def cmd_volume_deregister(self, args) -> int:
        self.api.volumes.deregister(args.vol_id, namespace=args.namespace,
                                    force=args.force)
        self.p(f"Successfully deregistered volume \"{args.vol_id}\"!")
        return 0

    def cmd_plugin_status(self, args) -> int:
        if args.plugin_id:
            v = self.api.plugins.info(args.plugin_id)
            for k in ("ID", "Provider", "ControllersHealthy",
                      "ControllersExpected", "NodesHealthy", "NodesExpected"):
                self.p(f"{k:<20} = {v.get(k)}")
        else:
            self.p("ID\tProvider\tControllers Healthy\tNodes Healthy")
            for v in self.api.plugins.list():
                self.p(f"{v['ID']}\t{v.get('Provider', '')}\t"
                       f"{v['ControllersHealthy']}/{v['ControllersExpected']}\t"
                       f"{v['NodesHealthy']}/{v['NodesExpected']}")
        return 0

    def cmd_version(self, args) -> int:
        from nomad_tpu import __version__
        self.p(f"nomad-tpu v{__version__}")
        return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="nomad-tpu",
        description="TPU-native cluster scheduler (Nomad-capability)")
    p.add_argument("-address", default=os.environ.get(
        "NOMAD_ADDR", "http://127.0.0.1:4646"))
    p.add_argument("-token", default=os.environ.get("NOMAD_TOKEN", ""))
    p.add_argument("-namespace", default=os.environ.get(
        "NOMAD_NAMESPACE", "default"))
    # target region (reference -region): the contacted server forwards
    # the request over the WAN when the region is not its own
    p.add_argument("-region", default=os.environ.get("NOMAD_REGION", ""),
                   help="region to route the request to")
    # consistency mode for reads (reference -stale / -consistent): stale
    # lets any server answer from its local store; consistent forces a
    # full raft read-index round; default is leader lease reads
    p.add_argument("-stale", action="store_true",
                   help="allow any server to answer without forwarding")
    p.add_argument("-consistent", action="store_true",
                   help="force a fully linearizable read-index read")
    sub = p.add_subparsers(dest="cmd", required=True)

    ag = sub.add_parser("agent", help="run an agent")
    ag.add_argument("-dev", action="store_true")
    ag.add_argument("-server", action="store_true")
    ag.add_argument("-client", action="store_true")
    ag.add_argument("-bind", default="127.0.0.1")
    ag.add_argument("-port", type=int, default=4646)
    ag.add_argument("-name", default="agent-1")
    ag.add_argument("-num-schedulers", type=int, default=4,
                    dest="num_schedulers")
    ag.add_argument("-acl-enabled", action="store_true",
                    dest="acl_enabled")
    ag.add_argument("-data-dir", default="", dest="data_dir")
    ag.add_argument("-config", default="", dest="config_file",
                    help="HCL agent configuration file")
    ag.set_defaults(fn="cmd_agent")

    job = sub.add_parser("job", help="job commands").add_subparsers(
        dest="sub", required=True)
    j = job.add_parser("run")
    j.add_argument("file")
    j.add_argument("-var", action="append", dest="var",
                   default=[], metavar="NAME=VALUE")
    j.add_argument("-var-file", action="append",
                   dest="var_file", default=[])
    j.add_argument("-detach", action="store_true")
    j.add_argument("-check-index", type=int, default=None,
                   dest="check_index")
    j.set_defaults(fn="cmd_job_run")
    j = job.add_parser("status")
    j.add_argument("job_id", nargs="?")
    j.set_defaults(fn="cmd_job_status")
    j = job.add_parser("stop")
    j.add_argument("job_id")
    j.add_argument("-purge", action="store_true")
    j.add_argument("-detach", action="store_true")
    j.set_defaults(fn="cmd_job_stop")
    j = job.add_parser("plan")
    j.add_argument("file")
    j.add_argument("-var", action="append", dest="var",
                   default=[], metavar="NAME=VALUE")
    j.add_argument("-var-file", action="append",
                   dest="var_file", default=[])
    j.set_defaults(fn="cmd_job_plan")
    j = job.add_parser("inspect")
    j.add_argument("job_id")
    j.set_defaults(fn="cmd_job_inspect")
    j = job.add_parser("validate")
    j.add_argument("file")
    j.add_argument("-var", action="append", dest="var",
                   default=[], metavar="NAME=VALUE")
    j.add_argument("-var-file", action="append",
                   dest="var_file", default=[])
    j.set_defaults(fn="cmd_job_validate")
    j = job.add_parser("dispatch")
    j.add_argument("job_id")
    j.add_argument("payload_file", nargs="?")
    j.add_argument("-meta", action="append")
    j.set_defaults(fn="cmd_job_dispatch")
    j = job.add_parser("scale")
    j.add_argument("job_id")
    j.add_argument("group")
    j.add_argument("count", type=int)
    j.set_defaults(fn="cmd_job_scale")
    j = job.add_parser("scale-status")
    j.add_argument("job_id")
    j.set_defaults(fn="cmd_job_scale_status")
    j = job.add_parser("history")
    j.add_argument("job_id")
    j.set_defaults(fn="cmd_job_history")
    j = job.add_parser("revert")
    j.add_argument("job_id")
    j.add_argument("version", type=int)
    j.set_defaults(fn="cmd_job_revert")
    j = job.add_parser("periodic-force")
    j.add_argument("job_id")
    j.set_defaults(fn="cmd_job_periodic_force")

    node = sub.add_parser("node", help="node commands").add_subparsers(
        dest="sub", required=True)
    n = node.add_parser("status")
    n.add_argument("node_id", nargs="?")
    n.set_defaults(fn="cmd_node_status")
    n = node.add_parser("drain")
    n.add_argument("node_id")
    n.add_argument("-disable", action="store_true")
    n.add_argument("-deadline", type=float, default=3600.0)
    n.set_defaults(fn="cmd_node_drain")
    n = node.add_parser("eligibility")
    n.add_argument("node_id")
    g = n.add_mutually_exclusive_group(required=True)
    g.add_argument("-enable", dest="enable", action="store_true")
    g.add_argument("-disable", dest="enable", action="store_false")
    n.set_defaults(fn="cmd_node_eligibility")

    ev = sub.add_parser("eval", help="eval commands").add_subparsers(
        dest="sub", required=True)
    e = ev.add_parser("status")
    e.add_argument("eval_id")
    e.set_defaults(fn="cmd_eval_status")
    e = ev.add_parser("list")
    e.set_defaults(fn="cmd_eval_list")

    al = sub.add_parser("alloc", help="alloc commands").add_subparsers(
        dest="sub", required=True)
    a = al.add_parser("status")
    a.add_argument("alloc_id")
    a.add_argument("-verbose", action="store_true")
    a.set_defaults(fn="cmd_alloc_status")
    a = al.add_parser("stop")
    a.add_argument("alloc_id")
    a.set_defaults(fn="cmd_alloc_stop")
    a = al.add_parser("logs")
    a.add_argument("alloc_id")
    a.add_argument("task", nargs="?", default="")
    a.add_argument("-stderr", action="store_true")
    a.add_argument("-f", action="store_true", dest="follow")
    a.add_argument("-follow-timeout", type=float, default=30.0,
                   dest="follow_timeout")
    a.set_defaults(fn="cmd_alloc_logs")
    a = al.add_parser("fs")
    a.add_argument("alloc_id")
    a.add_argument("path", nargs="?", default="/")
    a.set_defaults(fn="cmd_alloc_fs")

    dep = sub.add_parser("deployment",
                         help="deployment commands").add_subparsers(
        dest="sub", required=True)
    d = dep.add_parser("list")
    d.set_defaults(fn="cmd_deployment_list")
    d = dep.add_parser("status")
    d.add_argument("deployment_id")
    d.set_defaults(fn="cmd_deployment_status")
    d = dep.add_parser("promote")
    d.add_argument("deployment_id")
    d.set_defaults(fn="cmd_deployment_promote")
    d = dep.add_parser("fail")
    d.add_argument("deployment_id")
    d.set_defaults(fn="cmd_deployment_fail")
    d = dep.add_parser("pause")
    d.add_argument("deployment_id")
    d.add_argument("-resume", action="store_true")
    d.set_defaults(fn="cmd_deployment_pause")

    srv = sub.add_parser("server", help="server commands").add_subparsers(
        dest="sub", required=True)
    s = srv.add_parser("members")
    s.set_defaults(fn="cmd_server_members")

    op = sub.add_parser("operator",
                        help="operator commands").add_subparsers(
        dest="sub", required=True)
    sch = op.add_parser("scheduler").add_subparsers(dest="sub2",
                                                    required=True)
    o = sch.add_parser("get-config")
    o.set_defaults(fn="cmd_operator_scheduler_get")
    o = sch.add_parser("set-config")
    o.add_argument("-scheduler-algorithm", dest="scheduler_algorithm",
                   choices=["binpack", "spread"], default=None)
    o.add_argument("-memory-oversubscription",
                   dest="memory_oversubscription",
                   choices=["true", "false"], default=None)
    o.add_argument("-fair-dequeue", dest="fair_dequeue",
                   choices=["true", "false"], default=None,
                   help="weighted fair eval dequeue across namespaces")
    o.add_argument("-default-namespace-weight", type=int, default=None,
                   dest="default_namespace_weight")
    o.add_argument("-namespace-weight", action="append",
                   dest="namespace_weight", metavar="NS=WEIGHT",
                   help="per-namespace dequeue weight (repeatable)")
    o.set_defaults(fn="cmd_operator_scheduler_set")
    rft = op.add_parser("raft").add_subparsers(dest="sub2", required=True)
    o = rft.add_parser("list-peers")
    o.set_defaults(fn="cmd_operator_raft_list_peers")
    o = rft.add_parser("remove-peer")
    o.add_argument("-peer-id", dest="peer_id", required=True)
    o.set_defaults(fn="cmd_operator_raft_remove_peer")
    o = op.add_parser("transfer-leadership")
    o.add_argument("-peer-id", dest="peer_id", default=None)
    o.set_defaults(fn="cmd_operator_transfer_leadership")
    o = op.add_parser("trace",
                      help="list sampled traces, show one, or export "
                           "Chrome-trace JSON for Perfetto")
    o.add_argument("trace_id", nargs="?", default=None)
    o.add_argument("-chrome", dest="chrome_out", default=None,
                   metavar="FILE")
    o.set_defaults(fn="cmd_operator_trace")
    o = op.add_parser("integrity",
                      help="replica-integrity plane: last checkpoint "
                           "digest, per-peer divergence, quarantine "
                           "state, repair counters")
    o.set_defaults(fn="cmd_operator_integrity")

    acl = sub.add_parser("acl", help="acl commands").add_subparsers(
        dest="sub", required=True)
    c = acl.add_parser("bootstrap")
    c.set_defaults(fn="cmd_acl_bootstrap")
    pol = acl.add_parser("policy").add_subparsers(dest="sub2",
                                                  required=True)
    c = pol.add_parser("apply")
    c.add_argument("name")
    c.add_argument("file")
    c.add_argument("-description", default="")
    c.set_defaults(fn="cmd_acl_policy_apply")
    tok = acl.add_parser("token").add_subparsers(dest="sub2",
                                                 required=True)
    c = tok.add_parser("create")
    c.add_argument("-name", default="")
    c.add_argument("-type", default="client")
    c.add_argument("-policy", action="append")
    c.set_defaults(fn="cmd_acl_token_create")

    ns = sub.add_parser("namespace",
                        help="namespace commands").add_subparsers(
        dest="sub", required=True)
    c = ns.add_parser("list")
    c.set_defaults(fn="cmd_namespace_list")
    c = ns.add_parser("apply")
    c.add_argument("name")
    c.add_argument("-description", default="")
    c.add_argument("-quota", default="",
                   help="quota spec governing this namespace")
    c.set_defaults(fn="cmd_namespace_apply")
    c = ns.add_parser("delete")
    c.add_argument("name")
    c.set_defaults(fn="cmd_namespace_delete")

    qt = sub.add_parser("quota",
                        help="resource quota commands").add_subparsers(
        dest="sub", required=True)
    c = qt.add_parser("list")
    c.set_defaults(fn="cmd_quota_list")
    c = qt.add_parser("apply")
    c.add_argument("name")
    c.add_argument("-description", default="")
    c.add_argument("-cpu", type=int, default=None,
                   help="CPU MHz limit (omit for unlimited)")
    c.add_argument("-memory", type=int, default=None, dest="memory_mb",
                   help="memory MiB limit")
    c.add_argument("-devices", type=int, default=None,
                   help="accelerator device-count limit")
    c.add_argument("-allocs", type=int, default=None,
                   help="live allocation-count limit")
    c.set_defaults(fn="cmd_quota_apply")
    c = qt.add_parser("delete")
    c.add_argument("name")
    c.set_defaults(fn="cmd_quota_delete")
    c = qt.add_parser("usage")
    # dest kept distinct from the global -namespace flag: a subparser
    # positional default would clobber the already-parsed global value
    c.add_argument("usage_ns", nargs="?", default="",
                   metavar="namespace")
    c.set_defaults(fn="cmd_quota_usage")

    vol = sub.add_parser("volume",
                         help="CSI volume commands").add_subparsers(
        dest="sub", required=True)
    c = vol.add_parser("register")
    c.add_argument("file")
    c.add_argument("-namespace", default="default")
    c.set_defaults(fn="cmd_volume_register")
    c = vol.add_parser("status")
    c.add_argument("vol_id", nargs="?")
    c.add_argument("-namespace", default="default")
    c.set_defaults(fn="cmd_volume_status")
    c = vol.add_parser("deregister")
    c.add_argument("vol_id")
    c.add_argument("-namespace", default="default")
    c.add_argument("-force", action="store_true")
    c.set_defaults(fn="cmd_volume_deregister")

    plug = sub.add_parser("plugin",
                          help="CSI plugin commands").add_subparsers(
        dest="sub", required=True)
    c = plug.add_parser("status")
    c.add_argument("plugin_id", nargs="?")
    c.set_defaults(fn="cmd_plugin_status")

    v = sub.add_parser("version")
    v.set_defaults(fn="cmd_version")

    st = sub.add_parser("status", help="job status shorthand")
    st.add_argument("job_id", nargs="?")
    st.add_argument("-prefix", default="",
                    help="server-side prefix search across all contexts")
    st.set_defaults(fn="cmd_status")

    svc = sub.add_parser("service",
                         help="nomad-native service registry").add_subparsers(
        dest="sub", required=True)
    sv = svc.add_parser("list")
    sv.set_defaults(fn="cmd_service_list")
    sv = svc.add_parser("info")
    sv.add_argument("name")
    sv.set_defaults(fn="cmd_service_info")
    return p


def main(argv: Optional[List[str]] = None, out=sys.stdout) -> int:
    args = build_parser().parse_args(argv)
    consistency = ("stale" if getattr(args, "stale", False) else
                   "consistent" if getattr(args, "consistent", False)
                   else None)
    api = ApiClient(address=args.address, token=args.token,
                    namespace=args.namespace, consistency=consistency,
                    region=getattr(args, "region", "") or None)
    cli = Cli(api, out=out)
    try:
        return getattr(cli, args.fn)(args)
    except ApiError as e:
        print(f"Error: {e}", file=sys.stderr)
        return 1
    except OSError as e:
        print(f"Error connecting to {args.address}: {e}", file=sys.stderr)
        return 1
