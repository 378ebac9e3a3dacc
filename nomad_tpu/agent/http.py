"""HTTP API server (reference: command/agent/http.go:320-392 — the /v1
route table over the agent's RPC layer).

Conventions mirrored from the reference:
 - JSON bodies both ways; struct wire format from nomad_tpu.api.codec.
 - Blocking queries: `?index=N&wait=SECONDS` on list/get endpoints —
   the handler waits until the state store advances past N (go-memdb
   watchsets in the reference; a condition poll here).
 - `X-Nomad-Index` response header carries the state index.
 - /v1/event/stream streams NDJSON events with topic filters.
 - ACL: `X-Nomad-Token` header resolved when ACLs are enabled.
"""
from __future__ import annotations

import json
import re
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from nomad_tpu import chaos, deadline, tracing
from nomad_tpu.api.codec import from_wire, to_wire
from nomad_tpu.raft.transport import Unreachable
from nomad_tpu.rpc.endpoints import RpcError
from nomad_tpu.serving import EventStreamer, READ_METHODS, mode_from_query
from nomad_tpu.structs import Job
from nomad_tpu.telemetry import global_metrics


class HTTPError(Exception):
    def __init__(self, code: int, msg: str,
                 retry_after: Optional[float] = None):
        super().__init__(msg)
        self.code = code
        self.msg = msg
        # overload refusals tell the client when to come back
        self.retry_after = retry_after


def _parse_wait(val: str) -> float:
    """`wait` accepts go-style durations ("5s", "100ms") or seconds."""
    m = re.fullmatch(r"(\d+(?:\.\d+)?)(ms|s|m|h)?", val)
    if not m:
        raise HTTPError(400, f"invalid wait duration {val!r}")
    n = float(m.group(1))
    return n * {"ms": 1e-3, "s": 1.0, "m": 60.0, "h": 3600.0,
                None: 1.0}[m.group(2)]


class HTTPServer:
    def __init__(self, agent, host: str = "127.0.0.1", port: int = 0):
        self.agent = agent
        self.host = host
        # per-request read point (one handler thread per connection):
        # _rpc may only serve READ_METHODS from the local store when the
        # route gate established a read point for the CURRENT request
        self._read_local = threading.local()
        outer = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):          # quiet
                pass

            def _dispatch(self):
                # set by _route: admission slot to hand back and the
                # previous deadline binding to restore (the connection
                # thread outlives the request under keep-alive)
                self._admitted = None
                self._deadline_bound = False
                self._deadline_prev = None
                try:
                    outer._route(self)
                except HTTPError as e:
                    self._reply(e.code, {"error": e.msg},
                                retry_after=e.retry_after)
                except RpcError as e:
                    code = {"not_found": 404, "permission_denied": 403,
                            "unknown_method": 404, "bad_request": 400,
                            "unknown_namespace": 400,
                            "unknown_region": 400,
                            "no_region_leader": 503,
                            "no_region_path": 502,
                            "admission_denied": 503,
                            "brownout": 503,
                            "quarantined": 503,
                            "deadline_exceeded": 504}.get(e.kind, 500)
                    self._reply(code, {"error": str(e)},
                                retry_after=getattr(e, "retry_after",
                                                    None))
                except Unreachable as e:
                    # a `?region=` request into a dark region fails fast
                    self._reply(503, {"error": f"region unreachable: {e}"})
                except BrokenPipeError:
                    pass
                except Exception as e:                   # noqa: BLE001
                    self._reply(500, {"error": f"{type(e).__name__}: {e}"})
                finally:
                    if self._admitted is not None:
                        gate, ns = self._admitted
                        gate.release(ns)
                    if self._deadline_bound:
                        deadline.bind(self._deadline_prev)

            do_GET = do_PUT = do_POST = do_DELETE = _dispatch

            def _reply(self, code: int, obj, index: Optional[int] = None,
                       ctx=None, retry_after: Optional[float] = None,
                       body: Optional[bytes] = None):
                if body is None:
                    body = json.dumps(obj).encode()
                try:
                    self.send_response(code)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    if retry_after is not None:
                        # overload refusal: an honest client hint
                        # (rounded up — Retry-After is integer seconds)
                        self.send_header(
                            "Retry-After",
                            str(max(1, int(retry_after + 0.999))))
                    if index is not None:
                        self.send_header("X-Nomad-Index", str(index))
                    if ctx is not None:
                        # staleness metadata from the read gate
                        # (reference setMeta, command/agent/http.go)
                        self.send_header(
                            "X-Nomad-KnownLeader",
                            "true" if ctx.known_leader else "false")
                        self.send_header(
                            "X-Nomad-LastContact",
                            str(int(ctx.last_contact_ms)))
                    self.end_headers()
                    self.wfile.write(body)
                except (BrokenPipeError, ConnectionResetError):
                    pass

            def _body(self):
                n = int(self.headers.get("Content-Length") or 0)
                if n == 0:
                    return {}
                raw = self.rfile.read(n)
                try:
                    return json.loads(raw) if raw else {}
                except json.JSONDecodeError as e:
                    raise HTTPError(400, f"invalid JSON body: {e}")

        self._server = ThreadingHTTPServer((host, port), Handler)
        self._server.daemon_threads = True
        self.port = self._server.server_address[1]
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------ lifecycle

    def start(self) -> None:
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        name="http", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        if self._thread:
            self._thread.join(2.0)

    # ------------------------------------------------------------ routing

    def _route(self, h) -> None:
        url = urllib.parse.urlparse(h.path)
        # keep_blank_values: bare flags like `?consistent` must survive
        q = {k: v[-1] for k, v in urllib.parse.parse_qs(
            url.query, keep_blank_values=True).items()}
        parts = [urllib.parse.unquote(p)
                 for p in url.path.split("/") if p]
        if not parts or parts[0] != "v1":
            raise HTTPError(404, f"no handler for {url.path}")
        parts = parts[1:]
        method = h.command

        # ---- overload plane, before any other work for the request
        # ingress-flood chaos: the front door sheds exactly as if this
        # tenant's bucket were empty — deny-by-503 with a Retry-After,
        # never accept-then-drop
        if chaos.active is not None and \
                chaos.should("overload.ingress_flood"):
            global_metrics.incr("admission.denied.flood")
            raise HTTPError(503, "ingress flood: request shed",
                            retry_after=1.0)
        ns = q.get("namespace", "default")
        gate = self.agent.server.admission \
            if self.agent.server is not None else None
        if gate is not None and gate.enabled:
            retry = gate.try_acquire(ns)
            if retry is not None:
                raise HTTPError(
                    503, f"admission limit for namespace {ns!r}",
                    retry_after=retry)
            h._admitted = (gate, ns)
        # request deadline: X-Nomad-Deadline carries the budget in
        # seconds (else the NOMAD_TPU_DEFAULT_DEADLINE default); bound
        # to the request thread so every downstream stage — rpc
        # dispatch, broker, applier, retry loops — checks it
        budget = h.headers.get("X-Nomad-Deadline")
        if budget is not None:
            try:
                budget = float(budget)
            except ValueError:
                raise HTTPError(
                    400, f"invalid X-Nomad-Deadline {budget!r}")
        else:
            budget = deadline.default_budget()
        if budget is not None:
            h._deadline_prev = deadline.bind(
                time.monotonic() + max(0.0, budget))
            h._deadline_bound = True

        token = h.headers.get("X-Nomad-Token", "") or \
            q.get("token", "")
        self._check_acl(parts, method, token, ns, h)

        server = self.agent.server
        store = server.store if server else None
        # `?region=`: a request for another region (reference
        # QueryOptions.Region) skips the LOCAL read gate — the remote
        # region's servers establish the read point — and instead rides
        # the consistency mode in the RPC args (see _rpc)
        region = q.get("region") or None
        if server is not None and region == server.region:
            region = None
        read_ctx = None
        if server is not None and method == "GET" and region is None:
            # establish the read point for this request's consistency
            # mode BEFORE any blocking wait: `?consistent` pays a quorum
            # round, default rides the leader lease, `?stale` serves
            # whatever the local store has right now
            mode = mode_from_query(q)
            gate_timeout = 2.0
            if "index" in q:
                # blocking queries bound the whole request by `wait`
                gate_timeout = min(_parse_wait(q.get("wait", "5s")), 600.0)
            try:
                read_ctx = server.serving_gate.begin_read(
                    mode, timeout=gate_timeout)
            except Exception as e:              # noqa: BLE001
                # vacant or unreachable leadership: linearizable reads
                # fail fast rather than serving possibly-stale data
                raise HTTPError(503, f"read gate ({mode}): "
                                     f"{type(e).__name__}: {e}")
        self._read_local.ctx = read_ctx
        self._read_local.region = region
        self._read_local.mode = mode_from_query(q) if region else None
        # local reads: the gate already ran above, but the brownout
        # shed decision inside endpoints.handle still needs the mode —
        # a stale read must shed LAST, not as a default read
        self._read_local.local_mode = mode_from_query(q) \
            if read_ctx is not None else None
        try:
            m = method.lower()
            handler = None
            for name in ([f"_h_{m}_{parts[0]}_id"] if len(parts) >= 2
                         else []) + [f"_h_{m}_{parts[0]}"]:
                handler = getattr(self, name, None)
                if handler is not None:
                    break
            if handler is None:
                raise HTTPError(404, f"no handler for {method} {url.path}")
            # trace ingress: one sampling decision per request; the span
            # is named for the route's handler (`http.put.jobs`,
            # `http.get.job_id`), never for a path the caller made up
            tracer = tracing.active
            tctx = tracer.new_context() \
                if tracer is not None and parts[0] != "traces" else None
            with tracing.span(
                    "http." + name[3:].replace("_", ".", 1), ctx=tctx,
                    node=server.name if server is not None else "agent"):
                if store is not None and "index" in q and region is None:
                    min_index = int(q["index"])
                    wait = _parse_wait(q.get("wait", "5s"))
                    # a deadline-bound blocking query parks for at most
                    # its remaining budget, then serves the current state
                    rem = deadline.remaining()
                    if rem is not None:
                        wait = min(wait, rem)
                    with tracing.span("http.park", wait=True):
                        store.wait_for_index(min_index + 1,
                                             timeout=min(wait, 600.0))
                result = handler(h, parts, q)
                if result is not _STREAMED:
                    # serialising the reply is the request's work too;
                    # the socket write stays after the span, as close to
                    # the admission slot's release as it was
                    body = json.dumps(to_wire(result)).encode()
        finally:
            self._read_local.ctx = None
            self._read_local.region = None
            self._read_local.mode = None
            self._read_local.local_mode = None
        if result is not _STREAMED:
            # a cross-region reply must not carry the LOCAL store's
            # index as if it were the remote region's
            index = store.latest_index \
                if store is not None and region is None else None
            if index is not None and "index" in q:
                # a blocking query must never return an index lower than
                # the one it was given (reference blockingRPC contract)
                index = max(index, int(q["index"]))
            h._reply(200, None, index=index, ctx=read_ctx, body=body)

    def _rpc(self, method: str, args: dict):
        server = self.agent.server
        if tracing.active is not None:
            ctx = tracing.current()
            if ctx is not None:
                # sampled request: the context rides the RPC args
                # (endpoints.handle pops it before dispatch; forwarded
                # copies keep it, so it survives federation hops)
                args = dict(args)
                args[tracing.TRACE_KEY] = ctx
        if deadline.current() is not None:
            # the request's remaining budget rides the RPC args just
            # like the trace ctx, re-encoded relative so clock skew
            # between hops cannot spuriously expire it
            args = dict(args)
            args[deadline.DEADLINE_KEY] = deadline.to_wire()
        region = getattr(self._read_local, "region", None)
        if server is not None and region:
            # cross-region request: ship the target region (and the
            # caller's consistency mode, applied by the REMOTE region's
            # read gate) in the args — endpoints.handle forwards it over
            # the WAN to that region's current leader
            args = dict(args)
            args["region"] = region
            mode = getattr(self._read_local, "mode", None)
            if mode is not None and method in READ_METHODS:
                args["consistency"] = mode
            return server.endpoints.handle(method, args)
        if server is not None and method in READ_METHODS \
                and getattr(self._read_local, "ctx", None) is not None:
            # a read point was established by _route's gate for THIS
            # request: serve from the LOCAL store, leader and follower
            # alike (follower reads).  Reads invoked without one — e.g.
            # from POST paths like /v1/search or job evaluate/revert
            # preconditions — forward to the leader as before, rather
            # than reading an ungated follower store with no staleness
            # metadata.
            local_mode = getattr(self._read_local, "local_mode", None)
            if local_mode is not None:
                # ride the args for shed classification only — the read
                # point for this request is already established, so it
                # must NOT trigger a second begin_read
                args = dict(args)
                args["_read_mode"] = local_mode
            return server.endpoints.handle(method, args)
        return self.agent.rpc(method, args)

    # ------------------------------------------------------------ ACL

    def _check_acl(self, parts, method, token: str,
                   namespace: str = "default", h=None) -> None:
        server = self.agent.server
        if server is None or not getattr(server, "acl_enabled", False):
            if h is not None:
                h.acl = None
            return
        from nomad_tpu.acl import required_capability
        cap, ns = required_capability(parts, method, namespace)
        if cap is None:
            if h is not None:
                h.acl = server.resolve_token(token)
            return
        acl = server.resolve_token(token)
        if h is not None:
            h.acl = acl
        if acl is None:
            raise HTTPError(403, "ACL token not found")
        if not acl.allows(ns, cap):
            raise HTTPError(403, f"Permission denied: needs {cap}")

    def _require_ns_cap(self, h, namespace: str, cap: str) -> None:
        """Authorize `cap` against the *object's own* namespace after
        fetching it by ID (the reference checks alloc.Namespace in
        alloc_endpoint.go, not the caller-supplied ?namespace= param —
        otherwise a token with the capability in any one namespace could
        act on objects in all of them)."""
        if not getattr(self.agent.server, "acl_enabled", False):
            return
        acl = getattr(h, "acl", None)
        if acl is None or not acl.allows(namespace, cap):
            raise HTTPError(403, f"Permission denied: needs {cap} in "
                                 f"namespace {namespace!r}")

    def _require_ns_read(self, h, namespace: str) -> None:
        from nomad_tpu.acl.policy import CAP_READ_JOB
        self._require_ns_cap(h, namespace, CAP_READ_JOB)

    def _ns_visible(self, h, namespace: str) -> bool:
        """Namespace-level read filter for list endpoints (the reference
        scopes every list RPC by the token's namespace grants)."""
        if not getattr(self.agent.server, "acl_enabled", False):
            return True
        acl = getattr(h, "acl", None)
        if acl is None:
            return False
        from nomad_tpu.acl.policy import CAP_LIST_JOBS, CAP_READ_JOB
        return acl.allows(namespace, CAP_LIST_JOBS) or \
            acl.allows(namespace, CAP_READ_JOB)

    def _ns_param(self, q):
        """Validate `?namespace=`: an unknown namespace is rejected
        naming the known set (matching Job.Register's unknown-region
        error shape); `*` is the wildcard list-all.  Cross-region
        requests skip the check — only the remote region knows its
        namespaces."""
        ns = q.get("namespace")
        if not ns or ns == "*":
            return ns
        server = self.agent.server
        if server is None or getattr(self._read_local, "region", None):
            return ns
        if server.store.namespace(ns) is None:
            known = sorted(n.name for n in server.store.namespaces())
            raise HTTPError(
                400, f"unknown namespace {ns!r} (known namespaces: "
                     f"{', '.join(known)})")
        return ns

    # ------------------------------------------------------------ jobs

    def _h_get_jobs(self, h, parts, q):
        jobs = self._rpc("Job.List", {"namespace": self._ns_param(q)})
        prefix = q.get("prefix", "")
        return [_job_stub(j) for j in jobs
                if j.id.startswith(prefix)
                and self._ns_visible(h, j.namespace)]

    def _h_put_jobs(self, h, parts, q):
        body = h._body()
        if len(parts) > 1 and parts[1] == "parse":
            return self._parse_jobspec(body)
        job = from_wire(Job, body.get("Job") or body.get("job") or body)
        # the authoritative namespace is the one in the job body — re-check
        # against it (the URL-level check used the ?namespace= param)
        acl = getattr(h, "acl", None)
        if getattr(self.agent.server, "acl_enabled", False):
            from nomad_tpu.acl.policy import CAP_SUBMIT_JOB
            if acl is None or not acl.allows(job.namespace, CAP_SUBMIT_JOB):
                raise HTTPError(
                    403, f"Permission denied: needs submit-job in "
                         f"namespace {job.namespace!r}")
        resp = self._rpc("Job.Register", {"job": job})
        return {"EvalID": resp["eval_id"],
                "JobModifyIndex": resp["job_modify_index"]}

    _h_post_jobs = _h_put_jobs

    def _parse_jobspec(self, body):
        from nomad_tpu.jobspec import parse_job
        src = body.get("JobHCL") or body.get("job_hcl") or ""
        if not src:
            raise HTTPError(400, "JobHCL required")
        return parse_job(src)

    # sub-resources under /v1/job/<id>/... ; the id itself may contain
    # slashes (dispatched/periodic children), so scan from the end
    _JOB_SUBS = {"allocations", "evaluations", "deployments", "deployment",
                 "summary", "versions", "evaluate", "plan", "dispatch",
                 "stability", "revert", "force", "scale"}

    @classmethod
    def _job_path(cls, parts):
        """['job', *id-segments, sub?] -> (job_id, sub)."""
        segs = parts[1:]
        if segs and segs[-1] == "force" and len(segs) >= 2 \
                and segs[-2] == "periodic":
            return "/".join(segs[:-2]), "periodic/force"
        if segs and segs[-1] in cls._JOB_SUBS:
            return "/".join(segs[:-1]), segs[-1]
        return "/".join(segs), None

    def _h_get_job_id(self, h, parts, q):
        ns = q.get("namespace", "default")
        job_id, sub = self._job_path(parts)
        store = self.agent.server.store
        if sub is None:
            job = self._rpc("Job.GetJob", {"namespace": ns, "job_id": job_id})
            if job is None:
                raise HTTPError(404, f"job not found: {job_id}")
            return job
        if sub == "allocations":
            return [_alloc_stub(a) for a in self._rpc(
                "Job.Allocations", {"namespace": ns, "job_id": job_id})]
        if sub == "evaluations":
            return self._rpc("Job.Evaluations",
                             {"namespace": ns, "job_id": job_id})
        if sub == "deployments":
            return [d for d in self._rpc("Deployment.List", {})
                    if d.job_id == job_id and d.namespace == ns]
        if sub == "deployment":
            return store.latest_deployment_by_job_id(ns, job_id)
        if sub == "summary":
            return store.job_summary(ns, job_id)
        if sub == "versions":
            return store.job_versions(ns, job_id)
        if sub == "scale":
            return self._rpc("Job.ScaleStatus",
                             {"namespace": ns, "job_id": job_id})
        raise HTTPError(404, f"no handler for job/{sub}")

    def _h_put_job_id(self, h, parts, q):
        ns = q.get("namespace", "default")
        job_id, sub = self._job_path(parts)
        if sub is None:                      # update = register
            return self._h_put_jobs(h, ["jobs"], q)
        if sub == "scale":
            body = h._body()
            target = body.get("Target", {}) or {}
            return self._rpc("Job.Scale", {
                "namespace": ns, "job_id": job_id,
                "group": target.get("Group", body.get("group", "")),
                "count": body.get("Count", body.get("count")),
                "message": body.get("Message", ""),
                "error": bool(body.get("Error", False)),
                "meta": body.get("Meta")})
        if sub == "evaluate":
            job = self._rpc("Job.GetJob", {"namespace": ns, "job_id": job_id})
            if job is None:
                raise HTTPError(404, f"job not found: {job_id}")
            from nomad_tpu.structs import Evaluation, EvalStatus
            from nomad_tpu.structs.evaluation import EvalTrigger
            ev = Evaluation(namespace=ns, priority=job.priority,
                            type=job.type, job_id=job_id,
                            triggered_by=EvalTrigger.JOB_REGISTER,
                            status=EvalStatus.PENDING)
            self._rpc("Eval.Create", {"evals": [ev]})
            return {"EvalID": ev.id}
        if sub == "plan":
            body = h._body()
            job = from_wire(Job, body.get("Job") or body.get("job") or {})
            return self._rpc("Job.Plan", {"job": job,
                                          "diff": body.get("Diff", True)})
        if sub == "periodic/force":
            return self._force_periodic(ns, job_id)
        if sub == "dispatch":
            body = h._body()
            return self._rpc("Job.Dispatch", {
                "namespace": ns, "job_id": job_id,
                "payload": body.get("Payload", ""),
                "meta": body.get("Meta") or {}})
        if sub == "stability":
            body = h._body()
            self._rpc("Job.Stability", {
                "namespace": ns, "job_id": job_id,
                "version": body.get("JobVersion", 0),
                "stable": body.get("Stable", True)})
            return {}
        if sub == "revert":
            body = h._body()
            return self._rpc("Job.Revert", {
                "namespace": ns, "job_id": job_id,
                "version": body.get("JobVersion", 0)})
        raise HTTPError(404, f"no handler for job/{sub}")

    _h_post_job_id = _h_put_job_id

    def _force_periodic(self, ns, job_id):
        server = self.agent.server
        job = server.store.job_by_id(ns, job_id)
        if job is None or not job.is_periodic():
            raise HTTPError(404, f"periodic job not found: {job_id}")
        child_id = server.periodic._launch(job, time.time())
        return {"DispatchedJobID": child_id}

    def _h_delete_job_id(self, h, parts, q):
        job_id, _ = self._job_path(parts)
        resp = self._rpc("Job.Deregister", {
            "namespace": q.get("namespace", "default"), "job_id": job_id,
            "purge": q.get("purge", "").lower() == "true"})
        return {"EvalID": resp["eval_id"]}

    # ------------------------------------------------------------ nodes

    def _h_get_nodes(self, h, parts, q):
        prefix = q.get("prefix", "")
        return [_node_stub(n) for n in self._rpc("Node.List", {})
                if n.id.startswith(prefix)]

    def _h_get_node_id(self, h, parts, q):
        sub = parts[2] if len(parts) > 2 else None
        if sub == "allocations":
            return self._rpc("Node.GetAllocs", {"node_id": parts[1]})
        node = self._rpc("Node.GetNode", {"node_id": parts[1]})
        if node is None:
            raise HTTPError(404, f"node not found: {parts[1]}")
        return node

    def _h_put_node_id(self, h, parts, q):
        sub = parts[2] if len(parts) > 2 else None
        body = h._body()
        if sub == "drain":
            spec = body.get("DrainSpec")
            if spec:
                self._rpc("Node.UpdateDrain", {
                    "node_id": parts[1],
                    "deadline_s": float(spec.get("Deadline", 3600.0)),
                    "ignore_system_jobs": spec.get("IgnoreSystemJobs",
                                                   False)})
            else:                      # nil spec = cancel (reference API)
                self._rpc("Node.CancelDrain", {"node_id": parts[1]})
            return {}
        if sub == "eligibility":
            self._rpc("Node.UpdateEligibility", {
                "node_id": parts[1],
                "eligibility": body.get("Eligibility", "eligible")})
            return {}
        if sub == "purge":
            self._rpc("Node.Deregister", {"node_id": parts[1]})
            return {}
        raise HTTPError(404, f"no handler for node/{sub}")

    _h_post_node_id = _h_put_node_id

    # ------------------------------------------------------------ evals/allocs

    def _h_get_evaluations(self, h, parts, q):
        prefix = q.get("prefix", "")
        return [e for e in self._rpc("Eval.List",
                                     {"namespace": self._ns_param(q)})
                if e.id.startswith(prefix)
                and self._ns_visible(h, e.namespace)]

    def _h_get_evaluation_id(self, h, parts, q):
        sub = parts[2] if len(parts) > 2 else None
        if sub == "allocations":
            allocs = [a for a in self._rpc("Alloc.List", {})
                      if a.eval_id == parts[1]]
            for a in allocs:
                self._require_ns_read(h, a.namespace)
            return allocs
        ev = self._rpc("Eval.GetEval", {"eval_id": parts[1]})
        if ev is None:
            raise HTTPError(404, f"eval not found: {parts[1]}")
        self._require_ns_read(h, ev.namespace)
        return ev

    def _h_get_allocations(self, h, parts, q):
        prefix = q.get("prefix", "")
        return [_alloc_stub(a) for a in
                self._rpc("Alloc.List", {"namespace": self._ns_param(q)})
                if a.id.startswith(prefix)
                and self._ns_visible(h, a.namespace)]

    def _h_get_allocation_id(self, h, parts, q):
        a = self._rpc("Alloc.GetAlloc", {"alloc_id": parts[1]})
        if a is None:
            raise HTTPError(404, f"alloc not found: {parts[1]}")
        self._require_ns_read(h, a.namespace)
        return a

    def _h_post_allocation_id(self, h, parts, q):
        sub = parts[2] if len(parts) > 2 else None
        if sub == "stop":
            a = self._rpc("Alloc.GetAlloc", {"alloc_id": parts[1]})
            if a is None:
                raise HTTPError(404, f"alloc not found: {parts[1]}")
            from nomad_tpu.acl.policy import CAP_ALLOC_LIFECYCLE
            self._require_ns_cap(h, a.namespace, CAP_ALLOC_LIFECYCLE)
            return self._rpc("Alloc.Stop", {"alloc_id": parts[1]})
        raise HTTPError(404, f"no handler for allocation/{sub}")

    _h_put_allocation_id = _h_post_allocation_id

    # ------------------------------------------------------------ deployments

    def _h_get_deployments(self, h, parts, q):
        return [d for d in
                self._rpc("Deployment.List",
                          {"namespace": self._ns_param(q)})
                if self._ns_visible(h, d.namespace)]

    def _h_get_deployment_id(self, h, parts, q):
        d = self._rpc("Deployment.GetDeployment",
                      {"deployment_id": parts[1]})
        if d is None:
            raise HTTPError(404, f"deployment not found: {parts[1]}")
        self._require_ns_read(h, d.namespace)
        return d

    def _h_put_deployment_id(self, h, parts, q):
        # /v1/deployment/<verb>/<id> (reference routing)
        verb, dep_id = parts[1], parts[2] if len(parts) > 2 else None
        body = h._body()
        if verb == "promote":
            return self._rpc("Deployment.Promote", {
                "deployment_id": dep_id, "groups": body.get("Groups")})
        if verb == "fail":
            return self._rpc("Deployment.Fail", {"deployment_id": dep_id})
        if verb == "pause":
            return self._rpc("Deployment.Pause", {
                "deployment_id": dep_id, "pause": body.get("Pause", True)})
        raise HTTPError(404, f"no handler for deployment/{verb}")

    _h_post_deployment_id = _h_put_deployment_id

    # ------------------------------------------------------------ operator

    def _h_get_operator(self, h, parts, q):
        if parts[1:3] == ["scheduler", "configuration"]:
            cfg = self._rpc("Operator.SchedulerGetConfiguration", {})
            return {"SchedulerConfig": cfg}
        if parts[1:3] == ["raft", "configuration"]:
            cfg = self._rpc("Operator.RaftGetConfiguration", {})
            return {
                "Index": cfg["index"],
                "Servers": [
                    {"ID": n, "Node": n, "Voter": True,
                     "Leader": n == cfg["leader"]}
                    for n in cfg["voters"]
                ] + [
                    {"ID": n, "Node": n, "Voter": False, "Leader": False}
                    for n in cfg["nonvoters"]
                ],
            }
        if parts[1:2] == ["integrity"]:
            # local replica's integrity view: last checkpoint digest,
            # quarantine state, repair counters (leader adds per-peer
            # report table)
            return self._rpc("Operator.Integrity", {})
        raise HTTPError(404, "unknown operator path")

    def _h_put_operator(self, h, parts, q):
        if parts[1:3] == ["scheduler", "configuration"]:
            from nomad_tpu.structs.config import SchedulerConfiguration
            cfg = from_wire(SchedulerConfiguration, h._body())
            self._rpc("Operator.SchedulerSetConfiguration", {"config": cfg})
            return {"Updated": True}
        if parts[1:3] == ["raft", "remove-peer"]:
            body = h._body() or {}
            name = body.get("ID") or body.get("Node") or q.get("id", "")
            if not name:
                raise HTTPError(400, "missing peer id")
            out = self._rpc("Operator.RaftRemovePeer", {"name": name})
            return {"Index": out["index"]}
        if parts[1:3] == ["raft", "transfer-leadership"]:
            body = h._body() or {}
            out = self._rpc("Operator.TransferLeadership",
                            {"name": body.get("ID") or body.get("Node")})
            return {"Transferred": out["transferred"],
                    "Leader": out["leader"]}
        raise HTTPError(404, "unknown operator path")

    _h_post_operator = _h_put_operator

    # ------------------------------------------------------------ status/agent

    def _h_get_status(self, h, parts, q):
        if parts[1] == "leader":
            return self._rpc("Status.Leader", {})
        if parts[1] == "peers":
            return self._rpc("Status.Peers", {})
        raise HTTPError(404, "unknown status path")

    # ------------------------------------------------------------ client fs

    def _h_get_client_id(self, h, parts, q):
        """/v1/client/fs/{ls,stat,cat,logs}/<alloc_id> — alloc filesystem
        and task log access (reference client/fs_endpoint.go +
        command/agent/fs_endpoint.go).  Requests for allocs on another
        node forward to that node's advertised agent address (the
        reference's server->client streaming hop)."""
        import os

        if len(parts) < 4 or parts[1] != "fs":
            raise HTTPError(404, "expected /v1/client/fs/<verb>/<alloc>")
        verb, alloc_id = parts[2], parts[3]
        # re-check the capability against the alloc's OWN namespace when
        # this agent can see the record (the ?namespace= param is only
        # the caller's claim, same discipline as the alloc endpoints)
        if self.agent.server is not None:
            alloc = self.agent.server.store.alloc_by_id(alloc_id)
            if alloc is not None:
                from nomad_tpu.acl.policy import CAP_READ_FS, CAP_READ_LOGS
                self._require_ns_cap(
                    h, alloc.namespace,
                    CAP_READ_LOGS if verb == "logs" else CAP_READ_FS)
        client = self.agent.client
        root = None
        if client is not None:
            cand = os.path.join(client.alloc_dir_root, alloc_id)
            if os.path.isdir(cand):
                root = cand
        if root is None:
            # one forwarding hop only: a forwarded request that still
            # finds no local dir must 404, not bounce again (self-proxy
            # loop when a combined agent's alloc dir is already gone)
            if h.headers.get("X-Nomad-Forwarded"):
                raise HTTPError(404,
                                f"allocation {alloc_id} not on this node")
            return self._proxy_fs(h, parts, q)

        def resolve(rel: str) -> str:
            p = os.path.realpath(os.path.join(root, rel.lstrip("/")))
            real_root = os.path.realpath(root)
            if not (p + os.sep).startswith(real_root + os.sep) \
                    and p != real_root:
                raise HTTPError(403, "path escapes allocation directory")
            # secrets dirs are invisible to the fs API even inside the
            # alloc dir (reference client/allocdir escapingfs + the
            # secrets-dir guard, fs_endpoint.go): layout is
            # <alloc>/<task>/secrets — reject any resolved path whose
            # second component under the alloc root is "secrets"
            rel_parts = os.path.relpath(p, real_root).split(os.sep)
            if len(rel_parts) >= 2 and rel_parts[1] == "secrets":
                raise HTTPError(403, "path is in a secrets directory")
            return p

        if verb == "ls":
            d = resolve(q.get("path", "/"))
            if not os.path.isdir(d):
                raise HTTPError(404, f"not a directory: {q.get('path')}")
            out = []
            for name in sorted(os.listdir(d)):
                try:
                    st = os.lstat(os.path.join(d, name))
                except OSError:
                    continue       # raced deletion / dangling symlink
                out.append({"Name": name,
                            "IsDir": os.path.isdir(os.path.join(d, name)),
                            "Size": st.st_size, "ModTime": st.st_mtime})
            return out
        if verb == "stat":
            p = resolve(q.get("path", "/"))
            if not os.path.exists(p):
                raise HTTPError(404, f"no such file: {q.get('path')}")
            st = os.stat(p)
            return {"Name": os.path.basename(p), "IsDir": os.path.isdir(p),
                    "Size": st.st_size, "ModTime": st.st_mtime}
        if verb == "cat":
            p = resolve(q.get("path", "/"))
            if not os.path.isfile(p):
                raise HTTPError(404, f"no such file: {q.get('path')}")
            with open(p, "rb") as fh:
                data = fh.read()
            return self._raw_reply(h, data)
        if verb == "logs":
            return self._client_logs(h, q, root)
        raise HTTPError(404, f"unknown fs verb {verb!r}")

    @staticmethod
    def _raw_reply(h, data: bytes):
        h.send_response(200)
        h.send_header("Content-Type", "application/octet-stream")
        h.send_header("Content-Length", str(len(data)))
        h.end_headers()
        try:
            h.wfile.write(data)
        except (BrokenPipeError, ConnectionResetError):
            pass
        return _STREAMED

    def _client_logs(self, h, q, root: str):
        """?task=&type=stdout|stderr&offset=&origin=start|end&follow="""
        import os

        from nomad_tpu.client.logmon import log_size, read_log
        task = q.get("task", "")
        kind = q.get("type", "stdout")
        if kind not in ("stdout", "stderr"):
            raise HTTPError(400, "type must be stdout or stderr")
        logs_dir = os.path.join(root, "alloc", "logs")
        offset = int(q.get("offset", 0))
        if q.get("origin", "start") == "end":
            offset = max(0, log_size(logs_dir, task, kind) - offset)
        if q.get("follow", "") not in ("true", "1"):
            data, _ = read_log(logs_dir, task, kind, offset)
            return self._raw_reply(h, data)
        # follow: chunked stream of appended bytes until timeout/close
        deadline = time.time() + float(q.get("timeout", 30.0))
        h.send_response(200)
        h.send_header("Content-Type", "application/octet-stream")
        h.send_header("Transfer-Encoding", "chunked")
        h.end_headers()
        try:
            while time.time() < deadline:
                data, offset = read_log(logs_dir, task, kind, offset)
                if data:
                    h.wfile.write(hex(len(data))[2:].encode() + b"\r\n"
                                  + data + b"\r\n")
                    h.wfile.flush()
                else:
                    time.sleep(0.25)
            h.wfile.write(b"0\r\n\r\n")
        except (BrokenPipeError, ConnectionResetError):
            pass
        return _STREAMED

    def _proxy_fs(self, h, parts, q):
        """Forward an fs request to the agent on the alloc's node."""
        import urllib.request

        server = self.agent.server
        if server is None:
            raise HTTPError(404, "allocation not on this node")
        alloc = server.store.alloc_by_id(parts[3])
        if alloc is None:
            raise HTTPError(404, f"unknown allocation {parts[3]}")
        node = server.store.node_by_id(alloc.node_id)
        addr = getattr(node, "http_addr", "") if node else ""
        if not addr:
            raise HTTPError(
                404, "allocation's node advertises no HTTP address")
        url = (f"http://{addr}/v1/" + "/".join(parts)
               + ("?" + urllib.parse.urlencode(q) if q else ""))
        headers = {"X-Nomad-Forwarded": "1"}
        token = h.headers.get("X-Nomad-Token", "")
        if token:
            headers["X-Nomad-Token"] = token   # ACLs check on both hops
        req = urllib.request.Request(url, headers=headers)
        # socket timeout must outlast a quiet follow window, or an idle
        # tail-follow is silently truncated mid-stream
        timeout = float(q.get("timeout", 30.0)) + 30.0
        # connect BEFORE writing any response bytes: upstream errors
        # must map to clean statuses, not corrupt a half-sent stream
        try:
            resp = urllib.request.urlopen(req, timeout=timeout)
        except urllib.error.HTTPError as e:
            raise HTTPError(e.code, e.read().decode(errors="replace"))
        except Exception as e:                       # noqa: BLE001
            raise HTTPError(502, f"fs forward to {addr} failed: {e}")
        try:
            with resp:
                h.send_response(resp.status)
                h.send_header("Content-Type",
                              resp.headers.get("Content-Type",
                                               "application/octet-stream"))
                h.send_header("Transfer-Encoding", "chunked")
                h.end_headers()
                while True:
                    chunk = resp.read(65536)
                    if not chunk:
                        break
                    h.wfile.write(hex(len(chunk))[2:].encode() + b"\r\n"
                                  + chunk + b"\r\n")
                    h.wfile.flush()
                h.wfile.write(b"0\r\n\r\n")
        except Exception:                            # noqa: BLE001
            # headers already sent: truncate the stream, never write a
            # second status line into it
            pass
        return _STREAMED

    def _h_get_agent(self, h, parts, q):
        if parts[1] == "self":
            cfg = self.agent.config
            return {"config": to_wire(cfg), "member": {"Name": cfg.name},
                    "stats": {"client": self.agent.client is not None,
                              "server": self.agent.server is not None}}
        if parts[1] == "members":
            return {"Members": [
                {"Name": m["name"], "Status": m["status"],
                 "Addr": m["addr"]}
                for m in self._rpc("Status.Members", {})]}
        if parts[1] == "health":
            return {"server": {"ok": self.agent.server is not None},
                    "client": {"ok": self.agent.client is not None}}
        if parts[1] == "pprof":
            return self._agent_pprof(h, parts, q)
        if parts[1] == "monitor":
            return self._agent_monitor(h, q)
        raise HTTPError(404, "unknown agent path")

    def _agent_pprof(self, h, parts, q):
        """/v1/agent/pprof/profile — CPU profile of this agent for
        ?seconds= (cProfile stats text; the Python analog of the pprof
        protobuf the reference serves, command/agent/http.go:379-381).
        /v1/agent/pprof/goroutine — all-thread stack dump."""
        kind = parts[2] if len(parts) > 2 else "profile"
        if kind in ("goroutine", "threads"):
            import sys
            import threading as _threading
            import traceback
            names = {t.ident: t.name for t in _threading.enumerate()}
            out = []
            for tid, frame in sys._current_frames().items():
                out.append(f"Thread {names.get(tid, tid)}:\n"
                           + "".join(traceback.format_stack(frame)))
            return {"stacks": "\n".join(out)}
        if kind != "profile":
            raise HTTPError(404, f"unknown pprof kind {kind}")
        import cProfile
        import io
        import pstats
        seconds = min(float(q.get("seconds", 1.0)), 30.0)
        prof = cProfile.Profile()
        prof.enable()
        time.sleep(seconds)
        prof.disable()
        buf = io.StringIO()
        pstats.Stats(prof, stream=buf).sort_stats("cumulative") \
            .print_stats(60)
        return {"seconds": seconds, "profile": buf.getvalue()}

    def _agent_monitor(self, h, q):
        """/v1/agent/monitor — chunked stream of this agent's log lines
        (reference command/agent/agent_endpoint.go monitor)."""
        deadline = time.time() + float(q.get("timeout", 5.0))
        last_seq = 0
        h.send_response(200)
        h.send_header("Content-Type", "text/plain")
        h.send_header("Transfer-Encoding", "chunked")
        h.end_headers()
        try:
            # replay the ring, then follow by sequence number (the ring
            # rotates; indexes would shift under the reader)
            while time.time() < deadline:
                snap = [(seq, line) for seq, line
                        in list(self.agent.log_ring) if seq > last_seq]
                new = [line for _, line in snap]
                if new:
                    last_seq = snap[-1][0]
                    chunk = ("\n".join(new) + "\n").encode()
                    h.wfile.write(hex(len(chunk))[2:].encode() + b"\r\n"
                                  + chunk + b"\r\n")
                    h.wfile.flush()
                else:
                    with self.agent._log_cv:
                        self.agent._log_cv.wait(0.25)
            h.wfile.write(b"0\r\n\r\n")
        except (BrokenPipeError, ConnectionResetError):
            pass
        return _STREAMED

    # ------------------------------------------------------------ search

    def _h_post_search(self, h, parts, q):
        """Prefix search via the server-side Search.PrefixSearch RPC
        (reference nomad/search_endpoint.go); the agent only computes the
        caller's namespace visibility from its ACL token."""
        body = h._body()
        namespaces = None
        if getattr(self.agent.server, "acl_enabled", False):
            store = self.agent.server.store
            namespaces = [ns.name for ns in store.namespaces()
                          if self._ns_visible(h, ns.name)]
        resp = self._rpc("Search.PrefixSearch", {
            "prefix": body.get("Prefix", ""),
            "context": body.get("Context", "all"),
            "namespaces": namespaces})
        return {"Matches": resp["matches"],
                "Truncations": resp["truncations"]}

    # ------------------------------------------------------------ metrics

    def _h_get_metrics(self, h, parts, q):
        if q.get("format") == "prometheus":
            body = global_metrics.prometheus().encode()
            h.send_response(200)
            h.send_header("Content-Type", "text/plain; version=0.0.4")
            h.send_header("Content-Length", str(len(body)))
            h.end_headers()
            h.wfile.write(body)
            return _STREAMED
        return global_metrics.snapshot()

    # ------------------------------------------------------------ traces

    def _h_get_traces(self, h, parts, q):
        """/v1/traces — trace summaries from the in-process span stores;
        /v1/traces/<trace_id> — that trace's spans (`?format=chrome`
        exports Chrome-trace JSON for Perfetto)."""
        tracer = tracing.active
        if tracer is None:
            raise HTTPError(404, "tracing disabled "
                                 "(set NOMAD_TPU_TRACE=1)")
        return tracer.traces()

    def _h_get_traces_id(self, h, parts, q):
        tracer = tracing.active
        if tracer is None:
            raise HTTPError(404, "tracing disabled "
                                 "(set NOMAD_TPU_TRACE=1)")
        trace_id = parts[1]
        spans = [s.to_dict() for s in tracer.spans(trace_id)]
        if not spans:
            raise HTTPError(404, f"no spans for trace {trace_id!r}")
        if q.get("format") == "chrome":
            return tracing.chrome_trace(spans)
        return {"trace_id": trace_id, "spans": spans}

    # ------------------------------------------------------------ events

    def _h_get_event(self, h, parts, q):
        """/v1/event/stream — NDJSON event stream with ?topic=Topic:Key
        filters (reference nomad/stream/ndjson.go)."""
        if len(parts) < 2 or parts[1] != "stream":
            raise HTTPError(404, "unknown event path")
        topics: dict = {}
        raw = urllib.parse.urlparse(h.path).query
        for k, vals in urllib.parse.parse_qs(raw).items():
            if k != "topic":
                continue
            for v in vals:
                topic, _, key = v.partition(":")
                topics.setdefault(topic, []).append(key or "*")
        if not topics:
            topics = {"*": ["*"]}
        acl_on = getattr(self.agent.server, "acl_enabled", False)
        sub = self.agent.server.event_broker.subscribe(
            topics, from_index=int(q.get("index", 0)))
        filter_fn = None
        if acl_on:
            filter_fn = (lambda ev: not ev.namespace
                         or self._ns_visible(h, ev.namespace))
        heartbeat = _parse_wait(q["heartbeat"]) if "heartbeat" in q else None
        streamer = EventStreamer(sub, heartbeat=heartbeat,
                                 filter_fn=filter_fn)
        try:
            h.send_response(200)
            h.send_header("Content-Type", "application/json")
            h.send_header("Transfer-Encoding", "chunked")
            h.end_headers()

            def write(chunk: bytes) -> None:
                h.wfile.write(hex(len(chunk))[2:].encode() + b"\r\n"
                              + chunk + b"\r\n")
                h.wfile.flush()

            # the stream is held open, not worked on: a 45 s "work" span
            # here would overlap every idle gap of a profiler trace
            with tracing.span("http.park", wait=True):
                streamer.run(write, float(q.get("timeout", 5.0)))
            h.wfile.write(b"0\r\n\r\n")
        except (BrokenPipeError, ConnectionResetError):
            pass
        finally:
            sub.close()
        return _STREAMED

    # ------------------------------------------------------------ ACL mgmt

    def _h_get_acl(self, h, parts, q):
        server = self.agent.server
        if parts[1] == "policies":
            return [{"Name": p.name, "Description": p.description}
                    for p in server.acl_policies()]
        if parts[1] == "policy" and len(parts) > 2:
            p = server.acl_policy(parts[2])
            if p is None:
                raise HTTPError(404, f"policy not found: {parts[2]}")
            return {"Name": p.name, "Description": p.description,
                    "Rules": p.rules}
        if parts[1] == "tokens":
            return [_token_stub(t) for t in server.acl_tokens()]
        if parts[1] == "token" and len(parts) > 2:
            t = server.acl_token(parts[2]) if parts[2] != "self" else \
                server.acl_token_by_secret(
                    h.headers.get("X-Nomad-Token", ""))
            if t is None:
                raise HTTPError(404, "token not found")
            return _token_full(t)
        raise HTTPError(404, "unknown acl path")

    def _h_put_acl(self, h, parts, q):
        server = self.agent.server
        body = h._body()
        if parts[1] == "policy" and len(parts) > 2:
            server.upsert_acl_policy(
                parts[2], body.get("Description", ""),
                body.get("Rules", ""))
            return {}
        if parts[1] == "token":
            t = server.create_acl_token(
                name=body.get("Name", ""),
                type_=body.get("Type", "client"),
                policies=body.get("Policies") or [])
            return _token_full(t)
        if parts[1] == "bootstrap":
            t = server.bootstrap_acl()
            return _token_full(t)
        raise HTTPError(404, "unknown acl path")

    _h_post_acl = _h_put_acl

    def _h_delete_acl(self, h, parts, q):
        server = self.agent.server
        if parts[1] == "policy" and len(parts) > 2:
            server.delete_acl_policy(parts[2])
            return {}
        if parts[1] == "token" and len(parts) > 2:
            server.delete_acl_token(parts[2])
            return {}
        raise HTTPError(404, "unknown acl path")

    # ------------------------------------------------------------ namespaces

    def _h_get_namespaces(self, h, parts, q):
        return self._rpc("Namespace.List", {})

    def _h_put_namespaces(self, h, parts, q):
        body = h._body()
        return self._rpc("Namespace.Upsert", {
            "name": body.get("Name", "default"),
            "description": body.get("Description", ""),
            "quota": body.get("Quota", "")})

    _h_post_namespaces = _h_put_namespaces

    def _h_get_namespace_id(self, h, parts, q):
        ns = self.agent.server.namespace(parts[1])
        if ns is None:
            raise HTTPError(404, f"namespace not found: {parts[1]}")
        return ns

    def _h_delete_namespace_id(self, h, parts, q):
        return self._rpc("Namespace.Delete", {"name": parts[1]})

    # ------------------------------------------------------------ quotas

    def _h_get_quotas(self, h, parts, q):
        return self._rpc("Quota.List", {})

    def _h_put_quotas(self, h, parts, q):
        from nomad_tpu.structs.namespace import QuotaSpec
        spec = from_wire(QuotaSpec, h._body())
        if not spec.name:
            raise HTTPError(400, "quota spec requires a Name")
        return self._rpc("Quota.Upsert", {"spec": spec})

    _h_post_quotas = _h_put_quotas

    def _h_get_quota_id(self, h, parts, q):
        # /v1/quota/usage/<namespace> | /v1/quota/<name>
        if parts[1] == "usage":
            if len(parts) > 2:
                return {"Namespace": parts[2],
                        "Usage": self._rpc(
                            "Quota.Usage",
                            {"namespace": parts[2]}).get(parts[2], {})}
            return self._rpc("Quota.Usage", {})
        return self._rpc("Quota.GetQuota", {"name": parts[1]})

    _h_put_quota_id = _h_put_quotas
    _h_post_quota_id = _h_put_quotas

    def _h_delete_quota_id(self, h, parts, q):
        resp = self._rpc("Quota.Delete", {"name": parts[1]})
        return resp

    # ------------------------------------------------------------ CSI
    # (reference command/agent/csi_endpoint.go: /v1/volumes,
    #  /v1/volume/csi/<id>, /v1/plugins, /v1/plugin/csi/<id>)

    def _h_get_volumes(self, h, parts, q):
        ns = q.get("namespace", "default")
        return self._rpc("CSIVolume.List", {"namespace": ns})

    def _h_get_volume_id(self, h, parts, q):
        # /v1/volume/csi/<id>
        vol_id = parts[2] if len(parts) > 2 else parts[1]
        vol = self._rpc("CSIVolume.Get", {
            "namespace": q.get("namespace", "default"),
            "volume_id": vol_id})
        out = vol.stub()
        out["ReadAllocs"] = sorted(vol.read_claims)
        out["WriteAllocs"] = sorted(vol.write_claims)
        return out

    def _h_put_volume_id(self, h, parts, q):
        body = h._body()
        from nomad_tpu.structs.csi import CSIVolume
        vols = body.get("Volumes") or [body.get("Volume", body)]
        for v in vols:
            if isinstance(v, dict):
                v = CSIVolume(
                    id=v.get("ID", ""),
                    namespace=v.get("Namespace",
                                    q.get("namespace", "default")),
                    name=v.get("Name", ""),
                    plugin_id=v.get("PluginID", ""),
                    access_mode=v.get("AccessMode", ""),
                    attachment_mode=v.get("AttachmentMode", ""),
                    requested_capabilities=v.get(
                        "RequestedCapabilities", []),
                )
            # re-check against the body's authoritative namespace (the
            # route gate only saw ?namespace=; mirrors _h_put_jobs)
            from nomad_tpu.acl.policy import CAP_CSI_WRITE_VOLUME
            self._require_ns_cap(h, v.namespace, CAP_CSI_WRITE_VOLUME)
            self._rpc("CSIVolume.Register", {"volume": v})
        return {}

    _h_post_volume_id = _h_put_volume_id

    def _h_delete_volume_id(self, h, parts, q):
        vol_id = parts[2] if len(parts) > 2 else parts[1]
        self._rpc("CSIVolume.Deregister", {
            "namespace": q.get("namespace", "default"),
            "volume_id": vol_id,
            "force": q.get("force", "") == "true"})
        return {}

    def _h_get_services(self, h, parts, q):
        """GET /v1/services: grouped nomad-native service listing
        (reference command/agent/service_registration_endpoint.go)."""
        return self._rpc("Service.List",
                         {"namespace": q.get("namespace")})

    def _h_get_service_id(self, h, parts, q):
        """GET /v1/service/<name>: instances of one service."""
        return self._rpc("Service.GetService", {
            "namespace": q.get("namespace", "default"),
            "service_name": parts[1]})

    def _h_delete_service_id(self, h, parts, q):
        """DELETE /v1/service/<name>/<id>."""
        if len(parts) < 3:
            raise HTTPError(400, "service registration id required")
        self._rpc("Service.Delete", {"id": parts[2]})
        return {}

    def _h_get_regions(self, h, parts, q):
        return self._rpc("Status.Regions", {})

    def _h_get_scaling(self, h, parts, q):
        """GET /v1/scaling/policies | /v1/scaling/policy/<id>."""
        if len(parts) >= 2 and parts[1] == "policies":
            return self._rpc("Scaling.ListPolicies",
                             {"namespace": q.get("namespace")})
        if len(parts) >= 3 and parts[1] == "policy":
            return self._rpc("Scaling.GetPolicy", {"id": parts[2]})
        raise HTTPError(404, "no handler for scaling path")

    def _h_get_plugins(self, h, parts, q):
        return self._rpc("CSIPlugin.List", {})

    def _h_get_plugin_id(self, h, parts, q):
        plugin_id = parts[2] if len(parts) > 2 else parts[1]
        plug = self._rpc("CSIPlugin.Get", {"plugin_id": plugin_id})
        return plug.stub()


_STREAMED = object()


def _is_id(s: str) -> bool:
    return bool(re.fullmatch(r"[0-9a-f-]{8,}", s))


def _job_stub(j) -> dict:
    return {"ID": j.id, "Name": j.name, "Namespace": j.namespace,
            "Type": j.type, "Priority": j.priority, "Status": j.status,
            "JobModifyIndex": j.job_modify_index,
            "ModifyIndex": j.modify_index, "Stop": j.stop}


def _node_stub(n) -> dict:
    return {"ID": n.id, "Name": n.name, "Datacenter": n.datacenter,
            "Status": n.status, "SchedulingEligibility":
            n.scheduling_eligibility, "Drain": n.drain_strategy is not None,
            "NodeClass": n.node_class}


def _alloc_stub(a) -> dict:
    return {"ID": a.id, "Name": a.name, "JobID": a.job_id,
            "Namespace": a.namespace,
            "TaskGroup": a.task_group, "NodeID": a.node_id,
            "EvalID": a.eval_id, "ClientStatus": a.client_status,
            "DesiredStatus": a.desired_status,
            "ModifyIndex": a.modify_index}


def _token_stub(t) -> dict:
    return {"AccessorID": t.accessor_id, "Name": t.name, "Type": t.type}


def _token_full(t) -> dict:
    return {"AccessorID": t.accessor_id, "SecretID": t.secret_id,
            "Name": t.name, "Type": t.type, "Policies": list(t.policies),
            "Global": t.global_}
