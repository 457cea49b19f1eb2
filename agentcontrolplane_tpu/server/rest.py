"""REST API server (aiohttp) — the reference's gin server, plus in-tree
human-approval endpoints.

Rebuilt from ``acp/internal/server/server.go`` (1,545 LoC):

- ``POST /v1/tasks``   — create a Task for an agent (strict JSON decode,
  404 on missing agent, name ``<agent>-task-<rand8>`` labeled with the agent;
  server.go:1274-1381)
- ``GET /v1/tasks`` / ``GET /v1/tasks/{name}``
- ``POST /v1/agents`` — create Agent + LLM + Secret (+MCP servers)
  "transactionally-ish" with manual cleanup on failure (server.go:219-437)
- ``GET/DELETE /v1/agents/{name}``, ``GET /v1/agents``
- ``POST /v1/beta3/events`` — inbound webhook: fabricates Secret +
  ContactChannel + Task with thread continuity (server.go:1384-1545)

In-tree additions (the reference delegates these to the HumanLayer SaaS):

- ``GET /v1/approvals`` / ``POST /v1/approvals/{id}/approve|reject``
- ``GET /v1/contacts`` / ``POST /v1/contacts/{id}/respond``
- ``GET /metrics`` (Prometheus text), ``/healthz``, ``/readyz``
- ``GET /v1/events`` — execution history
"""

from __future__ import annotations

import asyncio
import json
import os
import ssl
from typing import TYPE_CHECKING, Any, Optional

from aiohttp import web

from ..api.meta import ObjectMeta
from ..api.resources import (
    LABEL_AGENT,
    LABEL_V1BETA3,
    Agent,
    AgentSpec,
    BaseConfig,
    ContactChannel,
    ContactChannelSpec,
    LLM,
    LLMSpec,
    LocalObjectRef,
    Message,
    Secret,
    SecretKeyRef,
    SecretSpec,
    SlackChannelConfig,
    Task,
    TaskSpec,
)
from ..kernel.errors import AlreadyExists, Conflict, Invalid, NotFound
from ..observability.metrics import REGISTRY
from ..validation import generate_k8s_random_string, validate_task_message_input

if TYPE_CHECKING:
    from ..operator import Operator


def _json_error(status: int, message: str) -> web.Response:
    return web.json_response({"error": message}, status=status)


def _overloaded_response(e) -> web.Response:
    """503 for an EngineOverloadedError shed: tell the client when to come
    back instead of parking its connection (stream and non-stream paths
    share this so the shed contract can't diverge)."""
    return web.json_response(
        {"error": str(e)},
        status=503,
        headers={"Retry-After": str(max(1, int(e.retry_after_s)))},
    )


# health probes stay open (the reference likewise exempts healthz/readyz from
# its metrics authn filter, acp/cmd/main.go:306-313)
_UNAUTHENTICATED_PATHS = {"/healthz", "/readyz"}


@web.middleware
async def _error_middleware(request: web.Request, handler):
    """Map kernel errors that escape a handler to proper statuses — in
    particular a fencing Conflict from a deposed leader's FencedStore must
    surface as 409, not a 500 with a traceback. Handlers that catch these
    themselves are unaffected (this sees only what escapes)."""
    try:
        return await handler(request)
    except (AlreadyExists, Conflict) as e:
        return _json_error(409, str(e))
    except NotFound as e:
        return _json_error(404, str(e))
    except Invalid as e:
        return _json_error(400, str(e))


def _auth_middleware(token: str):
    """Bearer-token authn for every route except health probes — the
    standalone stand-in for the reference's authn/authz-filtered serving
    posture (acp/cmd/main.go:167-206). Enabled when a token is configured
    (--api-token / ACP_API_TOKEN); default off for localhost dev."""
    from ..utils.tokens import token_matches

    expected = f"Bearer {token}"

    @web.middleware
    async def middleware(request: web.Request, handler):
        if request.path not in _UNAUTHENTICATED_PATHS:
            if not token_matches(
                request.headers.get("Authorization", ""), expected
            ):
                return _json_error(401, "unauthorized")
        return await handler(request)

    return middleware


def _redact_secrets(manifest: dict[str, Any]) -> dict[str, Any]:
    """Blank Secret payloads on read endpoints. The reference never serves
    Secret contents over its REST API at all (routes:
    acp/internal/server/server.go:132-156; Secrets sit behind k8s RBAC);
    we keep the object GETtable for kubectl-style UX but redact the data."""
    if manifest.get("kind") == "Secret":
        data = (manifest.get("spec") or {}).get("data")
        if data:
            manifest["spec"]["data"] = {k: "<redacted>" for k in data}
    return manifest


def _strict_decode(raw: bytes, allowed: set[str]) -> dict[str, Any]:
    """DisallowUnknownFields equivalent (server.go:1288-1306)."""
    body = json.loads(raw)
    if not isinstance(body, dict):
        raise Invalid("request body must be a JSON object")
    unknown = set(body) - allowed
    if unknown:
        raise Invalid(f"unknown fields: {sorted(unknown)}")
    return body


def task_to_json(task: Task) -> dict[str, Any]:
    return {
        "name": task.name,
        "namespace": task.namespace,
        "agentName": task.spec.agent_ref.name,
        "phase": task.status.phase,
        "status": task.status.status,
        "statusDetail": task.status.status_detail,
        "output": task.status.output,
        "userMsgPreview": task.status.user_msg_preview,
        "messageCount": task.status.message_count,
        "contextWindow": [m.model_dump(exclude_none=True) for m in task.status.context_window],
        "error": task.status.error,
        "creationTimestamp": task.metadata.creation_timestamp,
    }


class RestServer:
    def __init__(self, operator: "Operator", host: str = "127.0.0.1", port: Optional[int] = None):
        self.operator = operator
        # Leader-gated serving writes through the FENCED view: once another
        # replica adopts the election lease, this replica's in-flight REST
        # mutations observe Conflict instead of landing on a stale
        # leadership view (docs/distributed-locking.md, "Fencing").
        # fenced_store() itself degrades to the raw store when leader
        # election is off.
        self.store = operator.manager.fenced_store()
        self.host = host
        self.port = port if port is not None else operator.options.api_port
        # options only — the CLI already defaults --api-token from
        # $ACP_API_TOKEN; a second env lookup here would silently flip auth
        # on for embedded/test servers
        self.api_token = operator.options.api_token
        middlewares = [_auth_middleware(self.api_token)] if self.api_token else []
        middlewares.append(_error_middleware)
        self.app = web.Application(middlewares=middlewares)
        self._register_routes()
        self._runner: Optional[web.AppRunner] = None
        self._site: Optional[web.TCPSite] = None
        self.bound_port: Optional[int] = None
        # TLS posture (acp/cmd/main.go:118-166 parity): cert+key => HTTPS,
        # client CA => verified client certs (mTLS). The context is built
        # eagerly so a bad cert path fails at construction, not mid-serve.
        opts = operator.options
        self._tls_paths = (
            (opts.tls_cert_path, opts.tls_key_path, opts.tls_client_ca_path)
            if getattr(opts, "tls_cert_path", None) and getattr(opts, "tls_key_path", None)
            else None
        )
        self._ssl_context = self._build_ssl_context() if self._tls_paths else None
        self._tls_mtimes = self._stat_tls_files()

    def _build_ssl_context(self) -> ssl.SSLContext:
        cert, key, client_ca = self._tls_paths  # type: ignore[misc]
        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        ctx.minimum_version = ssl.TLSVersion.TLSv1_2
        ctx.load_cert_chain(cert, key)
        if client_ca:
            ctx.load_verify_locations(client_ca)
            ctx.verify_mode = ssl.CERT_REQUIRED
        return ctx

    def _stat_tls_files(self) -> tuple:
        if not self._tls_paths:
            return ()
        return tuple(
            os.stat(p).st_mtime_ns if p else None for p in self._tls_paths
        )

    async def _tls_reload_loop(self) -> None:
        """Cert-watcher parity (acp/cmd/main.go:124-136): rotated cert/key
        files are picked up for NEW handshakes without a restart. A FRESH
        SSLContext is built and the listener swapped to it — reloading into
        the live context would be additive for the client-CA trust store
        (``load_verify_locations`` never unloads), so a rotated-OUT client
        CA would keep passing mTLS until restart. In-flight connections
        keep their session; the accept gap during the swap is a few ms."""
        interval = float(os.environ.get("ACP_TLS_RELOAD_INTERVAL_S", "30"))
        while True:
            await asyncio.sleep(interval)
            try:
                mtimes = self._stat_tls_files()
            except OSError:
                continue  # mid-rotation; retry next tick
            if mtimes != self._tls_mtimes and self._ssl_context is not None:
                try:
                    new_ctx = self._build_ssl_context()
                except (OSError, ssl.SSLError):
                    continue  # partial rotation; keep serving the old chain
                try:
                    await self._swap_listener(new_ctx)
                except (OSError, RuntimeError):
                    continue  # swap failed; mtimes stay stale so we retry
                self._ssl_context = new_ctx
                self._tls_mtimes = mtimes

    async def _swap_listener(self, new_ctx: ssl.SSLContext) -> None:
        """Stop the listening socket and re-bind it with the new context.
        Existing connections are owned by the runner and survive; only the
        accept loop restarts. Failure handling matters: a site whose
        start() failed must never be left in self._site (its stop() raises
        RuntimeError and would kill the reload loop), and losing the bind
        entirely must fall back to re-binding with the OLD context rather
        than leaving the server refusing all new connections."""
        if self._runner is None or self.bound_port is None:
            return
        port = self.bound_port
        if self._site is not None:
            await self._site.stop()
            self._site = None  # never retain a stopped/unstarted site
        site = web.TCPSite(self._runner, self.host, port, ssl_context=new_ctx)
        try:
            await site.start()
        except OSError:
            fallback = web.TCPSite(
                self._runner, self.host, port, ssl_context=self._ssl_context
            )
            try:
                await fallback.start()
                self._site = fallback
            except OSError:
                pass  # _site stays None; the next tick re-attempts the bind
            raise
        self._site = site

    def _register_routes(self) -> None:
        r = self.app.router
        r.add_post("/v1/tasks", self.create_task)
        r.add_get("/v1/tasks", self.list_tasks)
        r.add_get("/v1/tasks/{name}", self.get_task)
        r.add_post("/v1/agents", self.create_agent)
        r.add_get("/v1/agents", self.list_agents)
        r.add_get("/v1/agents/{name}", self.get_agent)
        r.add_patch("/v1/agents/{name}", self.update_agent)
        r.add_delete("/v1/agents/{name}", self.delete_agent)
        r.add_delete("/v1/tasks/{name}", self.delete_task)
        r.add_post("/v1/beta3/events", self.handle_v1beta3_event)
        r.add_post("/v1/apply", self.apply_manifests)
        r.add_get("/v1/resources/{kind}", self.list_resources)
        r.add_get("/v1/resources/{kind}/{name}", self.get_resource)
        r.add_delete("/v1/resources/{kind}/{name}", self.delete_resource)
        r.add_get("/v1/approvals", self.list_approvals)
        r.add_post("/v1/approvals/{call_id}/approve", self.approve)
        r.add_post("/v1/approvals/{call_id}/reject", self.reject)
        r.add_get("/v1/contacts", self.list_contacts)
        r.add_post("/v1/contacts/{call_id}/respond", self.respond)
        r.add_get("/v1/events", self.list_events)
        r.add_post("/v1/chat/completions", self.chat_completions)
        r.add_get("/v1/models", self.list_models)
        r.add_get("/v1/engine", self.engine_status)
        r.add_get("/v1/engine/perf", self.engine_perf)
        r.add_get("/v1/engine/flight", self.engine_flight)
        r.add_get("/v1/engine/trace", self.engine_trace)
        r.add_get("/v1/fleet", self.fleet_status)
        r.add_get("/v1/fleet/trace", self.fleet_trace)
        r.add_get("/v1/requests/{rid}/timeline", self.request_timeline)
        r.add_get("/metrics", self.metrics)
        r.add_get("/healthz", self.healthz)
        r.add_get("/readyz", self.healthz)

    # -- lifecycle -------------------------------------------------------

    async def run(self) -> None:
        """Serve until cancelled. Blocking (rather than fire-and-forget) so a
        leader-gated runner can cancel it on leadership loss and restart it on
        re-acquisition (see kernel.runtime._leader_gated_runner)."""
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        self._site = web.TCPSite(
            self._runner, self.host, self.port, ssl_context=self._ssl_context
        )
        await self._site.start()
        self.bound_port = self._site._server.sockets[0].getsockname()[1]  # type: ignore[union-attr]
        reloader = (
            asyncio.ensure_future(self._tls_reload_loop())
            if self._ssl_context is not None
            else None
        )
        try:
            await asyncio.Event().wait()
        finally:
            if reloader is not None:
                reloader.cancel()
            await self.stop()

    async def stop(self) -> None:
        if self._runner is not None:
            runner, self._runner = self._runner, None
            self.bound_port = None
            await runner.cleanup()

    # -- tasks (server.go:1274-1381) -------------------------------------

    async def create_task(self, request: web.Request) -> web.Response:
        try:
            body = _strict_decode(
                await request.read(),
                {"agentName", "userMessage", "contextWindow", "namespace", "contactChannelRef"},
            )
        except (Invalid, json.JSONDecodeError) as e:
            return _json_error(400, str(e))
        agent_name = body.get("agentName", "")
        if not agent_name:
            return _json_error(400, "agentName is required")
        ns = body.get("namespace", "default")
        context_window = None
        if body.get("contextWindow"):
            try:
                context_window = [Message.model_validate(m) for m in body["contextWindow"]]
            except Exception as e:
                return _json_error(400, f"invalid contextWindow: {e}")
        try:
            validate_task_message_input(body.get("userMessage"), context_window)
        except Invalid as e:
            return _json_error(400, str(e))
        if self.store.try_get("Agent", agent_name, ns) is None:
            return _json_error(404, f'agent "{agent_name}" not found')
        name = f"{agent_name}-task-{generate_k8s_random_string(8)}"
        task = Task(
            metadata=ObjectMeta(name=name, namespace=ns, labels={LABEL_AGENT: agent_name}),
            spec=TaskSpec(
                agent_ref=LocalObjectRef(name=agent_name),
                user_message=body.get("userMessage"),
                context_window=context_window,
                contact_channel_ref=(
                    LocalObjectRef(name=body["contactChannelRef"])
                    if body.get("contactChannelRef")
                    else None
                ),
            ),
        )
        created = self.store.create(task)
        return web.json_response(task_to_json(created), status=201)

    async def list_tasks(self, request: web.Request) -> web.Response:
        ns = request.query.get("namespace", "default")
        tasks = [t for t in self.store.list("Task", ns) if isinstance(t, Task)]
        return web.json_response([task_to_json(t) for t in tasks])

    async def get_task(self, request: web.Request) -> web.Response:
        ns = request.query.get("namespace", "default")
        task = self.store.try_get("Task", request.match_info["name"], ns)
        if not isinstance(task, Task):
            return _json_error(404, "task not found")
        return web.json_response(task_to_json(task))

    # -- agents (server.go:219-437) --------------------------------------

    async def create_agent(self, request: web.Request) -> web.Response:
        try:
            body = _strict_decode(
                await request.read(),
                {"name", "namespace", "systemPrompt", "description", "llm", "mcpServers", "subAgents"},
            )
        except (Invalid, json.JSONDecodeError) as e:
            return _json_error(400, str(e))
        name = body.get("name", "")
        ns = body.get("namespace", "default")
        llm_cfg = body.get("llm") or {}
        if not name or not body.get("systemPrompt") or not llm_cfg.get("provider"):
            return _json_error(400, "name, systemPrompt and llm.provider are required")

        created: list = []  # manual cleanup on failure (server.go:219-437)
        try:
            secret_ref = None
            if llm_cfg.get("apiKey"):
                secret = self.store.create(
                    Secret(
                        metadata=ObjectMeta(name=f"{name}-llm-key", namespace=ns),
                        spec=SecretSpec(data={"api-key": llm_cfg["apiKey"]}),
                    )
                )
                created.append(secret)
                secret_ref = SecretKeyRef(name=secret.name, key="api-key")
            llm = self.store.create(
                LLM(
                    metadata=ObjectMeta(name=f"{name}-llm", namespace=ns),
                    spec=LLMSpec(
                        provider=llm_cfg["provider"],
                        api_key_from=secret_ref,
                        parameters=BaseConfig(
                            model=llm_cfg.get("model", ""),
                            base_url=llm_cfg.get("baseURL"),
                        ),
                    ),
                )
            )
            created.append(llm)
            agent = self.store.create(
                Agent(
                    metadata=ObjectMeta(name=name, namespace=ns),
                    spec=AgentSpec(
                        llm_ref=LocalObjectRef(name=llm.name),
                        system=body["systemPrompt"],
                        description=body.get("description", ""),
                        mcp_servers=[LocalObjectRef(name=s) for s in body.get("mcpServers", [])],
                        sub_agents=[LocalObjectRef(name=s) for s in body.get("subAgents", [])],
                    ),
                )
            )
            created.append(agent)
        except Exception as e:  # incl. pydantic ValidationError for bad provider
            for obj in reversed(created):
                try:
                    self.store.delete(obj.kind, obj.metadata.name, obj.metadata.namespace)
                except NotFound:
                    pass
                except Conflict:
                    # deposed mid-create: the fenced cleanup cannot run
                    # either; stop trying (remaining partials are inert —
                    # no Agent references them) and report the deposition
                    break
            status = 409 if isinstance(e, (AlreadyExists, Conflict)) else 400
            return _json_error(status, str(e))
        return web.json_response({"name": name, "namespace": ns, "llm": llm.name}, status=201)

    async def list_agents(self, request: web.Request) -> web.Response:
        ns = request.query.get("namespace", "default")
        agents = [a for a in self.store.list("Agent", ns) if isinstance(a, Agent)]
        return web.json_response(
            [
                {
                    "name": a.name,
                    "ready": a.status.ready,
                    "status": a.status.status,
                    "description": a.spec.description,
                }
                for a in agents
            ]
        )

    async def get_agent(self, request: web.Request) -> web.Response:
        ns = request.query.get("namespace", "default")
        agent = self.store.try_get("Agent", request.match_info["name"], ns)
        if not isinstance(agent, Agent):
            return _json_error(404, "agent not found")
        return web.json_response(
            {
                "name": agent.name,
                "namespace": agent.namespace,
                "systemPrompt": agent.spec.system,
                "llmRef": agent.spec.llm_ref.name,
                "ready": agent.status.ready,
                "status": agent.status.status,
                "statusDetail": agent.status.status_detail,
                "validMCPServers": [s.model_dump() for s in agent.status.valid_mcp_servers],
                "validSubAgents": [s.model_dump() for s in agent.status.valid_sub_agents],
            }
        )

    async def update_agent(self, request: web.Request) -> web.Response:
        """Partial update (server.go:970-1004): systemPrompt / description /
        mcpServers / subAgents; the agent controller revalidates."""
        ns = request.query.get("namespace", "default")
        try:
            body = _strict_decode(
                await request.read(),
                {"systemPrompt", "description", "mcpServers", "subAgents"},
            )
        except (Invalid, json.JSONDecodeError) as e:
            return _json_error(400, str(e))
        for key in ("systemPrompt", "description"):
            if key in body and not isinstance(body[key], str):
                return _json_error(400, f"{key} must be a string")
        for key in ("mcpServers", "subAgents"):
            if key in body and (
                not isinstance(body[key], list)
                or not all(isinstance(s, str) and s for s in body[key])
            ):
                return _json_error(400, f"{key} must be a list of names")
        if body.get("systemPrompt") == "":
            return _json_error(400, "systemPrompt cannot be empty")

        for _ in range(3):  # conflict-retry against concurrent status writes
            agent = self.store.try_get("Agent", request.match_info["name"], ns)
            if not isinstance(agent, Agent):
                return _json_error(404, "agent not found")
            if "systemPrompt" in body:
                agent.spec.system = body["systemPrompt"]
            if "description" in body:
                agent.spec.description = body["description"]
            if "mcpServers" in body:
                agent.spec.mcp_servers = [LocalObjectRef(name=s) for s in body["mcpServers"]]
            if "subAgents" in body:
                agent.spec.sub_agents = [LocalObjectRef(name=s) for s in body["subAgents"]]
            try:
                updated = self.store.update(agent)
            except Conflict:
                continue
            return web.json_response(
                {"name": updated.name, "generation": updated.metadata.generation}
            )
        return _json_error(409, "conflict: concurrent updates, retry")

    async def delete_task(self, request: web.Request) -> web.Response:
        ns = request.query.get("namespace", "default")
        try:
            self.store.delete("Task", request.match_info["name"], ns)
        except NotFound:
            return _json_error(404, "task not found")
        return web.json_response({"deleted": request.match_info["name"]})

    async def delete_agent(self, request: web.Request) -> web.Response:
        ns = request.query.get("namespace", "default")
        try:
            self.store.delete("Agent", request.match_info["name"], ns)
        except NotFound:
            return _json_error(404, "agent not found")
        return web.json_response({"deleted": request.match_info["name"]})

    # -- v1beta3 inbound events (server.go:1384-1545) ---------------------

    async def handle_v1beta3_event(self, request: web.Request) -> web.Response:
        """Inbound webhook: fabricate Secret + ContactChannel + Task so a
        Slack-style thread event becomes a running agent whose final answer
        is routed back via respond_to_human."""
        try:
            body = json.loads(await request.read())
        except json.JSONDecodeError as e:
            return _json_error(400, str(e))
        event_type = body.get("type", "")
        if event_type not in ("agent_email.received", "agent_slack.received", ""):
            return _json_error(400, f"unsupported event type {event_type!r}")
        payload = body.get("event") or body
        agent_name = body.get("agentName") or payload.get("agent_name", "")
        message = (
            payload.get("message")
            or (payload.get("body") or {}).get("text", "")
            or payload.get("text", "")
        )
        channel_token = body.get("channelApiKey") or payload.get("channel_api_key", "")
        thread_id = payload.get("thread_id") or payload.get("thread_ts")
        event_id = payload.get("event_id") or generate_k8s_random_string(8)
        ns = body.get("namespace", "default")
        if not agent_name or not message:
            return _json_error(400, "agentName and message are required")
        if self.store.try_get("Agent", agent_name, ns) is None:
            return _json_error(404, f'agent "{agent_name}" not found')

        secret_name = f"v1beta3-token-{event_id}"
        channel_name = f"v1beta3-channel-{event_id}"
        try:
            self.store.create(
                Secret(
                    metadata=ObjectMeta(name=secret_name, namespace=ns),
                    spec=SecretSpec(data={"token": channel_token}),
                )
            )
        except AlreadyExists:
            pass
        channel = ContactChannel(
            metadata=ObjectMeta(name=channel_name, namespace=ns),
            spec=ContactChannelSpec(
                type="slack",
                channel_api_key_from=SecretKeyRef(name=secret_name, key="token"),
                channel_id=payload.get("channel_id", "C0000000000"),
                slack=SlackChannelConfig(
                    channel_or_user_id=payload.get("channel_id", "C0000000000")
                ),
            ),
        )
        try:
            ch = self.store.create(channel)
            ch.status.ready = True
            ch.status.status = "Ready"
            ch.status.status_detail = "v1beta3 channel (per-event token)"
            self.store.update_status(ch)
        except AlreadyExists:
            pass
        task = Task(
            metadata=ObjectMeta(
                name=f"{agent_name}-task-{generate_k8s_random_string(8)}",
                namespace=ns,
                labels={LABEL_AGENT: agent_name, LABEL_V1BETA3: "true"},
            ),
            spec=TaskSpec(
                agent_ref=LocalObjectRef(name=agent_name),
                user_message=message,
                contact_channel_ref=LocalObjectRef(name=channel_name),
                channel_token_from=SecretKeyRef(name=secret_name, key="token"),
                thread_id=thread_id,
            ),
        )
        created = self.store.create(task)
        return web.json_response({"taskName": created.name, "channel": channel_name}, status=201)

    # -- generic resources (kubectl-equivalent; no single reference file,
    #    spans the reference's kubectl+CRD UX) ----------------------------

    async def apply_manifests(self, request: web.Request) -> web.Response:
        from ..api.manifests import apply_resources, load_manifests

        try:
            resources = load_manifests((await request.read()).decode())
        except Exception as e:  # yaml errors surface as Invalid-ish
            return _json_error(400, str(e))
        try:
            results = apply_resources(self.store, resources)
        except Invalid as e:
            return _json_error(400, str(e))
        except Exception as e:
            return _json_error(500, f"apply failed: {e}")
        return web.json_response(
            [
                {"kind": r.kind, "name": r.metadata.name, "action": action}
                for action, r in results
            ]
        )

    async def list_resources(self, request: web.Request) -> web.Response:
        from ..api.manifests import resource_to_manifest
        from ..api.resources import KINDS

        kind = request.match_info["kind"]
        if kind not in KINDS:
            return _json_error(404, f"unknown kind {kind!r}")
        ns = request.query.get("namespace", "default")
        selector = None
        if request.query.get("labelSelector"):
            selector = dict(
                part.split("=", 1)
                for part in request.query["labelSelector"].split(",")
                if "=" in part
            )
        objs = self.store.list(kind, ns, label_selector=selector)
        return web.json_response([_redact_secrets(resource_to_manifest(o)) for o in objs])

    async def get_resource(self, request: web.Request) -> web.Response:
        from ..api.manifests import resource_to_manifest
        from ..api.resources import KINDS

        kind = request.match_info["kind"]
        if kind not in KINDS:
            return _json_error(404, f"unknown kind {kind!r}")
        ns = request.query.get("namespace", "default")
        obj = self.store.try_get(kind, request.match_info["name"], ns)
        if obj is None:
            return _json_error(404, "not found")
        return web.json_response(_redact_secrets(resource_to_manifest(obj)))

    async def delete_resource(self, request: web.Request) -> web.Response:
        from ..api.resources import KINDS

        kind = request.match_info["kind"]
        if kind not in KINDS:
            return _json_error(404, f"unknown kind {kind!r}")
        ns = request.query.get("namespace", "default")
        try:
            self.store.delete(kind, request.match_info["name"], ns)
        except NotFound:
            return _json_error(404, "not found")
        return web.json_response({"deleted": request.match_info["name"]})

    # -- in-tree human interaction (no reference analogue) ----------------

    async def list_approvals(self, request: web.Request) -> web.Response:
        b = self.operator.human_backend
        return web.json_response(
            [
                {
                    "callId": a.call_id,
                    "runId": a.run_id,
                    "fn": a.fn,
                    "kwargs": a.kwargs,
                    "created": a.created,
                }
                for a in b.pending_approvals()
            ]
        )

    async def approve(self, request: web.Request) -> web.Response:
        return self._verdict(request, True)

    async def reject(self, request: web.Request) -> web.Response:
        return self._verdict(request, False)

    def _verdict(self, request: web.Request, approve: bool) -> web.Response:
        call_id = request.match_info["call_id"]
        comment = request.query.get("comment", "")
        b = self.operator.human_backend
        if call_id not in b.approvals:
            return _json_error(404, "approval not found")
        (b.approve if approve else b.reject)(call_id, comment)
        return web.json_response({"callId": call_id, "approved": approve})

    async def list_contacts(self, request: web.Request) -> web.Response:
        b = self.operator.human_backend
        return web.json_response(
            [
                {"callId": c.call_id, "runId": c.run_id, "message": c.message, "created": c.created}
                for c in b.pending_contacts()
            ]
        )

    async def respond(self, request: web.Request) -> web.Response:
        call_id = request.match_info["call_id"]
        b = self.operator.human_backend
        if call_id not in b.contacts:
            return _json_error(404, "contact not found")
        try:
            body = json.loads(await request.read())
        except json.JSONDecodeError as e:
            return _json_error(400, str(e))
        if not isinstance(body.get("response"), str):
            return _json_error(400, "response (string) is required")
        b.respond(call_id, body["response"])
        return web.json_response({"callId": call_id})

    # -- OpenAI-compatible serving front door (engine-direct; no reference
    #    analogue — lets any OpenAI client target the TPU engine) ---------

    async def chat_completions(self, request: web.Request) -> web.Response:
        import asyncio as _asyncio
        import time as _time
        import uuid as _uuid

        # the fleet router (when configured) IS the serving engine for the
        # chat paths — same submit surface, pool-wide routing behind it
        engine = getattr(self.operator, "fleet", None) or self.operator.engine
        if engine is None:
            return _json_error(503, "no TPU engine configured (run with --tpu-preset/--tpu-checkpoint)")
        from ..engine.engine import SamplingParams
        from ..engine.tokenizer import render_prompt
        from ..engine.toolparse import to_message
        from ..llmclient.base import Tool, ToolFunction
        from ..api.resources import MessageToolCall, ToolCallFunction

        # one broad parse block: ANY malformed client input is a 400
        try:
            body = json.loads(await request.read())
            if not isinstance(body, dict):
                raise ValueError("request body must be a JSON object")
            messages = [
                Message(
                    role=m["role"],
                    content=m.get("content") or "",
                    tool_call_id=m.get("tool_call_id"),
                    tool_calls=[
                        MessageToolCall(
                            id=tc.get("id", f"call_{i}"),
                            function=ToolCallFunction(
                                name=tc["function"]["name"],
                                arguments=tc["function"].get("arguments") or "{}",
                            ),
                        )
                        for i, tc in enumerate(m.get("tool_calls") or [])
                    ],
                )
                for m in body["messages"]
            ]
            tools = [
                Tool(
                    function=ToolFunction(
                        name=t["function"]["name"],
                        description=t["function"].get("description", ""),
                        parameters=t["function"].get("parameters") or {},
                    )
                )
                for t in body.get("tools") or []
            ]
            json_only = (body.get("response_format") or {}).get("type") == "json_object"
            # OpenAI tool_choice: "required"/{"type": "function", ...} force
            # a parseable call exactly like LLM.spec tool_choice does for
            # the task controller — teacher-forced envelope + grammar
            # constraint (engine/client.py forced_call_prefix)
            from ..engine.client import forced_call_prefix

            tool_choice = body.get("tool_choice")
            if isinstance(tool_choice, dict):
                tool_choice = (tool_choice.get("function") or {}).get("name") or ""
            tool_choice = str(tool_choice or "auto")
            forced = forced_call_prefix(engine.tokenizer, tools, tool_choice)
            json_required = tool_choice == "required" and bool(tools)
            sampling = SamplingParams(
                temperature=float(body.get("temperature") or 0.0),
                top_p=float(body["top_p"]) if body.get("top_p") is not None else 1.0,
                max_tokens=int(body.get("max_tokens") or 512),
                json_only=json_only or bool(forced) or json_required,
                forced_prefix=forced,
            )
            # per-request generation deadline (replaces the old hard-coded
            # 600s): propagated into the engine's admission queue, so a
            # request that expires while QUEUED fails fast without prefill
            timeout_s = min(3600.0, max(1.0, float(body.get("timeout_s") or 600.0)))
            # render here too: a client-supplied assistant history message
            # with unparseable tool_calls[].function.arguments is malformed
            # *client* input and must 400, not 500
            prompt = render_prompt(messages, tools)
            stream = bool(body.get("stream"))
        except Exception as e:
            return _json_error(400, f"invalid request: {e}")

        # crash recovery before admission; off the event loop (KV rebuild
        # jit-compiles and allocates HBM). False = deliberately stopped.
        if not await asyncio.to_thread(engine.ensure_running):
            return _json_error(503, "TPU engine is stopped")
        # fleet routing: name the conversation's persona so every turn of
        # this agent lands on the replica holding its prefix hot
        submit_extra = {}
        if getattr(engine, "supports_affinity", False):
            from ..fleet.router import persona_affinity_key

            submit_extra["affinity_key"] = persona_affinity_key(messages)
        if stream:
            return await self._stream_chat(
                request, engine, prompt, sampling, tools, body, timeout_s,
                submit_extra=submit_extra,
            )

        from ..engine.engine import DeadlineExceededError, EngineOverloadedError

        fut = engine.submit(prompt, sampling, timeout_s=timeout_s, **submit_extra)
        try:
            result = await _asyncio.wait_for(
                _asyncio.wrap_future(fut), timeout=timeout_s
            )
        except _asyncio.TimeoutError:
            engine.cancel(fut)  # free the slot; don't decode for a gone caller
            return _json_error(504, "generation timed out")
        except _asyncio.CancelledError:
            engine.cancel(fut)  # client disconnected mid-generation
            raise
        except EngineOverloadedError as e:
            # load shedding, never an unbounded queue wait
            return _overloaded_response(e)
        except DeadlineExceededError as e:
            return _json_error(504, str(e))
        except Exception as e:
            return _json_error(500, f"generation failed: {e}")

        allowed = {t.function.name for t in tools} if tools else None
        msg = to_message(result.text, allowed)
        out_msg: dict[str, Any] = {"role": "assistant", "content": msg.content or None}
        if msg.tool_calls:
            out_msg["tool_calls"] = [
                {
                    "id": tc.id,
                    "type": "function",
                    "function": {
                        "name": tc.function.name,
                        "arguments": tc.function.arguments,
                    },
                }
                for tc in msg.tool_calls
            ]
        return web.json_response(
            {
                "id": f"chatcmpl-{_uuid.uuid4().hex[:24]}",
                "object": "chat.completion",
                "created": int(_time.time()),
                "model": body.get("model") or "tpu",
                "choices": [
                    {
                        "index": 0,
                        "message": out_msg,
                        "finish_reason": "tool_calls" if msg.tool_calls else (
                            "length" if result.finish_reason == "length" else "stop"
                        ),
                    }
                ],
                "usage": {
                    "prompt_tokens": result.prompt_tokens,
                    "completion_tokens": len(result.tokens),
                    "total_tokens": result.prompt_tokens + len(result.tokens),
                },
            }
        )

    async def _stream_chat(self, request, engine, prompt, sampling, tools, body,
                           timeout_s: float = 600.0, submit_extra=None):
        """SSE streaming (OpenAI chat.completion.chunk wire format): token
        deltas flow from the engine thread per decode block. With tools, the
        engine stream-parses the completion and each call is emitted as a
        ``tool_calls`` delta chunk the moment its arguments close — while
        the model is still decoding — so agent clients can start executing
        early (overlapped tool execution); the finish chunk follows once
        generation ends. Calls the final batch parse finds beyond the
        streamed ones are flushed as trailing deltas before the finish
        chunk, so accumulate-by-index clients always end with the full
        set."""
        import asyncio as _asyncio
        import time as _time
        import uuid as _uuid

        from ..engine.engine import EngineOverloadedError
        from ..engine.toolparse import to_message

        loop = _asyncio.get_running_loop()
        q: _asyncio.Queue = _asyncio.Queue()
        allowed = {t.function.name for t in tools} if tools else None

        def _on_tool_call(_idx, tc):
            if allowed is not None and tc.function.name not in allowed:
                return
            loop.call_soon_threadsafe(q.put_nowait, ("tool_call", tc))

        fut = engine.submit(
            prompt, sampling,
            on_tokens=lambda ids: loop.call_soon_threadsafe(q.put_nowait, list(ids)),
            on_tool_call=_on_tool_call if tools else None,
            timeout_s=timeout_s,
            **(submit_extra or {}),
        )
        if fut.done() and isinstance(fut.exception(), EngineOverloadedError):
            # shed before the stream opened: a plain 503 the client can
            # retry (no SSE preamble has been written yet)
            return _overloaded_response(fut.exception())
        resp = web.StreamResponse(
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "Connection": "keep-alive",
            }
        )
        await resp.prepare(request)
        cid = f"chatcmpl-{_uuid.uuid4().hex[:24]}"
        created = int(_time.time())
        model = body.get("model") or "tpu"

        def chunk(delta: dict, finish: Optional[str] = None) -> bytes:
            doc = {
                "id": cid,
                "object": "chat.completion.chunk",
                "created": created,
                "model": model,
                "choices": [{"index": 0, "delta": delta, "finish_reason": finish}],
            }
            return f"data: {json.dumps(doc)}\n\n".encode()

        pending: list[int] = []  # ids not yet emitted (decode is O(block))
        sent = 0  # chars already streamed
        timed_out = False
        deadline = _time.monotonic() + timeout_s
        # with tools offered the final message is EITHER content OR
        # tool_calls (matching the non-streamed path): buffer instead of
        # streaming raw tool-call JSON as content deltas
        buffer_mode = bool(tools)
        streamed_calls: list = []  # tool calls already sent as deltas

        def tool_chunk(calls, base: int) -> bytes:
            return chunk({
                "tool_calls": [
                    {
                        "index": base + i,
                        "id": tc.id,
                        "type": "function",
                        "function": {
                            "name": tc.function.name,
                            "arguments": tc.function.arguments,
                        },
                    }
                    for i, tc in enumerate(calls)
                ]
            })

        async def error_event(message: str, etype: str) -> None:
            # OpenAI-style streamed error event; no [DONE] after an error
            await resp.write(
                f'data: {json.dumps({"error": {"message": message, "type": etype}})}\n\n'.encode()
            )

        try:
            await resp.write(chunk({"role": "assistant"}))
            while not fut.done() or not q.empty():
                if _time.monotonic() > deadline:
                    engine.cancel(fut)
                    timed_out = True
                    break
                try:
                    ids = await _asyncio.wait_for(q.get(), timeout=0.1)
                except _asyncio.TimeoutError:
                    continue
                if isinstance(ids, tuple) and ids and ids[0] == "tool_call":
                    # early tool-call delta: the call's arguments closed in
                    # the decode stream; flush it NOW so the client can
                    # dispatch while the model keeps generating
                    tc = ids[1]
                    await resp.write(tool_chunk([tc], len(streamed_calls)))
                    streamed_calls.append(tc)
                    continue
                pending.extend(ids)
                if buffer_mode:
                    continue
                text = engine.tokenizer.decode(pending)
                if text.endswith("�"):
                    continue  # partial multi-byte char at a block edge
                if text:
                    await resp.write(chunk({"content": text}))
                    sent += len(text)
                pending.clear()
            if timed_out:
                await error_event("generation timed out", "timeout")
                await resp.write_eof()
                return resp
            try:
                # the loop exits when fut is done (or on timeout, handled
                # above); the residual wait only covers the done-callback
                # race, bounded by what's left of the request's own budget
                result = fut.result(
                    timeout=max(1.0, min(30.0, deadline - _time.monotonic()))
                )
            except Exception as e:
                await error_event(f"generation failed: {e}", "server_error")
                await resp.write_eof()
                return resp
            finish = "length" if result.finish_reason == "length" else "stop"
            msg = to_message(result.text, allowed)
            # the batch parse is authoritative (it is what the non-streamed
            # endpoint returns): if it yields NO calls, the content flows
            # and finish stays stop/length even when degenerate output made
            # the stream emit speculative deltas
            if not (buffer_mode and msg.tool_calls):
                # authoritative final flush: result.text covers tokens whose
                # queue callback raced the loop exit and held-back chars;
                # in buffer mode this is the whole (non-tool-call) content
                delta = result.text[sent:]
                if delta:
                    await resp.write(chunk({"content": delta}))
            if msg.tool_calls:
                # dedupe against the early deltas: the streamed prefix that
                # positionally matches the batch parse was already sent;
                # flush only the remainder. (A divergent stream — possible
                # only for degenerate mixed fenced/bare output — appends
                # the definitive set after the streamed indices so an
                # accumulate-by-index client still ends with every real
                # call.)
                matched = 0
                for tc in msg.tool_calls:
                    if matched >= len(streamed_calls):
                        break
                    s = streamed_calls[matched]
                    if (
                        s.function.name == tc.function.name
                        and s.function.arguments == tc.function.arguments
                    ):
                        matched += 1
                    else:
                        break
                rest_calls = (
                    msg.tool_calls[matched:]
                    if matched == len(streamed_calls)
                    else msg.tool_calls
                )
                if rest_calls:
                    await resp.write(tool_chunk(rest_calls, len(streamed_calls)))
                finish = "tool_calls"
            final = {
                "id": cid,
                "object": "chat.completion.chunk",
                "created": created,
                "model": model,
                "choices": [{"index": 0, "delta": {}, "finish_reason": finish}],
                # usage on the final chunk (OpenAI stream_options parity)
                "usage": {
                    "prompt_tokens": result.prompt_tokens,
                    "completion_tokens": len(result.tokens),
                    "total_tokens": result.prompt_tokens + len(result.tokens),
                },
            }
            await resp.write(f"data: {json.dumps(final)}\n\n".encode())
            await resp.write(b"data: [DONE]\n\n")
        except (ConnectionResetError, _asyncio.CancelledError):
            engine.cancel(fut)  # client went away mid-stream
            raise
        await resp.write_eof()
        return resp

    # -- observability ----------------------------------------------------

    async def list_events(self, request: web.Request) -> web.Response:
        ns = request.query.get("namespace", "default")
        events = self.store.list("Event", ns)
        return web.json_response(
            [
                {
                    "involved": f"{e.spec.involved_kind}/{e.spec.involved_name}",
                    "type": e.spec.type,
                    "reason": e.spec.reason,
                    "message": e.spec.message,
                    "count": e.spec.count,
                    "lastTimestamp": e.spec.last_timestamp,
                }
                for e in events
            ]
        )

    async def list_models(self, request: web.Request) -> web.Response:
        """OpenAI-compatible model listing: the engine's model (when
        configured) plus every LLM resource with its readiness flag."""
        import time as _time

        models = []
        engine = self.operator.engine
        if engine is not None:
            dims = engine.stats()["model"]
            models.append(
                {
                    "id": "tpu",
                    "object": "model",
                    "created": int(_time.time()),
                    "owned_by": "acp-tpu",
                    "metadata": dims,
                }
            )
        for llm in self.store.list("LLM", request.query.get("namespace", "default")):
            models.append(
                {
                    "id": llm.metadata.name,
                    "object": "model",
                    "created": int(_time.time()),
                    "owned_by": llm.spec.provider,
                    "ready": llm.status.ready,
                }
            )
        return web.json_response({"object": "list", "data": models})

    async def engine_status(self, request: web.Request) -> web.Response:
        engine = self.operator.engine
        if engine is None:
            return web.json_response({"configured": False})
        return web.json_response({"configured": True, **engine.stats()})

    async def engine_perf(self, request: web.Request) -> web.Response:
        """Compute efficiency observatory: per-program dispatch telemetry
        (host/device time, real-vs-padded tokens), the cold-compile
        observatory, and the goodput/waste ledger. The profiler's stats()
        is its declared cross-thread read surface (same contract as the
        flight recorder's read methods)."""
        engine = self.operator.engine
        if engine is None:
            return _json_error(503, "no TPU engine configured")
        return web.json_response({"configured": True, **engine.profiler.stats()})

    async def engine_flight(self, request: web.Request) -> web.Response:
        """Flight-recorder window (token-authed like every non-health
        route): the engine's recent scheduler decisions, last-N filterable
        by event kind and/or request id. The recorder's read methods are
        its cross-thread surface (they take the recorder lock)."""
        engine = self.operator.engine
        if engine is None:
            return _json_error(503, "no TPU engine configured")
        try:
            last = int(request.query.get("last", "200"))
        except ValueError:
            return _json_error(400, "last must be an integer")
        flight = engine.flight
        return web.json_response({
            **flight.stats(),
            "request_ids": flight.request_ids(),
            "events": flight.events(
                last=last,
                kind=request.query.get("kind") or None,
                rid=request.query.get("rid") or None,
            ),
        })

    async def engine_trace(self, request: web.Request) -> web.Response:
        """Anonymized replayable workload trace derived from the flight
        recorder (observability/trace_export.py): arrival offsets, token
        lengths, persona mix, tool-call offsets, deadlines/cancels — no
        content. Token-authed like every non-health route; the export walks
        the recorder's declared cross-thread read surface only."""
        engine = self.operator.engine
        if engine is None:
            return _json_error(503, "no TPU engine configured")
        from ..observability.trace_export import export_trace

        return web.json_response(export_trace(engine.flight))

    async def fleet_trace(self, request: web.Request) -> web.Response:
        """Fleet-wide trace: one row per ROUTER request, stitched across
        the router's recorder and every replica-local leg it linked, so
        handoff/failover traffic appears as one timeline with queue_wait
        counted once."""
        fleet = getattr(self.operator, "fleet", None)
        if fleet is None:
            return _json_error(
                503, "no fleet router configured (single-engine deployment)"
            )
        from ..observability.trace_export import export_fleet_trace

        return web.json_response(export_fleet_trace(fleet))

    async def fleet_status(self, request: web.Request) -> web.Response:
        """Pool status: per-replica row (role, liveness, lease holder +
        fencing epoch, queue depth, goodput, homed affinity keys) plus the
        router's routing/failover/handoff counters. stats() is the
        router's declared cross-thread read surface, same contract as
        Engine.stats()."""
        fleet = getattr(self.operator, "fleet", None)
        if fleet is None:
            return _json_error(
                503, "no fleet router configured (single-engine deployment)"
            )
        return web.json_response({"configured": True, **fleet.stats()})

    async def request_timeline(self, request: web.Request) -> web.Response:
        """One request's full lifecycle: every recorded scheduler decision
        in monotonic order, plus the derived phase attribution
        (queue_wait | prefill | decode | preempt_stall |
        tool_overlap_hidden) whose durations sum to ~end-to-end latency."""
        engine = self.operator.engine
        if engine is None:
            return _json_error(503, "no TPU engine configured")
        doc = engine.flight.timeline_doc(request.match_info["rid"])
        if doc is None:
            return _json_error(
                404,
                "unknown request id (never recorded, or its timeline aged "
                "out of the finished-request window)",
            )
        return web.json_response(doc)

    async def metrics(self, request: web.Request) -> web.Response:
        self._update_phase_gauges()
        return web.Response(text=REGISTRY.render(), content_type="text/plain")

    def _update_phase_gauges(self) -> None:
        """Object counts by kind+phase, computed at scrape time (the store is
        the source of truth; a cached gauge would drift across restarts).
        Powers the task/toolcall phase panels in the observability stack
        (deploy/observability/) — the equivalent of the reference's
        kube-state-metrics CR phase view."""
        try:
            counts = self.store.phase_counts()
        except Exception:
            return  # transient store failure: keep last scrape's values
        # Drained-series lifecycle (cardinality hygiene): a series that
        # existed last scrape but is empty now is zeroed for exactly ONE
        # scrape (so dashboards see the drain, not a frozen last value),
        # then removed from the registry. Accumulating every (kind, phase)
        # pair ever observed would re-emit unbounded zeros forever.
        live = set(counts.keys())
        prev: set[tuple[str, str]] = getattr(self, "_phase_series", set())
        zeroed_last: set[tuple[str, str]] = getattr(self, "_phase_zeroed", set())
        for kind, phase in zeroed_last - live:
            REGISTRY.gauge_remove("acp_objects", labels={"kind": kind, "phase": phase})
        to_zero = prev - live
        for key in to_zero:
            counts[key] = 0
        self._phase_series = live
        self._phase_zeroed = to_zero
        for (kind, phase), n in counts.items():
            REGISTRY.gauge_set(
                "acp_objects",
                float(n),
                labels={"kind": kind, "phase": phase},
                help="live objects by kind and phase",
            )
        # engine occupancy/queue-depth refreshed at scrape time too: the
        # engine loop only updates them per decode step, which reads stale
        # during admission hold (prewarm) and before the first dispatch
        engine = getattr(self.operator.options, "engine", None)
        if engine is not None:
            try:
                s = engine.stats()
                REGISTRY.gauge_set(
                    "acp_engine_active_slots", float(s["active_slots"]),
                    help="occupied decode slots",
                )
                REGISTRY.gauge_set(
                    "acp_engine_waiting_requests", float(s["waiting"]),
                    help="admission queue depth",
                )
                REGISTRY.gauge_set(
                    "acp_engine_tokens_per_decode_step",
                    float(s.get("tokens_per_decode_step", 0.0)),
                    help="mean tokens committed per decode model step "
                    "(> 1 means speculative decoding is paying)",
                )
                drafter = s.get("drafter")  # a family that drafts by itself (models/exaone.py)
                if drafter is not None:
                    REGISTRY.gauge_set(
                        "acp_engine_spec_self_proposed", float(drafter["proposed"]),
                        help="drafts the model's own drafter put to live lanes (device counter)",
                    )
                    REGISTRY.gauge_set(
                        "acp_engine_spec_self_accepted", float(drafter["accepted"]),
                        help="drafts of the model's own drafter the verify step kept (device counter)",
                    )
                REGISTRY.gauge_set(
                    "acp_engine_prefilling_slots",
                    float(s.get("prefilling_slots", 0)),
                    help="slots admitted but still mid-prefill under the "
                    "chunked token-budget scheduler",
                )
                sched = s.get("scheduler", {})
                REGISTRY.gauge_set(
                    "acp_engine_token_budget_utilization",
                    float(sched.get("budget_utilization_last", 0.0)),
                    help="tokens dispatched last scheduler cycle / "
                    "per-cycle token budget (chunked prefill mode)",
                )
                # KV memory tiers: host-pool occupancy + dedup'd pages,
                # refreshed at scrape time so an idle engine (no dispatch
                # cycles) still reports current tier state
                mem = s.get("memory", {})
                REGISTRY.gauge_set(
                    "acp_engine_host_kv_bytes",
                    float(mem.get("host_kv", {}).get("used_bytes", 0)),
                    help="bytes of swapped-out KV resident in the "
                    "host-RAM offload tier (bounded by "
                    "--tpu-host-kv-bytes)",
                )
                REGISTRY.gauge_set(
                    "acp_engine_prefix_shared_pages",
                    float(mem.get("prefix_dedup", {}).get("shared_pages", 0)),
                    help="HBM KV pages currently refcount-shared by more "
                    "than one owner (cross-request shared-prefix dedup + "
                    "prefix cache)",
                )
                # compute efficiency observatory: no re-set needed here —
                # the stats() call above ran profiler.stats(), whose
                # publish() already refreshed acp_engine_goodput_ratio and
                # the ledger counters from the same snapshot this scrape
                # serves
            except Exception:
                pass  # a crashed engine must not take /metrics down
        # fleet gauges refreshed from the router's declared stats() surface
        # at scrape time, same contract as the engine block above: the pool
        # only republishes acp_fleet_replicas on membership edges, which
        # reads stale between a silent replica death and the next heartbeat
        fleet = getattr(self.operator, "fleet", None)
        if fleet is not None:
            try:
                fs = fleet.stats()
                routing = fs.get("routing") or {}
                rows = fs.get("replicas") or []
                REGISTRY.gauge_set(
                    "acp_fleet_replicas",
                    float(sum(1 for r in rows if r.get("alive"))),
                    help="live engine replicas registered in the fleet pool "
                    "(lease-backed membership; a crashed or deposed replica "
                    "drops out on mark_dead)",
                )
                REGISTRY.gauge_set(
                    "acp_fleet_inflight", float(routing.get("inflight", 0)),
                    help="router submissions alive across the pool (not yet "
                    "resolved, failed over, or shed)",
                )
                REGISTRY.gauge_set(
                    "acp_fleet_affinity_keys",
                    float(routing.get("affinity_keys", 0)),
                    help="distinct persona/prefix affinity keys currently "
                    "homed to a replica by the cache-affinity router",
                )
                REGISTRY.gauge_set(
                    "acp_fleet_queue_depth",
                    float(sum(r.get("queue_depth") or 0 for r in rows)),
                    help="admission-queue depth summed across live fleet "
                    "replicas (pool-wide backpressure signal)",
                )
            except Exception:
                pass  # a sick router must not take /metrics down

    async def healthz(self, request: web.Request) -> web.Response:
        return web.json_response({"status": "ok"})
