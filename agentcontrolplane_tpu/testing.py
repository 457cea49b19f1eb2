"""Builder fixtures per kind, with setup / setup_with_status / teardown —
plus the deterministic fault-injection seam (:data:`FAULTS`).

Mirrors the reference's ``test/utils/*.go`` (SURVEY.md §4): the universal
trick is ``setup_with_status`` — write status directly through the status
subresource so a test can fabricate "LLM is Ready" without live API keys.

Fault injection
---------------

``FAULTS`` lives in :mod:`agentcontrolplane_tpu.faults` (a dependency-free
module so the engine can import it without this fixture surface) and is
re-exported here for test convenience — see that module's docstring for
the site catalogue and determinism contract.

A model family's test file
--------------------------

Its reference is :func:`greedy_reference` (the family's own ``forward``, no
cache, over one row padded to the file's ``max_ctx``: compiled once a file,
whatever the prompts' lengths), and ``tests/engine/test_reference_padding.py``
holds every family's forward to the property that rests on: what lies to
the right of a position never reaches it. A model program a case calls
itself goes through :func:`compiled`, as the engine compiles it. Its engines
are built a case: nearly every engine case of the five family files sets
options of its own, counts from zero, preempts or parks (6 to 16 s a case
once the reference is compiled: PR 50); cases that share one set of options
and only serve and read share a ``scope="module"`` engine, stopped at the
end (``tests/engine/test_shape_invariance.py``'s ``Family``). A file stays
under 300 s of the driver's junit file (``ROADMAP.md``, "a family
file's budget").
"""

from __future__ import annotations

import functools

from agentcontrolplane_tpu.api import ObjectMeta
from agentcontrolplane_tpu.api.resources import (
    Agent,
    AgentSpec,
    BaseConfig,
    ContactChannel,
    ContactChannelSpec,
    EmailChannelConfig,
    LLM,
    LLMSpec,
    LocalObjectRef,
    MCPServer,
    MCPServerSpec,
    MCPTool,
    Message,
    Secret,
    SecretKeyRef,
    SecretSpec,
    Task,
    TaskSpec,
    ToolCall,
    ToolCallSpec,
)
from agentcontrolplane_tpu.kernel import NotFound, Store


def setup_with_status(store: Store, obj, status_mutator=None):
    created = store.create(obj)
    if status_mutator is not None:
        status_mutator(created)
        created = store.update_status(created)
    return created


def teardown(store: Store, obj) -> None:
    try:
        store.delete(obj.kind, obj.metadata.name, obj.metadata.namespace)
    except NotFound:
        pass


def make_secret(store: Store, name="test-secret", data=None) -> Secret:
    return store.create(
        Secret(
            metadata=ObjectMeta(name=name),
            spec=SecretSpec(data=data or {"api-key": "sk-test-123"}),
        )
    )


def make_llm(store: Store, name="test-llm", provider="mock", ready=True, **kwargs) -> LLM:
    spec = LLMSpec(
        provider=provider,
        api_key_from=SecretKeyRef(name="test-secret", key="api-key")
        if provider in ("openai", "anthropic", "mistral", "google")
        else None,
        parameters=BaseConfig(model=kwargs.pop("model", "test-model")),
        **kwargs,
    )
    def mark_ready(o):
        o.status.ready = True
        o.status.status = "Ready"
    return setup_with_status(
        store, LLM(metadata=ObjectMeta(name=name), spec=spec), mark_ready if ready else None
    )


def make_agent(
    store: Store,
    name="test-agent",
    llm="test-llm",
    system="you are a helpful assistant",
    ready=True,
    mcp_servers=(),
    channels=(),
    sub_agents=(),
    resolved_tools=None,
    description="",
) -> Agent:
    spec = AgentSpec(
        llm_ref=LocalObjectRef(name=llm),
        system=system,
        description=description,
        mcp_servers=[LocalObjectRef(name=s) for s in mcp_servers],
        human_contact_channels=[LocalObjectRef(name=c) for c in channels],
        sub_agents=[LocalObjectRef(name=a) for a in sub_agents],
    )

    def mark_ready(o):
        o.status.ready = True
        o.status.status = "Ready"
        from agentcontrolplane_tpu.api.resources import ResolvedMCPServer, ResolvedSubAgent

        o.status.valid_mcp_servers = [
            ResolvedMCPServer(name=s, tools=(resolved_tools or {}).get(s, []))
            for s in mcp_servers
        ]
        o.status.valid_human_contact_channels = list(channels)
        o.status.valid_sub_agents = [ResolvedSubAgent(name=a) for a in sub_agents]

    return setup_with_status(
        store, Agent(metadata=ObjectMeta(name=name), spec=spec), mark_ready if ready else None
    )


def make_task(
    store: Store,
    name="test-task",
    agent="test-agent",
    user_message="what is the capital of france?",
    context_window=None,
    labels=None,
    **kwargs,
) -> Task:
    return store.create(
        Task(
            metadata=ObjectMeta(name=name, labels=labels or {}),
            spec=TaskSpec(
                agent_ref=LocalObjectRef(name=agent),
                user_message=user_message,
                context_window=context_window,
                **kwargs,
            ),
        )
    )


def make_toolcall(
    store: Store,
    name="test-task-abc1234-tc-01",
    task="test-task",
    tool="fetch__fetch",
    tool_type="MCP",
    arguments='{"url": "https://example.com"}',
    labels=None,
    owner=None,
) -> ToolCall:
    meta = ObjectMeta(name=name, labels=labels or {})
    if owner is not None:
        meta.owner_references = [owner.owner_ref()]
    return store.create(
        ToolCall(
            metadata=meta,
            spec=ToolCallSpec(
                tool_call_id="call_1",
                task_ref=LocalObjectRef(name=task),
                tool_ref=LocalObjectRef(name=tool),
                tool_type=tool_type,
                arguments=arguments,
            ),
        )
    )


def make_mcpserver(store: Store, name="fetch", connected=True, tools=("fetch",), approval_channel=None) -> MCPServer:
    def mark_connected(o):
        o.status.connected = True
        o.status.status = "Ready"
        o.status.tools = [MCPTool(name=t, description=f"{t} tool") for t in tools]

    return setup_with_status(
        store,
        MCPServer(
            metadata=ObjectMeta(name=name),
            spec=MCPServerSpec(
                transport="stdio",
                command="echo",
                approval_contact_channel=approval_channel,
            ),
        ),
        mark_connected if connected else None,
    )


def make_contactchannel(store: Store, name="approval-channel", ready=True) -> ContactChannel:
    def mark_ready(o):
        o.status.ready = True
        o.status.status = "Ready"

    return setup_with_status(
        store,
        ContactChannel(
            metadata=ObjectMeta(name=name),
            spec=ContactChannelSpec(
                type="email",
                api_key_from=SecretKeyRef(name="test-secret", key="api-key"),
                email=EmailChannelConfig(address="human@example.com"),
            ),
        ),
        mark_ready if ready else None,
    )


# ---------------------------------------------------------------------------
# The tests' greedy reference (jax is imported when it is first asked for)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def compiled(fn, config):
    """``jax.jit`` of a model program with its ``config`` bound, made once a
    (program, config): the calls of one test file share what it compiles."""
    import jax

    return jax.jit(functools.partial(fn, config=config))


def padded_logits(forward, params, config, tokens, width: int):
    """``forward``'s logits ``[len(tokens), vocab]`` for one row of tokens,
    run at ``width`` (the tail is token 0) and cut back: every length shares
    the one program ``jax.jit`` makes for (``forward``, ``config``, ``width``).
    Right for a forward whose row ``i`` reads rows ``<= i`` alone."""
    import numpy as np

    if len(tokens) > width:
        raise ValueError(f"{len(tokens)} tokens do not fit a row of {width}")
    row = np.zeros((1, width), np.int32)
    row[0, : len(tokens)] = tokens
    return np.asarray(compiled(forward, config)(params, row))[0, : len(tokens)]


def greedy_reference(forward, params, config, prompt, n: int, width: int) -> list[int]:
    """The ``n`` tokens greedy decoding gives after ``prompt`` by the family's
    plain ``forward(params, tokens, config)``: no cache, no state, nothing of
    the engine. Each token is the argmax at the last real row of
    :func:`padded_logits`."""
    toks = [int(t) for t in prompt]
    for _ in range(n):
        toks.append(int(padded_logits(forward, params, config, toks, width)[-1].argmax()))
    return toks[len(prompt):]


# ---------------------------------------------------------------------------
# Fault injection — re-exported from the dependency-free faults module
# ---------------------------------------------------------------------------

from agentcontrolplane_tpu.faults import FAULTS, FaultInjector  # noqa: E402,F401
