"""acp-tpu CLI: run the operator; kubectl-style resource management.

The reference's operational surface is kubectl + Makefile/kind
(``Makefile:36-100``, ``acp/config/samples``); standalone TPU-native
operation replaces that with one binary:

  acp-tpu run [--db state.db] [--port 8082] [--leader-elect]
              [--tpu-preset llama3-8b | --tpu-checkpoint /path/to/hf]
  acp-tpu apply -f manifests.yaml [--server URL]
  acp-tpu get <Kind> [name] [-o yaml]
  acp-tpu delete <Kind> <name>
  acp-tpu events
  acp-tpu approvals [approve|reject <call-id> [--comment ...]]
  acp-tpu contacts [respond <call-id> <text>]
  acp-tpu task create <agent> <message> [--follow]
  acp-tpu timeline [request-id]   (engine flight recorder)
  acp-tpu perf                    (compute efficiency observatory)
  acp-tpu trace export [--fleet] [-o trace.json]
  acp-tpu replay trace.json | --scenario NAME [--speed 10] [--gate]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import sys
import threading
import time

log = logging.getLogger("acp_tpu.cli")

DEFAULT_SERVER = os.environ.get("ACP_TPU_SERVER", "http://127.0.0.1:8082")


def _client(args, timeout: float | None = 30.0):
    import httpx

    headers = {}
    token = getattr(args, "token", None) or os.environ.get("ACP_API_TOKEN")
    if token:
        headers["Authorization"] = f"Bearer {token}"
    return httpx.Client(base_url=args.server, timeout=timeout, headers=headers)


def _add_tpu_flags(p) -> None:
    """Engine flags shared by `run` and `engine-follower` (multi-host ranks
    must construct identical engines)."""
    p.add_argument("--tpu-preset", default=None, help="serve a model preset on TPU")
    p.add_argument("--tpu-checkpoint", default=None, help="HF checkpoint dir to serve")
    p.add_argument(
        "--tpu-lora",
        default=None,
        help="LoRA adapter dir (train.lora.save_lora) merged into the checkpoint at load",
    )
    p.add_argument("--tpu-slots", type=int, default=64)
    p.add_argument("--tpu-ctx", type=int, default=2048)
    p.add_argument(
        "--tpu-tp", type=int, default=0,
        help="tensor parallelism (0 = all devices after --tpu-sp/--tpu-ep)",
    )
    p.add_argument(
        "--tpu-sp", type=int, default=1,
        help="context parallelism: shard the KV cache's ctx dim (slot) or "
        "within-page dim (paged) over an 'sp' mesh axis",
    )
    p.add_argument(
        "--tpu-ep", type=int, default=1,
        help="expert parallelism: shard MoE expert stacks over an 'ep' "
        "mesh axis (Mixtral-family presets/checkpoints)",
    )
    p.add_argument("--tpu-kv-layout", choices=["slot", "paged"], default="slot")
    p.add_argument(
        "--tpu-quantize", choices=["int8"], default=None,
        help="legacy spelling of --tpu-quantize-weights",
    )
    p.add_argument(
        "--tpu-quantize-weights", action="store_true",
        help="serve int8 weights (per-output-channel scales, quantized "
        "host-side at checkpoint load so the bf16 copy never reaches the "
        "device): half the weight HBM and ~2x decode bandwidth headroom "
        "(see docs/serving-engine.md 'Serving quantized')",
    )
    p.add_argument(
        "--tpu-quantize-kv", action="store_true",
        help="int8 KV cache with per-row scales (both layouts): a fixed "
        "HBM page/slot budget holds ~2x the tokens, and the host KV tier "
        "+ shared-prefix dedup carry the quantized bytes. Relaxes greedy "
        "byte-identity — outputs are gated by the pinned accuracy fixture "
        "(top-1 agreement + logit-MAE bounds vs bf16; see "
        "docs/serving-engine.md 'Serving quantized')",
    )
    p.add_argument(
        "--tpu-max-queue", type=int, default=0,
        help="admission-queue cap: submissions beyond this many waiting "
        "requests are shed (REST 503 + Retry-After) instead of queueing "
        "unboundedly; 0 = unbounded",
    )
    p.add_argument(
        "--tpu-spec-len", type=int, default=0,
        help="speculative decoding: max draft tokens verified per decode "
        "dispatch via n-gram prompt lookup (greedy outputs stay "
        "byte-identical; see docs/serving-engine.md); 0 = off",
    )
    p.add_argument(
        "--tpu-spec-ngram", type=int, default=3,
        help="longest n-gram the prompt-lookup drafter matches on",
    )
    p.add_argument(
        "--tpu-prefill-chunk", type=int, default=0,
        help="chunked prefill: split every prefill into chunks of at most "
        "this many tokens, co-scheduled with decode under the unified "
        "token-budget scheduler so one long prompt can't head-of-line-block "
        "decoding slots (greedy outputs byte-identical on/off; see "
        "docs/serving-engine.md); 0 = off (whole prefill at admission)",
    )
    p.add_argument(
        "--tpu-token-budget", type=int, default=0,
        help="per-dispatch-cycle token budget the scheduler spends across "
        "prefill chunks + decode + speculative verify; 0 = auto-sized "
        "(decode always dispatches, one chunk per mid-prefill slot rides "
        "along); only meaningful with --tpu-prefill-chunk",
    )
    p.add_argument(
        "--tpu-host-kv-bytes", type=int, default=0,
        help="host-RAM KV offload tier budget in bytes: preemption, park "
        "expiry, and mid-prefill deadline drops swap their written KV to "
        "host RAM and re-admission swaps it back instead of re-running "
        "prefill (greedy outputs byte-identical; see docs/serving-engine.md "
        "'KV memory tiers'); 0 = off (discard and recompute)",
    )
    p.add_argument(
        "--tpu-host-prefetch", type=int, default=1,
        help="async host-KV prefetch (paged layout): stage the NEXT "
        "restore chunk's host->device copies a cycle early so the scatter "
        "commit rides the dispatch window instead of blocking the engine "
        "thread (byte-identical on or off; "
        "acp_engine_kv_prefetch_commits_total counts the overlap); "
        "1 = on (default), 0 = blocking swap-ins",
    )
    p.add_argument(
        "--tpu-prefix-dedup", type=int, default=1,
        help="cross-request shared-prefix page dedup (paged KV layout): "
        "requests whose page-aligned prompt prefix matches a live slot "
        "refcount-share its pages instead of materializing a private copy "
        "— N concurrent tasks on one agent persona hold 1 copy, not N; "
        "0 disables (byte-identical either way)",
    )
    p.add_argument(
        "--tpu-megastep", type=int, default=1,
        help="fused megastep dispatch: a busy chunked cycle's prefill "
        "chunks + final-chunk continuations + decode block (or spec "
        "verify) compile into ONE program, so the steady-state cycle "
        "issues a single device dispatch (greedy outputs byte-identical "
        "on/off; see docs/megastep.md); 0 = the split per-phase dispatches",
    )
    p.add_argument(
        "--tpu-rate-planner", type=int, default=1,
        help="admission-time chunk-rate planner: deadline requests get a "
        "per-cycle chunk quota (tokens remaining / cycles until deadline, "
        "reprojected on preempt-resume and park-adopt) instead of the "
        "flat one-chunk cadence — deadlines met by arithmetic, not EDF "
        "luck (see docs/megastep.md); 0 = flat cadence",
    )
    p.add_argument(
        "--tpu-autopilot", type=int, default=0,
        help="scheduler autopilot: steer --tpu-prefill-chunk / "
        "--tpu-token-budget / --tpu-spec-len one bounded step at a time "
        "from observed phase attribution, budget utilization and "
        "speculative acceptance (see docs/megastep.md); 0 = off",
    )
    p.add_argument(
        "--tpu-park-max-s", type=float, default=30.0,
        help="overlapped tool execution: seconds a slot parked at "
        "generation end (prompt KV resident) waits for the conversation's "
        "next turn before releasing; 0 disables parking "
        "(see docs/serving-engine.md)",
    )


def _kv_pages_from_memory(args) -> int:
    """A preset's paged pool sized from the device's memory where the device
    reports it (0: it does not, as the CPU; the engine's own default stands):
    a page's bytes come from the family's pool itself (``models.page_bytes``),
    so a family whose cache is deeper than its weights is not handed a pool
    several times the chip."""
    import jax

    from .models import kv_pages_that_fit, preset, programs

    limit = (jax.local_devices()[0].memory_stats() or {}).get("bytes_limit", 0)
    if not limit:
        return 0
    config = preset(args.tpu_preset)
    weights = jax.eval_shape(lambda: programs(config).init_params(config, jax.random.key(0)))
    weight_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(weights))
    if args.tpu_quantize_weights or args.tpu_quantize:
        weight_bytes //= 2
    chips = max(1, args.tpu_tp)  # the llama family splits weights and pool alike over tp
    ctx = min(args.tpu_ctx, config.max_seq_len)
    return kv_pages_that_fit(config, args.tpu_slots, ctx, 16, limit * chips, weight_bytes,
                             quantize_kv=args.tpu_quantize_kv)


def _build_engine(args, coordination=None, **engine_kw):
    """Engine construction shared by `run` (leader/single-host) and
    `engine-follower` — multi-host lockstep requires every rank to build
    the IDENTICAL engine (same config/mesh/layout flags). ``engine_kw``
    lets callers layer construction-only knobs the flag surface doesn't
    carry (the chaos drill arms ``check_invariants`` on every replica)."""
    from .engine.engine import Engine
    from .engine.tokenizer import ByteTokenizer, HFTokenizer

    quantize = "int8" if args.tpu_quantize_weights else args.tpu_quantize
    kw = dict(
        max_slots=args.tpu_slots,
        max_ctx=args.tpu_ctx,
        kv_layout=args.tpu_kv_layout,
        quantize=quantize,
        quantize_kv=args.tpu_quantize_kv,
        max_queue=args.tpu_max_queue,
        spec_len=args.tpu_spec_len,
        spec_ngram=args.tpu_spec_ngram,
        park_max_s=args.tpu_park_max_s,
        prefill_chunk=args.tpu_prefill_chunk,
        token_budget=args.tpu_token_budget,
        host_kv_bytes=args.tpu_host_kv_bytes,
        host_prefetch=bool(args.tpu_host_prefetch),
        prefix_dedup=bool(args.tpu_prefix_dedup),
        megastep=bool(args.tpu_megastep),
        rate_planner=bool(args.tpu_rate_planner),
        autopilot=bool(args.tpu_autopilot),
        coordination=coordination,
    )
    kw.update(engine_kw)
    if args.tpu_kv_layout == "paged" and args.tpu_preset and "kv_pages" not in kw:
        pages = _kv_pages_from_memory(args)
        if pages:
            kw["kv_pages"] = pages
    if args.tpu_tp or args.tpu_sp > 1 or args.tpu_ep > 1:
        from .parallel.mesh import serving_mesh

        kw["mesh"] = serving_mesh(args.tpu_tp, args.tpu_sp, args.tpu_ep)
    if args.tpu_checkpoint:
        from .engine.weights import load_safetensors_dir

        # LoRA merge AND quantization both happen host-side at load, in
        # that order — the bf16 (and unmerged) copy of a big model never
        # reaches the device
        params, config = load_safetensors_dir(
            args.tpu_checkpoint,
            quantize=quantize,
            lora_path=args.tpu_lora,
        )
        if args.tpu_lora:
            print(f"merged LoRA adapter from {args.tpu_lora}", flush=True)
        tok_path = os.path.join(args.tpu_checkpoint, "tokenizer.json")
        tokenizer = HFTokenizer(tok_path) if os.path.exists(tok_path) else ByteTokenizer()
        return Engine(config=config, params=params, tokenizer=tokenizer, **kw)
    return Engine(config=args.tpu_preset, tokenizer=ByteTokenizer(), **kw)


class EnginePrewarm(threading.Thread):
    """Compile the serving programs in the background: the REST API comes
    up immediately and early requests simply queue behind the same compiles
    they would have caused.

    A prewarm that raises — a program the chip's compiler refuses, a
    program that does not fit the device — must not die silently while the
    server stays up: the error is kept on ``error``, the engine is stopped
    (requests then get 503, instead of each re-triggering the failure), and
    ``on_failure`` runs (`acp-tpu run` uses it to exit non-zero)."""

    def __init__(self, engine, on_failure=None):
        super().__init__(name="tpu-prewarm", daemon=True)
        self.engine = engine
        self.error: BaseException | None = None
        self._on_failure = on_failure

    def run(self) -> None:
        try:
            self.engine.prewarm(constrained=True)
        except Exception as e:
            log.exception("engine prewarm failed; stopping the engine")
            self.error = e
            self.engine.stop()
            if self._on_failure is not None:
                self._on_failure()


def cmd_engine_follower(args) -> int:
    """A non-zero rank of a multi-host serving cluster: joins the
    jax.distributed runtime, replays rank 0's admission frames, and serves
    until the leader's stop frame. No control plane runs here."""
    from .utils import setup_logging

    setup_logging(os.environ.get("ACP_TPU_LOG_LEVEL", "INFO"))
    from .engine.coordination import CoordinationFollower
    from .parallel.distributed import initialize_distributed, runtime_info

    initialize_distributed()
    import jax as _jax

    if _jax.process_count() > 1 and _jax.process_index() == 0:
        print("error: rank 0 runs `acp-tpu run`, not engine-follower", file=sys.stderr)
        return 2
    from .engine.coordination import client_ssl_context

    ca = os.environ.get("ACP_COORD_TLS_CA", "")
    coordination = CoordinationFollower(
        args.coordinator,
        rank=_jax.process_index(),
        token=os.environ.get("ACP_COORD_TOKEN", "") or None,
        ssl_context=client_ssl_context(ca) if ca else None,
    )
    engine = _build_engine(args, coordination)
    engine.start()
    print(f"engine follower serving: {runtime_info()}", flush=True)
    try:
        engine._thread.join()  # until the leader's stop frame
    except KeyboardInterrupt:
        pass
    finally:
        engine.stop()
        coordination.close()
    return 0


def cmd_run(args) -> int:
    from .operator import Operator, OperatorOptions
    from .utils import setup_logging

    setup_logging(os.environ.get("ACP_TPU_LOG_LEVEL", "INFO"))

    if args.tpu_lora and not args.tpu_checkpoint:
        print("error: --tpu-lora requires --tpu-checkpoint", file=sys.stderr)
        return 2
    engine = None
    prewarm = None
    if args.tpu_preset or args.tpu_checkpoint:
        # multi-host serving: join the jax.distributed cluster (env-driven
        # no-op single-host); this leader process broadcasts admission
        # frames to `acp-tpu engine-follower` processes on the other hosts
        from .parallel.distributed import initialize_distributed

        initialize_distributed()
        import jax as _jax

        coordination = None
        if _jax.process_count() > 1:
            from .engine.coordination import CoordinationLeader

            if _jax.process_index() != 0:
                print(
                    "error: on multi-host ranks > 0 run `acp-tpu "
                    "engine-follower`, not `run`", file=sys.stderr,
                )
                return 2
            from .engine.coordination import server_ssl_context

            bind = os.environ.get("ACP_COORD_BIND", "0.0.0.0:8091")
            token = os.environ.get("ACP_COORD_TOKEN", "")
            cert = os.environ.get("ACP_COORD_TLS_CERT", "")
            key = os.environ.get("ACP_COORD_TLS_KEY", "")
            bind_host = bind.rpartition(":")[0]
            if not token and bind_host not in ("127.0.0.1", "localhost", "::1"):
                # the frame stream carries every request's prompt token ids,
                # and any raw connector would count toward lockstep
                print(
                    "error: serving coordination on a non-loopback interface "
                    f"({bind}) requires ACP_COORD_TOKEN (and ideally "
                    "ACP_COORD_TLS_CERT/KEY); set ACP_COORD_BIND=127.0.0.1:8091 "
                    "for single-host use", file=sys.stderr,
                )
                return 2
            coordination = CoordinationLeader(
                bind=bind,
                token=token or None,
                ssl_context=server_ssl_context(cert, key) if cert and key else None,
            )
            # a wildcard bind is not a routable --coordinator target;
            # print this host's name in its place
            import socket as _socket

            shown = coordination.address.replace("0.0.0.0", _socket.getfqdn())
            print(f"serving coordination on {shown}; waiting for "
                  f"{_jax.process_count() - 1} follower(s)", flush=True)
            coordination.wait_for_followers(_jax.process_count() - 1)
        engine = _build_engine(args, coordination)
        engine.start()
        if args.tpu_prewarm:
            import signal

            # a failed prewarm ends the serve loop below (SIGTERM is what
            # serve_until_signalled waits on) and the exit code says why
            prewarm = EnginePrewarm(
                engine, on_failure=lambda: os.kill(os.getpid(), signal.SIGTERM)
            )
            prewarm.start()

    if args.store and (args.db or args.serve_store):
        raise SystemExit("--store joins a remote store; --db/--serve-store "
                         "belong to the replica that owns it")
    if args.serve_store and args.serve_store.startswith("tcp://") and not args.store_token:
        host = args.serve_store[len("tcp://"):].rpartition(":")[0]
        if host not in ("127.0.0.1", "localhost", "::1"):
            # same posture as the coordination channel: this socket grants
            # full control-plane read/write (Secrets and Leases included)
            raise SystemExit(
                f"error: serving the store on a non-loopback interface "
                f"({args.serve_store}) requires --store-token / "
                f"$ACP_STORE_TOKEN; use unix:// or tcp://127.0.0.1 for "
                f"token-less single-host setups"
            )
    options = OperatorOptions(
        db_path=args.db,
        store_address=args.store,
        serve_store=args.serve_store,
        store_token=args.store_token,
        identity=args.identity or f"acp-tpu-{os.getpid()}",
        leader_election=args.leader_elect,
        api_port=args.port,
        api_host=args.host,
        api_token=args.api_token,
        tls_cert_path=args.tls_cert,
        tls_key_path=args.tls_key,
        tls_client_ca_path=args.tls_client_ca,
        engine=engine,
    )

    async def main():
        from .operator import serve_until_signalled

        op = Operator(options)
        await op.start()
        print(f"operator running; REST API on :{args.port}", flush=True)
        try:
            await serve_until_signalled()
            print("shutting down", flush=True)
        except asyncio.CancelledError:
            pass
        finally:
            await op.stop()
            if engine is not None:
                engine.stop()

    try:
        asyncio.run(main())
    except KeyboardInterrupt:
        pass
    if prewarm is not None and prewarm.error is not None:
        print(f"error: engine prewarm failed: {prewarm.error}", file=sys.stderr)
        return 1
    return 0


def cmd_apply(args) -> int:
    with open(args.filename) as f:
        text = f.read()
    with _client(args) as http:
        resp = http.post("/v1/apply", content=text)
        if resp.status_code != 200:
            print(f"error: {resp.text}", file=sys.stderr)
            return 1
        for item in resp.json():
            print(f"{item['kind'].lower()}/{item['name']} {item['action']}")
    return 0


def cmd_get(args) -> int:
    import yaml

    with _client(args) as http:
        if args.name:
            resp = http.get(f"/v1/resources/{args.kind}/{args.name}")
            if resp.status_code != 200:
                print(f"error: {resp.text}", file=sys.stderr)
                return 1
            docs = [resp.json()]
        else:
            resp = http.get(f"/v1/resources/{args.kind}")
            if resp.status_code != 200:
                print(f"error: {resp.text}", file=sys.stderr)
                return 1
            docs = resp.json()
    if args.output == "yaml":
        print(yaml.safe_dump_all(docs, sort_keys=False), end="")
    else:
        rows = [
            (
                d["metadata"]["name"],
                (d.get("status") or {}).get("phase")
                or (d.get("status") or {}).get("status", ""),
                (d.get("status") or {}).get("status_detail", "")[:60],
            )
            for d in docs
        ]
        width = max([len(r[0]) for r in rows], default=4) + 2
        print(f"{'NAME':<{width}}{'STATUS':<14}DETAIL")
        for name, status, detail in rows:
            print(f"{name:<{width}}{status:<14}{detail}")
    return 0


def cmd_delete(args) -> int:
    with _client(args) as http:
        resp = http.delete(f"/v1/resources/{args.kind}/{args.name}")
        if resp.status_code != 200:
            print(f"error: {resp.text}", file=sys.stderr)
            return 1
        print(f"{args.kind.lower()}/{args.name} deleted")
    return 0


def cmd_events(args) -> int:
    with _client(args) as http:
        resp = http.get("/v1/events")
        for e in resp.json():
            print(f"{e['type']:<8}{e['reason']:<28}{e['involved']:<36}{e['message']}")
    return 0


def cmd_approvals(args) -> int:
    with _client(args) as http:
        if args.action == "list" or args.action is None:
            for a in http.get("/v1/approvals").json():
                print(f"{a['callId']:<16}{a['fn']:<32}{json.dumps(a['kwargs'])[:60]}")
            return 0
        if not args.call_id:
            print("error: approvals approve/reject requires a call-id", file=sys.stderr)
            return 2
        resp = http.post(
            f"/v1/approvals/{args.call_id}/{args.action}",
            params={"comment": args.comment or ""},
        )
        print(resp.json() if resp.status_code == 200 else resp.text)
        return 0 if resp.status_code == 200 else 1


def cmd_contacts(args) -> int:
    with _client(args) as http:
        if args.action == "list" or args.action is None:
            for c in http.get("/v1/contacts").json():
                print(f"{c['callId']:<16}{c['message'][:80]}")
            return 0
        if not args.call_id or args.text is None:
            print("error: contacts respond requires <call-id> <text>", file=sys.stderr)
            return 2
        resp = http.post(
            f"/v1/contacts/{args.call_id}/respond", json={"response": args.text}
        )
        print(resp.json() if resp.status_code == 200 else resp.text)
        return 0 if resp.status_code == 200 else 1


def cmd_task_create(args) -> int:
    with _client(args) as http:
        resp = http.post(
            "/v1/tasks", json={"agentName": args.agent, "userMessage": args.message}
        )
        if resp.status_code != 201:
            print(f"error: {resp.text}", file=sys.stderr)
            return 1
        task = resp.json()
        print(f"task/{task['name']} created")
        if not args.follow:
            return 0
        last_phase = ""
        while True:
            resp = http.get(f"/v1/tasks/{task['name']}")
            if resp.status_code != 200:
                print(f"error: {resp.text}", file=sys.stderr)
                return 1
            t = resp.json()
            if t["phase"] != last_phase:
                print(f"  phase: {t['phase']}  {t.get('statusDetail', '')}")
                last_phase = t["phase"]
            if t["phase"] in ("FinalAnswer", "Failed"):
                print(t.get("output") or t.get("error", ""))
                return 0 if t["phase"] == "FinalAnswer" else 1
            time.sleep(0.5)


def cmd_task_show(args) -> int:
    """Print a task's checkpointed conversation (the execution state)."""
    with _client(args) as http:
        resp = http.get(f"/v1/tasks/{args.name}")
        if resp.status_code != 200:
            print(f"error: {resp.text}", file=sys.stderr)
            return 1
        t = resp.json()
        print(f"task/{t['name']}  agent={t['agentName']}  phase={t['phase']}  {t['statusDetail']}")
        for m in t["contextWindow"]:
            role = m["role"].upper()
            content = m.get("content", "")
            if content:
                print(f"  [{role}] {content if len(content) <= 200 else content[:197] + '...'}")
            if m.get("tool_calls"):
                calls = ", ".join(
                    f"{tc['function']['name']}({tc['function']['arguments']})"
                    for tc in m["tool_calls"]
                )
                print(f"  [{role}] -> {calls}")
            if not content and not m.get("tool_calls"):
                print(f"  [{role}]")
        if t.get("error"):
            print(f"  ERROR: {t['error']}")
    return 0


def cmd_train(args) -> int:
    """LoRA fine-tuning in one command: JSONL dataset -> adapter directory
    servable via ``acp-tpu run --tpu-lora``. Lines are either
    {"text": "..."} or {"messages": [{role, content}, ...]} (rendered with
    the same chat template the engine serves)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from .api.resources import Message
    from .engine.tokenizer import ByteTokenizer, HFTokenizer, render_turns
    from .engine.weights import load_safetensors_dir
    from .parallel.mesh import make_mesh
    from .train import LoraConfig, LoraTrainer, save_lora
    from .utils import setup_logging

    setup_logging(os.environ.get("ACP_TPU_LOG_LEVEL", "INFO"))

    params, config = load_safetensors_dir(args.checkpoint)
    tok_path = os.path.join(args.checkpoint, "tokenizer.json")
    tokenizer = HFTokenizer(tok_path) if os.path.exists(tok_path) else ByteTokenizer()
    if tokenizer.vocab_size > config.vocab_size:
        # out-of-range ids would be silently clamped under jit — the
        # adapter would train on corrupted embeddings with no error
        print(
            f"error: tokenizer vocab {tokenizer.vocab_size} exceeds model "
            f"vocab {config.vocab_size}",
            file=sys.stderr,
        )
        return 2

    from .train.lora import LORA_TARGETS

    targets = tuple(t.strip() for t in args.targets.split(",") if t.strip())
    bad = [t for t in targets if t not in LORA_TARGETS]
    if not targets or bad:
        print(f"error: bad --targets {bad or '(empty)'}; valid: {LORA_TARGETS}", file=sys.stderr)
        return 2

    # rows = (token ids, per-token supervision flags): a position's loss is
    # counted when its TARGET (next token) is supervised
    rows: list[tuple[list[int], list[int]]] = []
    skipped = 0
    with open(args.data) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                doc = json.loads(line)
                if "messages" in doc:
                    # per-turn segments (no open generation header); with
                    # --mask-prompt only assistant turns are supervised —
                    # the model learns replies, not to parrot prompts
                    ids: list[int] = []
                    sup: list[int] = []
                    for role, seg in render_turns(
                        [Message(**m) for m in doc["messages"]], tools=[]
                    ):
                        seg_ids = tokenizer.encode(seg)
                        on = 1 if (role == "assistant" or not args.mask_prompt) else 0
                        ids.extend(seg_ids)
                        sup.extend([on] * len(seg_ids))
                else:
                    ids = tokenizer.encode(doc["text"])
                    sup = [1] * len(ids)
            except (KeyError, ValueError, TypeError) as e:
                print(f"error: {args.data}:{lineno}: {e}", file=sys.stderr)
                return 2
            ids, sup = ids[: args.seq_len], sup[: args.seq_len]
            if len(ids) >= 8 and any(sup):
                rows.append((ids, sup))
            else:
                skipped += 1
    if not rows:
        print(
            f"error: no usable examples ({skipped} skipped: shorter than 8 "
            "tokens or no supervised tokens within --seq-len)",
            file=sys.stderr,
        )
        return 2
    if skipped:
        print(f"note: skipped {skipped} examples (too short / nothing supervised)")
    print(f"dataset: {len(rows)} examples; model dim={config.dim} L={config.n_layers}")

    devices = jax.devices()
    tp = args.tp
    if len(devices) % tp:
        print(f"error: --tp {tp} does not divide {len(devices)} devices", file=sys.stderr)
        return 2
    # largest dp that divides the batch (a silent 1-chip fallback would
    # waste the host; an indivisible batch is likelier operator error)
    max_dp = len(devices) // tp
    dp = max(d for d in range(1, max_dp + 1) if args.batch % d == 0)
    if dp < max_dp:
        print(f"note: batch {args.batch} limits dp to {dp} of {max_dp} possible")
    mesh = make_mesh({"dp": dp, "tp": tp}, devices=devices[: dp * tp])
    lora_cfg = LoraConfig(rank=args.rank, alpha=args.alpha, targets=targets)
    trainer = LoraTrainer(
        config=config, lora=lora_cfg, mesh=mesh, optimizer=optax.adamw(args.lr)
    )
    base = jax.device_put(params, trainer.base_sharding)
    lora_params, opt_state = trainer.init(jax.random.key(args.seed))

    rng = np.random.default_rng(args.seed)
    pad = 0
    for step in range(args.steps):
        idx = rng.integers(0, len(rows), size=args.batch)
        batch = np.full((args.batch, args.seq_len), pad, dtype=np.int32)
        mask = np.zeros_like(batch)
        for j, i in enumerate(idx):
            ids, sup = rows[int(i)]
            batch[j, : len(ids)] = ids
            # position t predicts token t+1: supervise t iff target t+1 is
            # supervised (this also drops the last real token, whose
            # shifted target would be padding)
            mask[j, : len(ids) - 1] = sup[1:]
        tokens = jax.device_put(jnp.asarray(batch), trainer.batch_sharding)
        loss_mask = jax.device_put(jnp.asarray(mask), trainer.batch_sharding)
        lora_params, opt_state, loss = trainer.train_step(
            lora_params, opt_state, base, tokens, loss_mask
        )
        if step % max(1, args.steps // 20) == 0 or step == args.steps - 1:
            print(f"step {step:>5}  loss {float(loss):.4f}", flush=True)
    save_lora(args.out, lora_params, lora_cfg, step=args.steps)
    print(f"adapter saved to {args.out}; serve with: acp-tpu run "
          f"--tpu-checkpoint {args.checkpoint} --tpu-lora {args.out}")
    return 0


def cmd_chat(args) -> int:
    """Interactive REPL against the OpenAI-compatible front door (SSE
    streaming) — the quickest way to poke the TPU engine by hand."""
    import httpx

    messages: list[dict] = []
    if args.system:
        messages.append({"role": "system", "content": args.system})
    print("chatting with the engine; empty line or Ctrl-D to exit", flush=True)
    with _client(args, timeout=None) as http:
        while True:
            try:
                line = input("> ").strip()
            except (EOFError, KeyboardInterrupt):
                print(flush=True)
                return 0
            if not line:
                return 0
            messages.append({"role": "user", "content": line})
            payload = {
                "messages": messages,
                "max_tokens": args.max_tokens,
                "temperature": args.temperature,
                "stream": True,
            }
            reply = []
            errored = False
            try:
                with http.stream("POST", "/v1/chat/completions", json=payload) as resp:
                    if resp.status_code != 200:
                        resp.read()
                        print(f"error: {resp.text}", file=sys.stderr)
                        messages.pop()
                        continue
                    for raw in resp.iter_lines():
                        if not raw.startswith("data: ") or raw == "data: [DONE]":
                            continue
                        event = json.loads(raw[len("data: "):])
                        if "error" in event:
                            print(f"\nerror: {event['error']['message']}", file=sys.stderr)
                            errored = True
                            break
                        delta = event["choices"][0]["delta"]
                        chunk = delta.get("content") or ""
                        if chunk:
                            reply.append(chunk)
                            print(chunk, end="", flush=True)
                        for tc in delta.get("tool_calls") or []:
                            print(
                                f"\n[tool call] {tc['function']['name']}"
                                f"({tc['function']['arguments']})",
                                flush=True,
                            )
            except (httpx.HTTPError, KeyboardInterrupt) as e:
                print(f"\nerror: {e}", file=sys.stderr)
                errored = True
            if errored:
                # drop the failed exchange entirely so the next turn's
                # conversation isn't corrupted by a partial assistant turn
                messages.pop()
                continue
            print(flush=True)
            messages.append({"role": "assistant", "content": "".join(reply)})


def cmd_engine(args) -> int:
    with _client(args) as http:
        resp = http.get("/v1/engine")
        if resp.status_code != 200:
            print(f"error: {resp.text}", file=sys.stderr)
            return 1
        print(json.dumps(resp.json(), indent=2))
        return 0


def cmd_perf(args) -> int:
    """Compute efficiency observatory: per-program dispatch telemetry
    (where device time goes, how much of each dispatch is padding), the
    cold-compile observatory (compiles real traffic paid for after
    prewarm), the goodput/waste ledger (tokens computed vs emitted,
    waste attributed by cause), the engine loop's phase table (host
    ms per cycle by phase), and the engine's start: its set-up phases and
    the costliest first dispatches, each split into trace, lowering,
    compile or cache load, and first run."""
    with _client(args) as http:
        resp = http.get("/v1/engine/perf")
        if resp.status_code != 200:
            print(f"error: {resp.text}", file=sys.stderr)
            return 1
        doc = resp.json()
        if args.json:
            print(json.dumps(doc, indent=2))
            return 0
        g = doc.get("goodput", {})
        computed = g.get("computed", 0)
        print(
            f"goodput: {g.get('goodput', 0)}/{computed} token positions "
            f"({g.get('ratio', 1.0):.1%}); profiler "
            f"{'enabled' if doc.get('enabled') else 'DISABLED'}, "
            f"prewarmed={doc.get('prewarmed')}"
        )
        waste = {k: v for k, v in g.get("waste", {}).items() if v}
        if waste:
            print("waste by cause:")
            for cause, n in sorted(waste.items(), key=lambda kv: -kv[1]):
                pct = 100.0 * n / computed if computed else 0.0
                print(f"  {cause:<18}{n:>12}  ({pct:.1f}%)")
        cold = doc.get("cold_compiles", {})
        if cold.get("serving"):
            print(f"SERVING-TIME COLD COMPILES: {cold['serving']} "
                  "(each was a latency stall — widen prewarm coverage)")
            for ev in cold.get("events", []):
                if not ev.get("retrace"):
                    print(f"  {ev['program']:<34}{ev['wall_s'] * 1e3:>10.1f}ms")
        if cold.get("retraces"):
            print(f"RETRACES: {cold['retraces']} (a program key dispatched again "
                  "compiled again: the key holds less than jit's cache keys on)")
            for ev in cold.get("events", []):
                if ev.get("retrace"):
                    print(f"  {ev['program']:<34}{ev['wall_s'] * 1e3:>10.1f}ms")
        phases = doc.get("phases", {})
        cycles = doc.get("cycles", 0)
        busy = sum(p["s"] for name, p in phases.items() if name != "park")
        if phases and cycles and busy:
            # the engine loop's own phases: self time, so the rows partition
            # the loop's busy time (park = waiting on an empty queue)
            print(f"engine loop: {cycles} busy cycles, "
                  f"{doc.get('blocks', 0)} decode blocks, "
                  f"{doc.get('uploads', 0)} uploads")
            print(f"{'PHASE':<10}{'N':>9}{'ms/CYCLE':>11}{'SHARE':>8}")
            for name, p in sorted(phases.items(), key=lambda kv: -kv[1]["s"]):
                share = f"{p['s'] / busy:>8.1%}" if name != "park" else f"{'-':>8}"
                print(f"{name:<10}{p['n']:>9}{p['s'] * 1e3 / cycles:>11.3f}{share}")
        programs = doc.get("programs", {})
        if programs:
            print(f"{'PROGRAM':<34}{'N':>7}{'HOST ms':>10}{'DEV ms':>10}"
                  f"{'PAD%':>7}  TOKENS")
            for key, p in list(programs.items())[: args.top]:
                dev = p.get("device_ms_mean")
                print(
                    f"{key:<34}{p['dispatches']:>7}"
                    f"{p['host_ms_mean']:>10.3f}"
                    f"{dev if dev is not None else float('nan'):>10.3f}"
                    f"{p['padding_pct']:>7.1f}  {p['real_tokens']}"
                )
        setup = doc.get("setup")
        if setup:
            # the engine's start: wall seconds by phase (a phase opened in
            # another is inside it), then the costliest first dispatches
            print(f"set-up: {setup['programs']} programs first dispatched, "
                  f"{setup['cache_misses']} missed the compile cache; prewarm "
                  f"less its first dispatches {setup['prewarm_rest_s']:.2f}s")
            print(f"{'SET-UP PHASE':<20}{'N':>4}{'s':>10}{'JAX s':>9}{'FIRST DISPATCHES s':>20}")
            for name, p in setup["phases"].items():
                print(f"{name:<20}{p['n']:>4}{p['s']:>10.3f}{p['jax_s']:>9.3f}"
                      f"{p['first_wall_s']:>20.3f}")
            print(f"{'FIRST DISPATCH ms':<34}{'WALL':>9}{'TRACE':>9}{'LOWER':>9}"
                  f"{'COMPILE':>9}{'LOAD':>9}{'RUN':>9}  CACHE")
            costliest = sorted(programs.items(), key=lambda kv: -kv[1]["first_wall_ms"])
            for key, p in costliest[:5]:
                cache = {True: "hit", False: "miss", None: "-"}[p["cache_hit"]]
                print(f"{key:<34}{p['first_wall_ms']:>9.1f}{p['trace_ms']:>9.1f}"
                      f"{p['lower_ms']:>9.1f}{p['compile_ms']:>9.1f}{p['load_ms']:>9.1f}"
                      f"{p['run_ms']:>9.1f}  {cache}")
        return 0


def cmd_fleet(args) -> int:
    """Fleet observatory: the replica table (role, liveness, lease holder
    + epoch, queue depth, goodput, affinity keys homed) plus the router's
    routing/failover/handoff ledgers — GET /v1/fleet."""
    with _client(args) as http:
        resp = http.get("/v1/fleet")
        if resp.status_code != 200:
            print(f"error: {resp.text}", file=sys.stderr)
            return 1
        doc = resp.json()
        if args.json:
            print(json.dumps(doc, indent=2))
            return 0
        replicas = doc.get("replicas", [])
        routing = doc.get("routing", {})
        print(
            f"fleet: {sum(1 for r in replicas if r.get('alive'))}/"
            f"{len(replicas)} replicas live, policy={routing.get('policy')}"
        )
        print(
            f"{'REPLICA':<12}{'ROLE':<9}{'ALIVE':<7}{'LEASE HOLDER':<22}"
            f"{'EPOCH':>6}{'QUEUE':>7}{'ACTIVE':>8}{'GOODPUT':>9}{'KEYS':>6}"
        )
        for r in replicas:
            lease = r.get("lease", {})
            goodput = r.get("goodput_ratio")
            print(
                f"{r['id']:<12}{r.get('role', '?'):<9}"
                f"{('yes' if r.get('alive') else 'DEAD'):<7}"
                f"{(lease.get('holder') or '-'):<22}{lease.get('epoch', 0):>6}"
                f"{r.get('queue_depth', 0):>7}{r.get('active_slots', 0):>8}"
                f"{goodput if goodput is None else format(goodput, '.1%'):>9}"
                f"{r.get('affinity_keys', 0):>6}"
            )
        print(
            f"routing: {routing.get('routed', 0)} routed, "
            f"{routing.get('affinity_hits', 0)} affinity hits / "
            f"{routing.get('affinity_misses', 0)} misses, "
            f"{routing.get('sheds_skipped', 0)} shed replicas skipped, "
            f"{routing.get('inflight', 0)} in flight"
        )
        fo = doc.get("failover", {})
        print(
            f"failover: {fo.get('failovers', 0)} failovers, "
            f"max {fo.get('failover_max', 0)} per request"
        )
        ho = doc.get("handoff", {})
        if ho.get("enabled"):
            print(
                f"handoff: {ho.get('handoffs', 0)} prefill->decode handoffs "
                f"({ho.get('bytes', 0)} KV bytes), {ho.get('errors', 0)} "
                f"errors, min {ho.get('min_tokens', 0)} prompt tokens"
            )
        else:
            print("handoff: disabled (handoff_min_tokens=0)")
        return 0


def cmd_timeline(args) -> int:
    """Flight-recorder introspection: with a request id, replay that
    request's full decision sequence (admit, chunks, preempts, park/adopt,
    finish) with derived phase latencies; without one, show the recent
    window and the request ids whose timelines are queryable."""
    with _client(args) as http:
        if not args.request_id:
            resp = http.get("/v1/engine/flight", params={"last": str(args.last)})
            if resp.status_code != 200:
                print(f"error: {resp.text}", file=sys.stderr)
                return 1
            doc = resp.json()
            print(
                f"flight recorder: {doc['window_events']}/{doc['capacity']} "
                f"events windowed, {doc['recorded_total']} recorded total, "
                f"enabled={doc['enabled']}"
            )
            if doc.get("request_ids"):
                print("recent request ids: " + " ".join(doc["request_ids"]))
            for e in doc["events"]:
                _print_flight_event(e)
            return 0
        resp = http.get(f"/v1/requests/{args.request_id}/timeline")
        if resp.status_code != 200:
            print(f"error: {resp.text}", file=sys.stderr)
            return 1
        doc = resp.json()
        print(f"request {doc['request_id']}  total {doc['total_s'] * 1e3:.1f}ms")
        for e in doc["events"]:
            _print_flight_event(e, rel_key="t_rel")
        if doc.get("phases"):
            print("phases (sum ~ end-to-end; tool_overlap_hidden overlaps decode):")
            for phase, dur in doc["phases"].items():
                print(f"  {phase:<22}{dur * 1e3:>10.1f}ms")
        if doc.get("rate_plan"):
            rp = doc["rate_plan"]
            print(
                f"rate plan: quota {rp['quota']} chunk(s)/cycle, "
                f"{rp['reprojections']} reprojection(s); actual "
                f"{rp['chunks_dispatched']} chunks / {rp['chunk_tokens']} "
                f"tokens over {rp['prefill_span_s'] * 1e3:.1f}ms"
            )
            for pr in rp["projections"]:
                print(
                    f"  {pr['reason']:<8} quota={pr['quota']} "
                    f"tokens_left={pr['tokens_left']} "
                    f"seconds_left={pr['seconds_left']}"
                )
        return 0


def cmd_trace_export(args) -> int:
    """Pull the anonymized replayable workload trace off a running server:
    ``/v1/engine/trace`` for a single engine, ``/v1/fleet/trace`` for the
    stitched cross-replica view. The doc is validated before it is written
    — an export this command exits 0 on is guaranteed replayable."""
    from .observability.trace_export import validate_trace

    path = "/v1/fleet/trace" if args.fleet else "/v1/engine/trace"
    with _client(args) as http:
        resp = http.get(path)
    if resp.status_code != 200:
        print(
            f"error: GET {path} -> {resp.status_code}: {resp.text[:200]}",
            file=sys.stderr,
        )
        return 1
    doc = resp.json()
    problems = validate_trace(doc)
    if problems:
        print("error: server returned an unreplayable trace:", file=sys.stderr)
        for problem in problems[:10]:
            print(f"  {problem}", file=sys.stderr)
        return 1
    payload = json.dumps(doc, indent=2, sort_keys=True)
    if args.output:
        with open(args.output, "w") as fh:
            fh.write(payload + "\n")
        summary = (
            f"wrote {args.output}: {len(doc['requests'])} request(s) over "
            f"{doc.get('span_s', 0.0):.3f}s from {doc.get('source')}"
        )
        if not doc.get("complete", True):
            summary += "  [INCOMPLETE: recorder evicted timelines mid-window]"
        print(summary)
    else:
        print(payload)
    return 0


def _scenario_overrides(pairs: list[str]) -> dict:
    """``--set k=v`` pairs with int/float coercion (generator kwargs are
    numeric except ``crash_replica``)."""
    out: dict = {}
    for pair in pairs:
        key, sep, raw = pair.partition("=")
        if not sep or not key:
            raise ValueError(f"--set expects K=V, got {pair!r}")
        value: object = raw
        for cast in (int, float):
            try:
                value = cast(raw)
                break
            except ValueError:
                continue
        out[key] = value
    return out


def cmd_replay(args) -> int:
    """Deterministic local replay: load a trace file (or build a library
    scenario), validate it, play it against a freshly built in-process
    engine, and print the SLO summary. ``--gate`` judges the run against
    its scenario's envelope.

    Exit codes: 0 clean; 1 operational failure (unreadable/unreplayable
    trace, engine construction, or request errors during the run); 2 the
    run finished but tripped its SLO envelope (``--gate``)."""
    from .observability.trace_export import validate_trace
    from .scenarios import build, replay

    if args.trace and args.scenario:
        print("error: pass a trace file OR --scenario, not both", file=sys.stderr)
        return 1
    if args.trace:
        try:
            with open(args.trace) as fh:
                doc = json.load(fh)
        except (OSError, ValueError) as exc:
            print(f"error: cannot load {args.trace}: {exc}", file=sys.stderr)
            return 1
    elif args.scenario:
        try:
            doc = build(args.scenario, **_scenario_overrides(args.overrides))
        except (KeyError, TypeError, ValueError) as exc:
            print(f"error: {exc}", file=sys.stderr)
            return 1
    else:
        print("error: pass a trace file or --scenario NAME", file=sys.stderr)
        return 1
    problems = validate_trace(doc)
    if problems:
        print("error: unreplayable trace:", file=sys.stderr)
        for problem in problems[:10]:
            print(f"  {problem}", file=sys.stderr)
        return 1
    source = str(doc.get("source") or "replay")
    scenario = args.scenario or source.removeprefix("scenario:")
    if args.check:
        print(
            f"trace ok: {len(doc['requests'])} request(s) over "
            f"{doc.get('span_s', 0.0):.3f}s from {source}"
        )
        return 0
    engine = _build_engine(args)
    engine.start()
    try:
        if args.prewarm:
            engine.prewarm(constrained=True)
        report = replay(
            doc, engine, speed=args.speed, seed=args.seed, scenario=scenario,
        )
    finally:
        engine.stop()
    slo = report.slo_doc()
    if args.json:
        print(json.dumps(slo, indent=2, sort_keys=True))
    else:
        print(
            f"replayed {slo['requests']} request(s) at {args.speed:g}x "
            f"(seed {args.seed}) in {slo['wall_s']:.2f}s wall"
        )
        print(
            f"  outcomes: {slo['completed']} completed, {slo['shed']} shed, "
            f"{slo['cancelled']} cancelled, {slo['expired']} expired, "
            f"{slo['errors']} error(s); {slo['tool_calls']} tool call(s)"
        )
        print(
            f"  ttft p50/p99 {slo['ttft_p50_ms']:.1f}/{slo['ttft_p99_ms']:.1f}ms  "
            f"e2e p50/p99 {slo['e2e_p50_ms']:.1f}/{slo['e2e_p99_ms']:.1f}ms  "
            f"decode-stall p99 {slo['decode_stall_p99_ms']:.1f}ms"
        )
        if slo.get("goodput_ratio") is not None:
            print(f"  goodput ratio {slo['goodput_ratio']:.3f}")
    if args.gate:
        from .analysis.slo_gate import check_block

        violations = check_block(scenario, "single", slo)
        if violations:
            print(f"slo-gate: {len(violations)} envelope violation(s):")
            for violation in violations:
                print(f"  {violation}")
            return 2
        print(f"slo-gate: {scenario} inside its envelope")
    if slo["errors"]:
        for row in report.rows:
            if row.outcome == "error":
                print(f"error: request {row.index}: {row.error}", file=sys.stderr)
        return 1
    return 0


def cmd_chaos(args) -> int:
    """Seeded chaos drill: build an in-process fleet of ``--replicas``
    engines (invariant checkers armed) behind a FleetRouter, pour the
    seed's deterministic fault cocktail over a library-scenario replay,
    and judge the invariants that must survive graceful faults — request
    conservation, exactly-once streams, zero unexplained errors.

    Exit codes: 0 the run survived (or no --gate); 1 operational failure
    (construction / scenario errors); 2 an invariant tripped (--gate)."""
    from .fleet import FleetRouter
    from .kernel import Store
    from .scenarios import run_chaos

    try:
        overrides = _scenario_overrides(args.overrides)
    except ValueError as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 1
    engines: list = []
    router = None
    try:
        router = FleetRouter(
            store=Store(), heartbeat_interval=60.0,
            hedge_after_s=args.hedge_after_s,
        )
        for i in range(max(1, args.replicas)):
            engine = _build_engine(args, check_invariants=True)
            engine.start()
            engines.append(engine)
            router.add_replica(f"r{i}", engine)
        if args.prewarm:
            for engine in engines:
                engine.prewarm(constrained=True)
        report = run_chaos(
            router, seed=args.seed, scenario=args.scenario,
            speed=args.speed, scenario_kwargs=overrides,
        )
    except (KeyError, TypeError, ValueError) as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 1
    finally:
        if router is not None:
            router.stop()
        for engine in engines:
            try:
                engine.stop()
            except Exception:
                pass
    doc = report.doc()
    if args.json:
        print(json.dumps(doc, indent=2, sort_keys=True))
    else:
        slo = doc["slo"]
        print(
            f"chaos seed {report.seed} over {report.scenario}: "
            f"{len(report.ledger)}/{len(report.schedule)} fault(s) armed "
            f"across {slo['requests']} request(s) at {args.speed:g}x"
        )
        for offset, site, spec in report.ledger:
            detail = " ".join(f"{k}={v}" for k, v in sorted(spec.items()))
            print(f"  +{offset:7.3f}s  {site:<24}{detail}")
        print(
            f"  outcomes: {slo['completed']} completed, {slo['shed']} shed, "
            f"{slo['cancelled']} cancelled, {slo['expired']} expired, "
            f"{slo['errors']} error(s)"
        )
        if report.ok():
            print("  invariants: all held")
        else:
            print(f"  invariants: {len(report.violations)} violation(s):")
            for violation in report.violations:
                print(f"    {violation}")
    if args.gate and not report.ok():
        return 2
    return 0


def _print_flight_event(e: dict, rel_key: str | None = None) -> None:
    stamp = (
        f"+{e[rel_key] * 1e3:9.1f}ms" if rel_key and rel_key in e
        else f"t={e['t']:.3f}"
    )
    who = e.get("rid", "-")
    slot = f"slot {e['slot']}" if "slot" in e else ""
    detail = ""
    if e.get("detail"):
        detail = " ".join(f"{k}={v}" for k, v in e["detail"].items())
    print(f"  {stamp}  {e['kind']:<20}{who:<10}{slot:<9}{detail}")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="acp-tpu", description=__doc__)
    p.add_argument("--server", default=DEFAULT_SERVER, help="operator REST URL")
    p.add_argument(
        "--token",
        default=None,
        help="bearer token for the REST API (default: $ACP_API_TOKEN)",
    )
    sub = p.add_subparsers(dest="command", required=True)

    run = sub.add_parser("run", help="run the operator")
    run.add_argument("--db", default=None, help="sqlite state path (default: in-memory)")
    run.add_argument("--port", type=int, default=8082)
    run.add_argument(
        "--host", default="127.0.0.1",
        help="REST bind address (0.0.0.0 inside containers)",
    )
    run.add_argument("--identity", default=None)
    run.add_argument("--leader-elect", action="store_true")
    run.add_argument(
        "--serve-store", default=None, metavar="ADDR",
        help="serve this replica's store for other replicas "
        "(unix:///path.sock or tcp://host:port)",
    )
    run.add_argument(
        "--store", default=None, metavar="ADDR",
        help="join another replica's served store instead of owning one "
        "(multi-replica: leases + leader election hold across processes)",
    )
    run.add_argument(
        "--store-token",
        default=os.environ.get("ACP_STORE_TOKEN", ""),
        help="shared secret for the served-store socket — required from "
        "joining replicas when serving, presented when joining (default: "
        "$ACP_STORE_TOKEN). Empty disables auth: acceptable only for "
        "unix:// sockets (0600) or network-isolated loopback tcp://",
    )
    run.add_argument(
        "--api-token",
        default=os.environ.get("ACP_API_TOKEN", ""),
        help="require this bearer token on the REST API (default: $ACP_API_TOKEN)",
    )
    run.add_argument(
        "--tls-cert", default=os.environ.get("ACP_TLS_CERT") or None,
        help="serve the REST API over HTTPS with this certificate (PEM); "
        "rotated files are picked up without restart",
    )
    run.add_argument(
        "--tls-key", default=os.environ.get("ACP_TLS_KEY") or None,
        help="private key (PEM) for --tls-cert",
    )
    run.add_argument(
        "--tls-client-ca", default=os.environ.get("ACP_TLS_CLIENT_CA") or None,
        help="require client certificates signed by this CA (mTLS)",
    )
    _add_tpu_flags(run)
    run.add_argument(
        "--tpu-prewarm",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="compile serving programs in the background at startup",
    )
    run.set_defaults(fn=cmd_run)

    fol = sub.add_parser(
        "engine-follower",
        help="multi-host serving: a rank>0 engine that replays rank 0's "
        "admission frames (pass the SAME --tpu-* flags as rank 0's run)",
    )
    fol.add_argument(
        "--coordinator", required=True, metavar="HOST:PORT",
        help="rank 0's serving-coordination address (printed by `run`)",
    )
    _add_tpu_flags(fol)
    fol.set_defaults(fn=cmd_engine_follower)

    ap = sub.add_parser("apply", help="apply manifests")
    ap.add_argument("-f", "--filename", required=True)
    ap.set_defaults(fn=cmd_apply)

    get = sub.add_parser("get", help="get resources")
    get.add_argument("kind")
    get.add_argument("name", nargs="?")
    get.add_argument("-o", "--output", choices=["table", "yaml"], default="table")
    get.set_defaults(fn=cmd_get)

    de = sub.add_parser("delete", help="delete a resource")
    de.add_argument("kind")
    de.add_argument("name")
    de.set_defaults(fn=cmd_delete)

    ev = sub.add_parser("events", help="execution history")
    ev.set_defaults(fn=cmd_events)

    apr = sub.add_parser("approvals", help="pending human approvals")
    apr.add_argument("action", nargs="?", choices=["list", "approve", "reject"])
    apr.add_argument("call_id", nargs="?")
    apr.add_argument("--comment", default="")
    apr.set_defaults(fn=cmd_approvals)

    con = sub.add_parser("contacts", help="pending human contacts")
    con.add_argument("action", nargs="?", choices=["list", "respond"])
    con.add_argument("call_id", nargs="?")
    con.add_argument("text", nargs="?")
    con.set_defaults(fn=cmd_contacts)

    task = sub.add_parser("task", help="task operations")
    tsub = task.add_subparsers(dest="task_command", required=True)
    tc = tsub.add_parser("create")
    tc.add_argument("agent")
    tc.add_argument("message")
    tc.add_argument("--follow", action="store_true")
    tc.set_defaults(fn=cmd_task_create)
    ts = tsub.add_parser("show", help="print a task's conversation")
    ts.add_argument("name")
    ts.set_defaults(fn=cmd_task_show)

    eng = sub.add_parser("engine", help="TPU engine status")
    eng.set_defaults(fn=cmd_engine)

    pf = sub.add_parser(
        "perf",
        help="compute efficiency observatory: per-program dispatch "
        "telemetry, cold compiles, goodput/waste accounting",
    )
    pf.add_argument("--json", action="store_true", help="raw JSON payload")
    pf.add_argument(
        "--top", type=int, default=20,
        help="program rows to show (sorted by total host time)",
    )
    pf.set_defaults(fn=cmd_perf)

    fl = sub.add_parser(
        "fleet",
        help="fleet replica pool: replica table (lease holder, goodput, "
        "queue depth, affinity keys) + routing/failover/handoff ledgers",
    )
    fl.add_argument("--json", action="store_true", help="raw JSON payload")
    fl.set_defaults(fn=cmd_fleet)

    tl = sub.add_parser(
        "timeline",
        help="flight recorder: a request's lifecycle timeline (or, with no "
        "id, the recent engine decision window)",
    )
    tl.add_argument("request_id", nargs="?", help="engine request id (rid)")
    tl.add_argument(
        "--last", type=int, default=50,
        help="window events to show when no request id is given",
    )
    tl.set_defaults(fn=cmd_timeline)

    trc = sub.add_parser(
        "trace",
        help="anonymized replayable workload traces (flight recorder export)",
    )
    trsub = trc.add_subparsers(dest="trace_command", required=True)
    te = trsub.add_parser(
        "export",
        help="export the engine's (or, with --fleet, the stitched "
        "cross-replica) workload trace as validated JSON",
    )
    te.add_argument(
        "--fleet", action="store_true",
        help="stitch prefill/decode/failover legs across the replica pool",
    )
    te.add_argument(
        "-o", "--output", default=None,
        help="write the trace here (default: stdout)",
    )
    te.set_defaults(fn=cmd_trace_export)

    rp = sub.add_parser(
        "replay",
        help="deterministic local replay of a trace file or a library "
        "scenario against a freshly built engine (see docs/scenarios.md)",
    )
    rp.add_argument(
        "trace", nargs="?",
        help="trace JSON from `acp-tpu trace export` (omit with --scenario)",
    )
    rp.add_argument(
        "--scenario", default=None,
        help="build a scenario from the library instead of loading a file "
        "(persona_storm, long_tail, tool_swarm, cancel_churn, fault_cocktail)",
    )
    rp.add_argument(
        "--set", action="append", default=[], metavar="K=V", dest="overrides",
        help="scenario generator kwarg override, repeatable (e.g. --set n=24)",
    )
    rp.add_argument("--speed", type=float, default=1.0,
                    help="time compression: 10 replays a 30s trace in 3s")
    rp.add_argument("--seed", type=int, default=0,
                    help="synthetic-content seed (same seed = same workload)")
    rp.add_argument(
        "--check", action="store_true",
        help="validate the trace and exit without building an engine",
    )
    rp.add_argument(
        "--gate", action="store_true",
        help="judge the run against its scenario's SLO envelope "
        "(exit 2 on violation)",
    )
    rp.add_argument("--json", action="store_true",
                    help="print the SLO summary as JSON")
    rp.add_argument(
        "--prewarm",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="compile serving programs before replaying (byte-identity "
        "across repeated replays assumes a warmed engine)",
    )
    _add_tpu_flags(rp)
    rp.set_defaults(fn=cmd_replay)

    ch = sub.add_parser(
        "chaos",
        help="seeded chaos drill: a deterministic fault cocktail poured "
        "over a library scenario against an in-process replica fleet, "
        "with exactly-once/conservation invariants judged at the end",
    )
    ch.add_argument("--seed", type=int, default=0,
                    help="schedule seed (same seed = same fault schedule)")
    ch.add_argument(
        "--scenario", default="persona_storm",
        help="library scenario to replay under the cocktail",
    )
    ch.add_argument(
        "--set", action="append", default=[], metavar="K=V", dest="overrides",
        help="scenario generator kwarg override, repeatable (e.g. --set n=24)",
    )
    ch.add_argument("--replicas", type=int, default=3,
                    help="fleet size: in-process engine replicas")
    ch.add_argument("--speed", type=float, default=10.0,
                    help="virtual-time compression for arrivals AND faults")
    ch.add_argument(
        "--hedge-after-s", type=float, default=0.5, dest="hedge_after_s",
        help="router hedge threshold in seconds; 0 disables hedged "
        "re-dispatch (health observation stays on either way)",
    )
    ch.add_argument("--gate", action="store_true",
                    help="exit 2 when an invariant tripped")
    ch.add_argument("--json", action="store_true",
                    help="print the chaos report as JSON")
    ch.add_argument(
        "--prewarm",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="compile serving programs on every replica before the drill",
    )
    _add_tpu_flags(ch)
    ch.set_defaults(fn=cmd_chaos)

    tr = sub.add_parser("train", help="LoRA fine-tune a checkpoint on a JSONL dataset")
    tr.add_argument("--checkpoint", required=True, help="HF checkpoint dir")
    tr.add_argument("--data", required=True, help="JSONL: {text} or {messages} lines")
    tr.add_argument("--out", required=True, help="adapter output dir")
    tr.add_argument("--steps", type=int, default=100)
    tr.add_argument("--batch", type=int, default=4)
    tr.add_argument("--seq-len", type=int, default=512)
    tr.add_argument("--rank", type=int, default=8)
    tr.add_argument("--alpha", type=float, default=16.0)
    tr.add_argument("--targets", default="wq,wk,wv,wo")
    tr.add_argument("--lr", type=float, default=1e-4)
    tr.add_argument("--tp", type=int, default=1, help="shard the frozen base over tp chips")
    tr.add_argument(
        "--mask-prompt",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="supervise only assistant turns of {messages} rows (SFT masking)",
    )
    tr.add_argument("--seed", type=int, default=0)
    tr.set_defaults(fn=cmd_train)

    chat = sub.add_parser("chat", help="interactive chat with the TPU engine (SSE)")
    chat.add_argument("--system", default="")
    chat.add_argument("--max-tokens", type=int, default=256)
    chat.add_argument("--temperature", type=float, default=0.7)
    chat.set_defaults(fn=cmd_chat)

    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
