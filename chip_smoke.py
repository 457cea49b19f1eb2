#!/usr/bin/env python3
"""chip_smoke.py — does `provider: tpu` still start on the chip?

Drives the serving path once, through the entry points a user calls, at
the full published width and depth of Qwen2.5-7B (28 layers, dim 3,584,
28/4 heads of 128, ffn 18,944, vocabulary 152,064) with int8 weights made
from a seed. One process; no argument = one chip:

  device   jax.devices() must be a TPU (a CPU fallback is a failure)
  kernel   the COMPILED Pallas page walk vs the XLA gather reference on the
           device, Qwen2.5-7B geometry, bf16 pages and int8 pages + scales;
           the latent walk; a verify step's two rows a lane over pages and ring;
           the routed experts' grouped matmul vs ragged_dot at its widest layer and under long dead bounds
  serve    slot layout (the CLI default), then paged: the engine built the
           way `acp-tpu run --tpu-preset qwen2.5-7b --tpu-quantize-weights`
           builds it, prewarmed, behind the real Operator + REST server on
           a socket; concurrent /v1/chat/completions (one streamed, one
           grammar-masked) and one LLM + Agent + Task through /v1/tasks
  cache    where compiled programs persist, and both engines' compile time

`--chips 4` runs ONLY the sharded path and what it is compared with: the
same weights on one chip and over serving_mesh(tensor_parallelism=4), both
behind a paged engine.

Every phase prints its own lines; any failure exits non-zero at once. The
last line of stdout is one JSON object: {"ok": ..., "device": {...}}.
Seconds printed here are smoke timings (set-up / compile / serve of one
cold process), not benchmark metrics.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import sys
import time
import traceback

PRESET = "qwen2.5-7b"
# Sized so a cold run (no compiled program anywhere) fits the 1200 s limit:
# 4 slots x 512 context is 3 decode widths and 4 prefill buckets, ~18
# programs per layout. Width and depth are the model's own.
SLOTS, CTX = 4, 512
ENGINE_FLAGS = [
    "run", "--tpu-preset", PRESET, "--tpu-quantize-weights",
    "--tpu-slots", str(SLOTS), "--tpu-ctx", str(CTX), "--port", "0",
]
TASK_PHASES_DONE = ("FinalAnswer", "Failed")

DEVICE: dict | None = None  # as jax reports it; filled by the device phase


class SmokeFailure(Exception):
    pass


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def check(cond: bool, phase: str, msg: str) -> None:
    if not cond:
        raise SmokeFailure(f"{phase}: {msg}")


# -- device ------------------------------------------------------------------


def phase_device(chips: int):
    global DEVICE
    import jax

    devices = jax.devices()
    DEVICE = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    say("device", f"jax {jax.__version__}: {DEVICE}")
    check(DEVICE["platform"] == "tpu", "device",
          f"no TPU: jax found platform {DEVICE['platform']!r}; this is not a CPU smoke")
    check(len(devices) == chips, "device",
          f"--chips {chips} needs exactly {chips} device(s), jax sees {len(devices)}")
    return devices


def phase_cache_open() -> dict:
    from agentcontrolplane_tpu import xla_cache

    armed = xla_cache.enable_persistent_compilation_cache()
    path = xla_cache.cache_dir()
    placed = (
        "JAX_COMPILATION_CACHE_DIR (set from outside; no directory set in code)"
        if os.environ.get("JAX_COMPILATION_CACHE_DIR")
        else "the fixed in-checkout path"
    )
    entries = cache_entries(path)
    say("cache", f"persistent compile cache armed={armed} at {path} — {placed}; "
                 f"{entries} entries at start ({'warm' if entries else 'cold'})")
    return {"path": path, "entries_at_start": entries}


def cache_entries(path: str | None) -> int:
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


# -- kernel ------------------------------------------------------------------


def phase_kernel(seed: int) -> None:
    import jax.numpy as jnp

    from agentcontrolplane_tpu.models.llama import PRESETS
    from agentcontrolplane_tpu.engine.kernel_parity import (
        EXPERT_CASES, expert_matmul_parity, latent_walk_parity, make_latent_case, make_paged_case, make_verify_case, page_walk_parity,
        verify_walk_parity)

    c = PRESETS[PRESET]
    geometry = dict(S=16, H=c.n_heads, H_kv=c.n_kv_heads, d=c.head_dim,
                    P=16, max_pages=32, num_pages=1024)
    for name, int8 in (("bf16 pages", False), ("int8 pages + f32 scale twins", True)):
        t0 = time.monotonic()
        got = page_walk_parity(
            make_paged_case(seed, dtype=jnp.bfloat16, int8=int8, **geometry)
        )
        lens = got["seq_lens"]
        say("kernel", f"compiled page walk vs XLA reference, {PRESET} geometry "
                      f"({c.n_heads}/{c.n_kv_heads} heads, d {c.head_dim}, page 16), {name}: "
                      f"out {got['shape']} finite={got['finite']} "
                      f"max|err| {got['max_abs_err']:.2e} (tolerance {got['tolerance']:.0e}), "
                      f"ragged seq_lens {min(lens)}..{max(lens)}, "
                      f"{time.monotonic() - t0:.1f}s compile+run")
        check(got["ok"], "kernel", f"{name}: parity failed: {got}")
    t0 = time.monotonic()
    got = latent_walk_parity(make_latent_case(seed))
    lens = got["seq_lens"]
    say("kernel", f"compiled latent walk vs XLA reference (a pool of one leaf: 32 heads on a row of 640, value 512, "
                  f"page 16, {got['pages_per_turn']} pages a turn), bf16 pages, unnamed pages NaN: "
                  f"out {got['shape']} finite={got['finite']} max|err| {got['max_abs_err']:.2e} "
                  f"(tolerance {got['tolerance']:.0e}), slots of {min(lens)}..{max(lens)} rows, "
                  f"{time.monotonic() - t0:.1f}s compile+run")
    check(got["ok"], "kernel", f"latent walk: parity failed: {got}")
    case = make_verify_case(seed)
    for name, window in (("the lanes' pages", False), ("their rings, each row from its own edge", True)):
        t0 = time.monotonic()
        got = verify_walk_parity(case, window=window)
        lens = got["seq_lens"]
        say("kernel", f"compiled verify walk vs XLA reference (two rows a lane in one query group: 64/8 heads, d 128, "
                      f"page 16, a window of 128) over {name}, bf16 pages, pages outside the walk NaN: "
                      f"out {got['shape']} finite={got['finite']} max|err| {got['max_abs_err']:.2e} "
                      f"(tolerance {got['tolerance']:.0e}), lanes of {min(lens)}..{max(lens)} rows, "
                      f"{time.monotonic() - t0:.1f}s compile+run")
        check(got["ok"], "kernel", f"verify walk over {name}: parity failed: {got}")
    for name, kw in EXPERT_CASES.items():
        t0 = time.monotonic()
        got = expert_matmul_parity(seed, **kw)
        say("kernel", f"compiled grouped matmul vs ragged_dot ({name}: {kw['tokens']} rows x {kw['k']} choices over "
                      f"{kw['held']} of {kw['experts']} experts, {kw['hidden']} x {kw['width']}), "
                      f"{got['live_tiles']} of {got['tiles']} row tiles live, an idle expert among them: "
                      f"out {got['shape']} finite={got['finite']} max|err| {got['max_abs_err']:.2e} of a largest "
                      f"{got['largest']:.2f} (tolerance {got['tolerance']:.2e}), {time.monotonic() - t0:.1f}s compile+run")
        check(got["ok"], "kernel", f"grouped matmul, {name}: parity failed: {got}")


# -- serve -------------------------------------------------------------------


def parse_metrics(text: str) -> dict[str, float]:
    """Prometheus text -> {family: sum over label sets}."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        family = name.split("{", 1)[0]
        try:
            out[family] = out.get(family, 0.0) + float(value)
        except ValueError:
            pass
    return out


def hbm_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("bytes_in_use", 0))


def hbm_gb(device) -> str:
    return f"{hbm_bytes(device) / 1e9:.2f} GB in use"


async def drive_rest(phase: str, base: str) -> dict:
    """The requests a user would send, over the socket."""
    import httpx

    async with httpx.AsyncClient(base_url=base, timeout=600.0) as http:
        t0 = time.monotonic()

        def chat(content: str, **extra) -> dict:
            return {"model": PRESET, "max_tokens": 24, "temperature": 0.0,
                    "messages": [{"role": "user", "content": content}], **extra}

        async def streamed() -> dict:
            done = finish = None
            chunks = 0
            async with http.stream(
                "POST", "/v1/chat/completions",
                json=chat("stream a few words about systolic arrays", stream=True),
            ) as resp:
                status = resp.status_code
                async for line in resp.aiter_lines():
                    if not line.startswith("data: "):
                        continue
                    data = line[len("data: "):]
                    if data == "[DONE]":
                        done = True
                        continue
                    doc = json.loads(data)
                    check("error" not in doc, phase, f"streamed error event: {doc}")
                    chunks += 1
                    finish = doc["choices"][0].get("finish_reason") or finish
            return {"status": status, "done": done, "finish": finish, "chunks": chunks}

        plain = [
            http.post("/v1/chat/completions", json=chat(f"say something, request {i}"))
            for i in range(3)
        ]
        masked = http.post(
            "/v1/chat/completions",
            json=chat("answer as a JSON object", response_format={"type": "json_object"},
                      temperature=0.7),
        )
        *plain_r, masked_r, stream_r = await asyncio.gather(*plain, masked, streamed())
        completion_tokens = 0
        for r in (*plain_r, masked_r):
            check(r.status_code == 200, phase, f"chat completion -> {r.status_code}: {r.text[:400]}")
            doc = r.json()
            finish = doc["choices"][0]["finish_reason"]
            check(finish in ("stop", "length", "tool_calls"), phase, f"finish_reason {finish!r}")
            completion_tokens += int(doc["usage"]["completion_tokens"])
        check(stream_r["status"] == 200 and stream_r["done"] and stream_r["finish"],
              phase, f"streamed completion incomplete: {stream_r}")
        check(completion_tokens > 0, phase, "the burst generated no tokens")
        masked_text = masked_r.json()["choices"][0]["message"].get("content") or ""
        say(phase, f"5 concurrent POST /v1/chat/completions (3 plain, 1 response_format="
                   f"json_object, 1 stream): all 200, completion_tokens {completion_tokens} "
                   f"(non-streamed), stream {stream_r['chunks']} chunks + [DONE] "
                   f"finish={stream_r['finish']}, grammar-masked reply {masked_text[:40]!r}, "
                   f"{time.monotonic() - t0:.1f}s serve")

        # one LLM(provider: tpu) + Agent + Task through the control plane
        t0 = time.monotonic()
        manifests = f"""
kind: LLM
metadata: {{name: smoke-tpu}}
spec:
  provider: tpu
  parameters: {{model: {PRESET}, temperature: 0.0, maxTokens: 16}}
  tpu: {{preset: {PRESET}, quantizeWeights: true, maxSequences: {SLOTS}, maxContext: {CTX}}}
---
kind: Agent
metadata: {{name: smoke-agent}}
spec:
  llmRef: {{name: smoke-tpu}}
  system: You are a smoke test.
"""
        r = await http.post("/v1/apply", content=manifests)
        check(r.status_code == 200, phase, f"/v1/apply -> {r.status_code}: {r.text[:400]}")
        deadline = time.monotonic() + 120
        while True:
            r = await http.get("/v1/resources/Agent/smoke-agent")
            if r.status_code == 200 and r.json().get("status", {}).get("ready"):
                break
            check(time.monotonic() < deadline, phase,
                  f"Agent never became ready: {r.status_code} {r.text[:400]}")
            await asyncio.sleep(0.2)
        r = await http.post("/v1/tasks", json={"agentName": "smoke-agent",
                                               "userMessage": "say hello"})
        check(r.status_code == 201, phase, f"/v1/tasks -> {r.status_code}: {r.text[:400]}")
        name = r.json()["name"]
        deadline = time.monotonic() + 300
        while True:
            task = (await http.get(f"/v1/tasks/{name}")).json()
            phase_now = task.get("phase")
            if phase_now in TASK_PHASES_DONE:
                break
            check(time.monotonic() < deadline, phase,
                  f"Task {name} stuck in phase {phase_now!r}")
            await asyncio.sleep(0.2)
        check(phase_now == "FinalAnswer", phase,
              f"Task ended in {phase_now!r}, not FinalAnswer: {json.dumps(task)[:600]}")
        say(phase, f"LLM(provider: tpu) + Agent + Task via /v1/apply and /v1/tasks: "
                   f"Task {name} phase {phase_now}, {time.monotonic() - t0:.1f}s")

        metrics = parse_metrics((await http.get("/metrics")).text)
        status = (await http.get("/v1/engine")).json()
    counters = {k: metrics.get(k, 0.0) for k in (
        "acp_engine_restarts_total", "acp_engine_crashes_total",
        "acp_engine_kernel_fallbacks_total", "acp_engine_tokens_total",
    )}
    say(phase, f"/metrics: {counters}; /v1/engine kv_layout={status.get('kv_layout')}")
    check(counters["acp_engine_restarts_total"] == 0, phase, "the engine restarted")
    check(counters["acp_engine_crashes_total"] == 0, phase, "the engine crashed")
    check(counters["acp_engine_kernel_fallbacks_total"] == 0, phase,
          "a kernel fell back to the XLA reference on the chip")
    check(counters["acp_engine_tokens_total"] > 0, phase, "no tokens counted")
    return counters


async def phase_serve(layout: str, device) -> dict:
    """Build the engine as `acp-tpu run` does, prewarm it as `run` does,
    put the real Operator + REST server in front, drive it, tear it down."""
    import jax

    from agentcontrolplane_tpu import cli
    from agentcontrolplane_tpu.operator import Operator, OperatorOptions

    phase = f"serve/{layout}"
    flags = ENGINE_FLAGS + (["--tpu-kv-layout", "paged"] if layout == "paged" else [])
    args = cli.build_parser().parse_args(flags)
    say(phase, f"acp-tpu {' '.join(flags)}  (cli._build_engine + EnginePrewarm, "
               f"{SLOTS} slots x {CTX} ctx; HBM before: {hbm_gb(device)})")
    t0 = time.monotonic()
    engine = cli._build_engine(args)
    engine.start()
    setup_s = time.monotonic() - t0
    say(phase, f"engine up: weights {engine.weight_bytes / 1e9:.2f} GB int8+scales on "
               f"device, HBM {hbm_gb(device)}, {setup_s:.1f}s set-up (host-built seeded weights)")
    if layout == "paged":
        check(engine._use_pallas, phase,
              "the paged engine did not take the Pallas page walk on a TPU")
        say(phase, "engine._use_pallas=True: decode walks pages with the compiled kernel, "
                   + ", ".join(f"{k}={engine.stats()['kv_pages'][k]}"
                               for k in ("pages_per_turn", "turns_in_flight", "bytes_in_flight")))
    t0 = time.monotonic()
    prewarm = cli.EnginePrewarm(engine)
    prewarm.start()
    op = Operator(OperatorOptions(
        api_port=args.port, api_host=args.host, engine=engine,
        identity=f"chip-smoke-{layout}",
    ))
    await op.start()
    try:
        while op.rest_server.bound_port is None:
            await asyncio.sleep(0.05)
        base = f"http://{args.host}:{op.rest_server.bound_port}"
        say(phase, f"operator + REST server listening on {base}; prewarm compiling")
        await asyncio.to_thread(prewarm.join)
        compile_s = time.monotonic() - t0
        check(prewarm.error is None, phase, f"prewarm failed: {prewarm.error!r}")
        prof = engine.profiler.stats()
        say(phase, f"prewarm done: {len(prof.get('programs', {}))} program shapes "
                   f"dispatched, {compile_s:.1f}s compile (prewarm wall, incl. its own "
                   f"generations), HBM {hbm_gb(device)}")
        counters = await drive_rest(phase, base)
        check(await asyncio.to_thread(engine.ensure_running), phase,
              "engine not running after the drive")
        cold = engine.profiler.stats()["cold_compiles"]
        say(phase, f"cold compiles paid by requests after prewarm: {cold['serving']} "
                   f"{[e['program'] for e in cold['events']]}")
    finally:
        await op.stop()
        engine.stop()
    # one process holds the chip and two weight sets do not fit: this
    # engine's buffers must be GONE before the next one is built
    for leaf in jax.tree_util.tree_leaves((engine.params, engine.cache)):
        leaf.delete()
    del op, prewarm, engine
    gc.collect()
    left = hbm_bytes(device)
    say(phase, f"engine stopped and its buffers deleted; HBM after: {left / 1e9:.2f} GB in use")
    check(left < 1e9, phase, f"{left / 1e9:.2f} GB still held after the engine was dropped")
    return {"setup_s": setup_s, "compile_s": compile_s, **counters}


# -- four chips --------------------------------------------------------------


def quantized_shardings(mesh, config, params):
    """NamedShardings for an int8 params pytree: a QuantizedTensor's values
    take the matrix's spec; its [.., 1, out] scales keep only the output
    axis (the contraction axis they were reduced over has size 1)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from agentcontrolplane_tpu.ops.quant import QuantizedTensor
    from agentcontrolplane_tpu.parallel.mesh import param_shardings

    base = param_shardings(mesh, config, params)

    def expand(sharding, leaf):
        if isinstance(leaf, QuantizedTensor):
            spec = tuple(sharding.spec) + (None,) * (leaf.q.ndim - len(sharding.spec))
            scale = NamedSharding(mesh, P(*spec[:-2], None, spec[-1]))
            return QuantizedTensor(q=sharding, scale=scale)
        return sharding

    return jax.tree_util.tree_map(
        expand, base, params, is_leaf=lambda x: isinstance(x, NamedSharding)
    )


def per_device_bytes(tree) -> dict:
    import jax

    held: dict = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            held[shard.device] = held.get(shard.device, 0) + int(shard.data.nbytes)
    return held


def phase_four_chips(devices, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from agentcontrolplane_tpu import cli
    from agentcontrolplane_tpu.engine.engine import SamplingParams
    from agentcontrolplane_tpu.engine.weights import random_quantized_init
    from agentcontrolplane_tpu.models.llama import PRESETS, prefill_paged_batch
    from agentcontrolplane_tpu.parallel.mesh import make_mesh, serving_mesh

    phase = "tp4"
    config = PRESETS[PRESET]
    args = cli.build_parser().parse_args(
        ENGINE_FLAGS + ["--tpu-kv-layout", "paged", "--no-tpu-prewarm"]
    )
    t0 = time.monotonic()
    params = random_quantized_init(config, seed=seed)  # host-built, lands on device 0
    jax.block_until_ready(params)
    say(phase, f"host-built seeded int8 weights on {devices[0]}: "
               f"{sum(x.nbytes for x in jax.tree_util.tree_leaves(params)) / 1e9:.2f} GB, "
               f"{time.monotonic() - t0:.1f}s set-up")

    prompts = [f"four chips, prompt {i}: the quick brown fox" for i in range(SLOTS)]
    greedy = SamplingParams(temperature=0.0, max_tokens=16)
    tokens = np.zeros((1, 64), dtype=np.int32)
    text = np.frombuffer(prompts[0].encode(), dtype=np.uint8)
    tokens[0, : len(text)] = text
    lengths = np.asarray([len(text)], dtype=np.int32)
    page_ids = np.arange(1, 1 + 64 // 16, dtype=np.int32)[None]

    def run_engine(label: str, mesh, placed) -> dict:
        t0 = time.monotonic()
        engine = cli._build_engine(args, params=placed, mesh=mesh)
        check(engine._use_pallas, phase, f"{label}: paged engine without the kernel")
        # first-token logits: the engine's own prefill program, its params,
        # its mesh, its page pool (not donated: the pool is left as it was)
        put = lambda a: jax.device_put(a, engine._replicated)  # noqa: E731
        logits = np.asarray(jax.jit(
            lambda p, pages, t, n, ids: prefill_paged_batch(p, pages, t, n, ids, config)[1]
        )(engine.params, engine.cache, put(tokens), put(lengths), put(page_ids))
         .astype(jnp.float32))[0]
        # the decode program the engine dispatches, captured at its first call
        seen: dict = {}
        real = engine._jit_decode_paged

        def capture(*a):
            if not seen:
                seen["args"] = jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), a
                )
            return real(*a)

        engine._jit_decode_paged = capture
        engine.start()
        try:
            futs = [engine.submit(p, greedy) for p in prompts]
            generated = [f.result(timeout=900).tokens for f in futs]
        finally:
            engine.stop()
        check(seen, phase, f"{label}: no decode block was dispatched")
        hlo = real.lower(*seen["args"]).compile().as_text()
        out = {
            "logits": logits, "generated": generated,
            "all_reduce": "all-reduce" in hlo, "kernel": "tpu_custom_call" in hlo,
            "weights": per_device_bytes(engine.params),
            "kv": per_device_bytes(engine.cache),
            "kv_shard": engine.cache["k"].addressable_shards[0].data.shape,
        }
        say(phase, f"{label}: mesh {dict(mesh.shape)}, weights/device "
                   f"{[round(b / 1e9, 2) for b in out['weights'].values()]} GB, KV pool/device "
                   f"{[round(b / 1e6, 1) for b in out['kv'].values()]} MB (shard {out['kv_shard']}), "
                   f"decode program: all-reduce={out['all_reduce']} "
                   f"tpu_custom_call={out['kernel']}, "
                   f"{sum(len(g) for g in generated)} greedy tokens, "
                   f"{time.monotonic() - t0:.1f}s compile+serve")
        check(out["kernel"], phase, f"{label}: no Pallas kernel in the decode program")
        check(np.isfinite(logits).all(), phase, f"{label}: non-finite logits")
        return out

    one = run_engine("one chip", make_mesh({"tp": 1}, devices=devices[:1]), params)
    mesh4 = serving_mesh(tensor_parallelism=4)
    t0 = time.monotonic()
    placed4 = jax.device_put(params, quantized_shardings(mesh4, config, params))
    jax.block_until_ready(placed4)
    del params
    gc.collect()
    say(phase, f"same weights placed over serving_mesh(tensor_parallelism=4) "
               f"(one KV head per chip) in {time.monotonic() - t0:.1f}s")
    four = run_engine("four chips", mesh4, placed4)

    total = sum(one["weights"].values())
    check(len(four["weights"]) == 4 and len(four["kv"]) == 4, phase,
          "weights or KV pool do not live on all four devices")
    for dev, held in four["weights"].items():
        share = held / total
        check(0.24 <= share <= 0.27, phase,
              f"{dev} holds {share:.3f} of weight_bytes, not about a quarter")
    kv_total = sum(one["kv"].values())
    for dev, held in four["kv"].items():
        check(abs(held / kv_total - 0.25) < 0.01, phase,
              f"{dev} holds {held / kv_total:.3f} of the KV pool, not a quarter")
    check(four["kv_shard"][3] == config.head_dim, phase,
          f"KV shard {four['kv_shard']} is not one head's {config.head_dim} lanes of the row per chip")
    check(four["all_reduce"], phase, "no all-reduce in the tp=4 decode program")
    check(not one["all_reduce"], phase, "all-reduce in the one-chip decode program")

    # stated tolerance: row-parallel matmuls re-associate bf16 partial sums
    # across chips; first-token logits agree within 3% of their own range
    scale = float(np.max(np.abs(one["logits"])))
    err = float(np.max(np.abs(one["logits"] - four["logits"])))
    agree = [
        a == b
        for g1, g4 in zip(one["generated"], four["generated"])
        for a, b in zip(g1, g4)
    ]
    say(phase, f"first-token logits [V={one['logits'].shape[0]}]: max|one - four| {err:.4f} "
               f"= {err / scale:.4f} of max|logit| {scale:.3f} (tolerance 0.03), "
               f"argmax {int(one['logits'].argmax())} vs {int(four['logits'].argmax())}; "
               f"greedy agreement {sum(agree)}/{len(agree)} = "
               f"{sum(agree) / max(1, len(agree)):.3f} of compared positions")
    check(err <= 0.03 * scale, phase, "tp=4 logits outside the stated tolerance")


# -- main --------------------------------------------------------------------


def run(chips: int, seed: int) -> None:
    devices = phase_device(chips)
    cache = phase_cache_open()
    if chips == 4:
        phase_four_chips(devices, seed)
        return
    phase_kernel(seed)
    slot = asyncio.run(phase_serve("slot", devices[0]))
    paged = asyncio.run(phase_serve("paged", devices[0]))
    say("cache", f"{cache['path']}: {cache['entries_at_start']} entries at start, "
                 f"{cache_entries(cache['path'])} now; compile seconds slot engine "
                 f"{slot['compile_s']:.1f}, paged engine {paged['compile_s']:.1f} "
                 f"(set-up {slot['setup_s']:.1f} / {paged['setup_s']:.1f})")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the tensor-parallel path and its one-chip comparison")
    ap.add_argument("--seed", type=int, default=0, help="weights and kernel operands")
    opts = ap.parse_args()
    ok = False
    t0 = time.monotonic()
    try:
        run(opts.chips, opts.seed)
        ok = True
    except SmokeFailure as e:
        print(f"FAILED {e}", file=sys.stderr, flush=True)
    except BaseException:
        traceback.print_exc()
    say("done", f"{'ok' if ok else 'FAILED'} in {time.monotonic() - t0:.1f}s")
    print(json.dumps({"ok": ok, "device": DEVICE}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    # hard exit: a wedged engine or server thread must not keep a failed
    # smoke (or the chip) alive past its verdict
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
