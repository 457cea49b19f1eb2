"""The system under test: the program's engine alone, built from a
configuration's file with the benchmark's weights. The model's config and
weights come from the family the file names; the engine's options, the
weight precision (`quantize`) among them, from the file's `engine` block."""

from __future__ import annotations

import time

from .. import loadgen, spec


class System:
    """`submit(request, on_tokens) -> Future` over `Engine.submit`."""

    def __init__(self, config: dict, seed: int):
        import jax

        from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
        from agentcontrolplane_tpu.parallel.mesh import make_mesh

        self._sampling = SamplingParams
        opts = dict(config["engine"])
        tp = opts.pop("tensor_parallelism", 1)
        self.family = spec.family(config)
        self.program_config = self.family.program_config(config)
        self.mesh = make_mesh({"tp": tp}, devices=jax.devices()[:tp])
        t0 = time.monotonic()
        self.params = self.family.weights(config, self.program_config, self.mesh, seed)
        jax.block_until_ready(self.params)
        self.weights_s = time.monotonic() - t0
        for key in ("prefill_buckets", "width_buckets"):
            if key in opts:
                opts[key] = tuple(opts[key])
        self.engine = Engine(config=self.program_config, params=self.params, mesh=self.mesh,
                             seed=seed & 0x7FFFFFFF, tokenizer=tokenizer(config), **opts)
        self.engine.start()

    def submit(self, request: dict, on_tokens):
        sampling = self._sampling(temperature=request.get("temperature", 0.0),
                                  max_tokens=request["max_tokens"])
        return self.engine.submit(request["prompt"], sampling, on_tokens=on_tokens)

    def cancel(self, future) -> None:
        self.engine.cancel(future)

    def stats(self) -> dict:
        return self.engine.stats()

    def held(self):
        """While held, what is submitted waits, and on release the engine
        admits it as one group (the program's own `hold_admission`, which
        its prewarm uses to the same end)."""
        return self.engine.hold_admission()

    def prewarm(self) -> None:
        self.engine.prewarm()

    def drive(self, plan: dict, t0: float, stop_at: float, drain_limit_s: float) -> list:
        return loadgen.DRIVERS[plan["mode"]](self, plan, t0, stop_at, drain_limit_s)

    def stop(self) -> None:
        self.engine.stop()


def tokenizer(config: dict):
    """The program's byte tokenizer; with `ignore_stop_tokens`, one that
    names no stop token, so that every answer runs to the length the mix
    gives it. Random weights sample a stop token about once in 76,000
    tokens, a run of the saturated cell samples 43,000, and an answer cut
    short shifts every later admission: runs whose seeds drew one or two
    read up to 1% fewer tokens per second than runs that drew none (my
    chip runs, PR 25). The mix's answer lengths are the model of where a
    served model would stop."""
    from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer

    if not config.get("ignore_stop_tokens"):
        return ByteTokenizer()

    class NoStopTokens(ByteTokenizer):
        stop_tokens = frozenset()

    return NoStopTokens()


class CompileCounter:
    """Counts the programs JAX compiles or loads from its cache: every
    first use of a shape, whether or not the persistent cache had it."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.count += 1
