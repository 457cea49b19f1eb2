"""Worker for the real multi-process coordinated SERVING test.

Invoked as:
  python mp_serve_worker.py <pid> <nproc> <jax_port> <coord_port>

Rank 0 runs the leader engine over the 2-process global tp mesh, submits
three greedy prompts, and prints their tokens; rank 1 runs a follower that
replays the broadcast admission frames and joins the same global
dispatches. With nproc=1 it runs the single-process reference (no
coordination, all devices local).
"""

import json
import os
import sys

pid, nproc, jax_port, coord_port = (
    int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4]
)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

# The persistent compile cache holds XLA:CPU AOT entries compiled on other
# machines (the cpu_aot_loader machine-feature warnings). If one rank loads
# a cached executable while the other recompiles fresh, their collective
# DECOMPOSITIONS can differ -> gloo "received data size doesn't match"
# aborts mid-run. Multi-process CPU workers must compile deterministically.
jax.config.update("jax_enable_compilation_cache", False)

import dataclasses

from agentcontrolplane_tpu.engine.coordination import (
    CoordinationFollower,
    CoordinationLeader,
)
from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
from agentcontrolplane_tpu.models.llama import PRESETS
from agentcontrolplane_tpu.parallel.distributed import global_mesh, initialize_distributed

CFG = dataclasses.replace(
    PRESETS["tiny"], n_heads=4, n_kv_heads=4, vocab_size=512
)
PROMPTS = ["hello world", "bb", "coordinated serving"]


def build_engine(mesh, coordination):
    return Engine(
        config=CFG,
        tokenizer=ByteTokenizer(),
        mesh=mesh,
        max_slots=4,
        max_ctx=64,
        prefill_buckets=(32, 64),
        decode_block_size=4,
        prefix_cache_entries=0,
        seed=0,
        coordination=coordination,
    )


def main() -> None:
    if nproc > 1:
        initialize_distributed(f"localhost:{jax_port}", nproc, pid)
    # tp over every global device: 2 procs x 2 local = tp4; the
    # single-process reference runs with 4 local devices = the same tp4
    mesh = global_mesh({"tp": len(jax.devices())})

    if nproc == 1:
        coordination = None
    elif pid == 0:
        coordination = CoordinationLeader(
            bind=f"127.0.0.1:{coord_port}", token="mp-secret"
        )
        coordination.wait_for_followers(nproc - 1, timeout=120.0)
    else:
        coordination = CoordinationFollower(
            f"127.0.0.1:{coord_port}", rank=pid, token="mp-secret"
        )

    engine = build_engine(mesh, coordination)
    engine.start()
    try:
        if pid == 0:
            futs = [
                engine.submit(
                    list(ByteTokenizer().encode(p)),
                    SamplingParams(temperature=0.0, max_tokens=8),
                )
                for p in PROMPTS
            ]
            tokens = [f.result(timeout=300).tokens for f in futs]
            print(json.dumps({"tokens": tokens}), flush=True)
        else:
            # follower: serve until the leader's stop frame ends the loop.
            # NO timeout here: giving up early would stop this engine
            # mid-stream and desynchronize the ranks' dispatch sequences
            # (the leader always publishes stop in its finally; a dead
            # leader surfaces via the recv timeout crashing the loop).
            engine._thread.join()
            print(json.dumps({"follower": "done"}), flush=True)
    finally:
        engine.stop()
        if nproc > 1:
            # exit barrier: a rank tearing its runtime down while the other
            # still has the final decode block's collectives in flight
            # aborts gloo mid-transfer; align both ranks after their engine
            # loops have fully drained before any process exits
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("acp-serve-exit")
            jax.distributed.shutdown()
        if coordination is not None:
            coordination.close()


if __name__ == "__main__":
    main()
