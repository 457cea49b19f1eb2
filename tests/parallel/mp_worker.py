"""Worker for the real multi-process jax.distributed test.

Invoked as: python mp_worker.py <process_id> <num_processes> <coordinator_port>

Each process contributes 2 virtual CPU devices; together they form the
dp(across processes) x tp(within process) global mesh and run two identical
train steps on a deterministic batch, printing the losses as JSON.
"""

import json
import os
import sys

pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

# deterministic compiles across ranks (see mp_serve_worker.py): a cache
# hit on one rank + fresh compile on the other can decompose collectives
# differently and abort gloo mid-run
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np
import optax

from agentcontrolplane_tpu.models.llama import PRESETS
from agentcontrolplane_tpu.parallel.distributed import (
    global_mesh,
    initialize_distributed,
    runtime_info,
)
from agentcontrolplane_tpu.train.trainer import Trainer


def main() -> None:
    initialize_distributed(f"localhost:{port}", nproc, pid)
    info = runtime_info()
    mesh = global_mesh({"dp": 2, "tp": 2})

    cfg = PRESETS["tiny"]
    trainer = Trainer(config=cfg, mesh=mesh, optimizer=optax.adam(1e-3))
    params, opt_state = trainer.init(jax.random.key(0))

    # deterministic GLOBAL batch; every process materializes the same array
    # and hands JAX its addressable shards
    rng = np.random.RandomState(7)
    global_tokens = rng.randint(1, cfg.vocab_size, size=(4, 32)).astype(np.int32)
    global_mask = np.ones_like(global_tokens)

    def put(arr):
        return jax.make_array_from_callback(
            arr.shape, trainer.batch_sharding, lambda idx: arr[idx]
        )

    tokens, mask = put(global_tokens), put(global_mask)
    losses = []
    for _ in range(2):
        params, opt_state, loss = trainer.train_step(params, opt_state, tokens, mask)
        losses.append(float(loss))
    print(json.dumps({"losses": losses, "info": info}), flush=True)


if __name__ == "__main__":
    main()
