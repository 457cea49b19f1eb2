"""Test harness.

- Forces JAX onto a virtual 8-device CPU mesh (multi-chip sharding tests run
  without TPU hardware, per the driver contract).
- Native asyncio test support (async def tests run via asyncio.run).
- Shared builder fixtures live in agentcontrolplane_tpu.testing (shipped in
  the package so they import without tests/); tests/fixtures.py re-exports.
"""

import asyncio
import inspect
import os

# Must be set before the jax backend initializes (worker subprocesses
# inherit both). ACP_TEST_TPU=1 leaves the platform to jax, for
# tests/engine/test_tpu_hardware.py on a machine with a chip.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
# XLA:CPU's concurrency-optimized schedule lets each virtual device take a
# program's independent collectives in its own order (a decode step's
# grammar-mask reduction beside its layer loop's all-reduce), and the
# in-process communicator then waits for ever: a tp=2 megastep aborted half
# the runs of tests/engine/test_megastep.py under six workers (PR 36; the
# same program, scheduled in program order, never did). The tests are of
# the engine, not of that scheduler.
if "xla_cpu_enable_concurrency_optimized_scheduler" not in flags:
    flags += " --xla_cpu_enable_concurrency_optimized_scheduler=false"
if not os.environ.get("ACP_TEST_TPU"):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
# On the CPU only (never with ACP_TEST_TPU, where the programs are the
# chip's): the tests' programs are tiny and nearly all of a run is XLA:CPU
# compiling them, and LLVM's optimisation passes were a quarter of the
# suite's wall time (936 -> 706 s at six workers, the same tests passing:
# PR 50). What the byte-identity and tolerance cases then hold is the
# unoptimised CPU program: the same HLO, the same tolerances. The compiler
# for a described TPU (tests/engine/test_chip_compile.py) does not read the
# flag: its program text is the same byte for byte with it and without.
if os.environ.get("JAX_PLATFORMS") == "cpu" and "xla_backend_optimization_level" not in flags:
    flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = flags

import pytest


def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(fn(**kwargs))
        return True
    return None


@pytest.fixture
def store():
    from agentcontrolplane_tpu.kernel import Store

    return Store()
