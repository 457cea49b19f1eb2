"""One greedy request's tokens do not depend on the shape it was served in.

The engine pads every dispatch to a compiled shape: the decode width that
covers the live slots, the prefill bucket that covers the longest prompt of
an admission group, the group's size, the decode block's step count. The
benchmark's ``correct`` reaches one of each (prefill bucket 256, width 8,
groups of four: PERF.md section 2, "What ``correct`` does not reach"), so
here one prompt goes through every other one, in every model family, and
its tokens are held to the model's own full forward (no cache, no state,
no padding to a shape). Each case also reads back, from the profiler's
program keys or the flight record, that the shape it names is the shape
that ran.

CPU, float32, the paged layout, seeded weights. Identity of tokens is the
bar, as in the byte-identity matrices of test_chunked_prefill.py and
test_megastep.py: at these sizes every family gives it (a near-tie in the
logits could flip a token under another padding; none does for this probe).
"""

import functools
import time

import jax
import numpy as np
import pytest

from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
from agentcontrolplane_tpu.models import jamba, kanana, lfm2, llama, mellum, preset
from agentcontrolplane_tpu.parallel.mesh import make_mesh
from agentcontrolplane_tpu.testing import greedy_reference

FAMILIES = {"llama": ("tiny", llama), "lfm2": ("lfm2-tiny", lfm2), "jamba": ("jamba-tiny", jamba),
            # the probe's 44 tokens cross mellum-tiny's window of 32 inside its decode blocks
            "mellum": ("mellum-tiny", mellum),
            # expanded prefill and absorbed decode through a one-leaf pool
            "kanana": ("kanana-tiny", kanana)}
N_TOKENS = 24
MAX_CTX = 128
GREEDY = SamplingParams(temperature=0.0, max_tokens=N_TOKENS)
# outlive the probe, so the width it decodes at holds until it is done
NEIGHBOUR = SamplingParams(temperature=0.0, max_tokens=2 * N_TOKENS + 12)
_rng = np.random.default_rng(7)
PROBE = [int(t) for t in _rng.integers(0, 256, 20)]
OTHERS = [[int(t) for t in _rng.integers(0, 256, n)] for n in (17, 23, 29, 12, 26, 21, 15)]
BASE = dict(max_slots=8, width_buckets=(2, 4), prefill_buckets=(32, 64, 128), decode_block_size=4)
SMALL = dict(max_slots=2, width_buckets=(2,))  # where only the probe is served


class Family:
    """One family's weights, its engines by their options (built when a
    case first asks, stopped with the module), and the reference tokens."""

    def __init__(self, name):
        preset_name, self.model = FAMILIES[name]
        self.config = preset(preset_name)
        self.params = self.model.init_params(self.config, jax.random.key(0))
        self.engines = {}

    def engine(self, **kw):
        options = {**BASE, **kw}
        key = tuple(sorted(options.items()))
        if key not in self.engines:
            eng = Engine(
                config=self.config, params=self.params,
                mesh=make_mesh({"tp": 1}, devices=jax.devices()[:1]),
                max_ctx=MAX_CTX, kv_layout="paged", page_size=8, prefix_cache_entries=0,
                check_invariants=True, **options,
            )
            eng.start()
            self.engines[key] = eng
        return self.engines[key]

    @functools.cached_property
    def reference(self):
        return greedy_reference(self.model.forward, self.params, self.config, PROBE, N_TOKENS, MAX_CTX)

    def close(self):
        for eng in self.engines.values():
            eng.stop()


@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    f = Family(request.param)
    yield f
    f.close()


def dispatches(eng, key):
    return eng.stats()["perf"]["programs"].get(key, {}).get("dispatches", 0)


@pytest.mark.parametrize("neighbours,width", [(0, 2), (2, 4), (6, 8)])
def test_tokens_do_not_depend_on_the_decode_width(family, neighbours, width):
    eng = family.engine()
    running = [eng.submit(p, NEIGHBOUR) for p in OTHERS[:neighbours]]
    deadline = time.monotonic() + 300
    while eng.stats()["active_slots"] < neighbours:  # decoding, each in its slot
        assert time.monotonic() < deadline
        time.sleep(0.005)
    seen = len(eng.flight.events(kind="decode_block"))
    tokens = eng.submit(PROBE, GREEDY).result(300).tokens
    widths = [e["detail"]["width"] for e in eng.flight.events(kind="decode_block")[seen:]]
    assert all(not f.done() for f in running), "a neighbour ended before the probe"
    for f in running:
        f.result(300)
    assert tokens == family.reference
    # the neighbours alone may decode a block narrower before the probe joins
    assert len(widths) >= N_TOKENS // 4 and max(widths) == width, widths


@pytest.mark.parametrize("buckets,bucket", [((32, 64, 128), 32), ((64, 128), 64), ((128,), 128)])
def test_tokens_do_not_depend_on_the_prefill_bucket(family, buckets, bucket):
    eng = family.engine(**SMALL, prefill_buckets=buckets)
    key = f"prefill[paged,{bucket}x1]"
    before = dispatches(eng, key)
    assert eng.generate(PROBE, GREEDY).tokens == family.reference
    assert dispatches(eng, key) == before + 1, eng.stats()["perf"]["programs"].keys()


@pytest.mark.parametrize("group", [1, 2, 4, 8])
def test_tokens_do_not_depend_on_the_prefill_group(family, group):
    eng = family.engine()
    key = f"prefill[paged,32x{group}]"
    before = dispatches(eng, key)
    with eng.hold_admission():
        futures = [eng.submit(p, GREEDY) for p in [PROBE] + OTHERS[: group - 1]]
    tokens = [f.result(300).tokens for f in futures]
    assert tokens[0] == family.reference
    assert dispatches(eng, key) == before + 1, eng.stats()["perf"]["programs"].keys()


@pytest.mark.parametrize("block", [4, 8, 16])
def test_tokens_do_not_depend_on_the_decode_block(family, block):
    eng = family.engine(**SMALL, decode_block_size=block)
    key = f"decode[paged,2x{block}]"
    before = dispatches(eng, key)
    assert eng.generate(PROBE, GREEDY).tokens == family.reference
    assert dispatches(eng, key) >= before + N_TOKENS // block
