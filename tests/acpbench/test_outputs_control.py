"""The output check at a size a test run can hold: sound runs pass, and
the controls come out as not correct. On the chip the same readings were
made at the cells' own size (PERF.md section 2).

Controls: for `logit_rel_rms`, the reference itself computed with every
matmul input and K and V rounded to float8 e4m3 (the precision below
bfloat16); for `cache_excess`, the program's own lower-precision path,
int8 KV pages (`quantize_kv`); for `greedy_regret`, the reference itself
reading a page that holds another request's tokens."""

import functools
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from acpbench import check, spec, study
from acpbench import run as runner
from acpbench.families import llama_weights

DATA = os.path.join(os.path.dirname(__file__), "data")
SEEDS = (3, 2**31 + 4, 5)
# this size's own limits, from its own readings over eight seeds (sound
# logit_rel_rms <= 0.014, fp8 >= 0.096; sound cache_excess <= 0, int8 pages >= 0.08;
# sound greedy_regret <= 0.018, a swapped page >= 1.7)
LIMITS = {"logit_rel_rms": 0.04, "cache_excess": 0.04}
PATH_LIMITS = {"greedy_regret": 0.2, "stream_mismatch": 0}


@pytest.fixture(scope="module")
def config():
    return spec.load_json(os.path.join(DATA, "tiny-config.json"))


@pytest.fixture(scope="module", params=SEEDS)
def case(request, config):
    seed = request.param
    llama, mesh, params = study._engine_free_system(config, seed)
    s = check.sample(config["check"], config["vocab_size"], config["engine"]["page_size"], seed)
    want = check.reference_logits(_reference(config, params), s)
    return config, llama, mesh, params, s, want


def _reference(config, params):
    return functools.partial(spec.family(config).reference_logits, config, params)


def _program(case, **kw):
    config, llama, mesh, params, s, want = case
    got = spec.family(config).cached_logits(config, llama, params, mesh, s, False, **kw)
    return check.compare(got, want)


def test_sound_program_is_correct(case):
    ok, lines = check.decide(_program(case), LIMITS)
    assert ok, lines


def test_int8_pages_are_not_correct(case):
    numbers = _program(case, quantize_kv=True)
    ok, lines = check.decide(numbers, LIMITS)
    assert not ok and numbers["cache_excess"] > LIMITS["cache_excess"], lines


def test_fp8_matmuls_are_not_correct(case):
    config, llama, mesh, params, s, want = case
    got = check.reference_logits(_reference(config, params), s, lower="fp8")
    numbers = check.compare(got, want)
    ok, lines = check.decide(numbers, LIMITS)
    assert not ok and numbers["logit_rel_rms"] > LIMITS["logit_rel_rms"], lines


@pytest.fixture(scope="module", params=SEEDS)
def path_case(request, config):
    """Greedy requests through the engine's own submit, at this seed."""
    from acpbench.systems.engine import System

    seed = request.param
    system = System(config, seed)
    try:
        s = check.sample(config["check"], config["vocab_size"], config["engine"]["page_size"], seed)
        path = check.engine_path(system, s, config["check"]["engine_tokens"])
        return _reference(config, system.params), s, path
    finally:
        system.stop()


def test_the_engines_own_tokens_are_the_references_choice(path_case):
    reference, s, path = path_case
    numbers = dict(check.engine_numbers(reference, s, path), finite=True)
    ok, lines = check.decide(numbers, PATH_LIMITS)
    assert ok and numbers["engine_tokens"] == s["B"] * path["budget"], lines


def test_a_page_of_another_requests_tokens_is_not_correct(path_case):
    reference, s, path = path_case
    numbers = dict(check.engine_numbers(reference, s, path, control=True), finite=True)
    ok, lines = check.decide(numbers, PATH_LIMITS)
    assert not ok and numbers["greedy_regret"] > PATH_LIMITS["greedy_regret"], lines


@pytest.mark.parametrize("fault", ["dropped", "doubled", "short"])
def test_a_token_lost_between_stream_and_result_is_not_correct(path_case, fault):
    reference, s, path = path_case
    broken = dict(path, streamed=[list(t) for t in path["streamed"]], returned=[list(t) for t in path["returned"]])
    if fault == "dropped":
        del broken["streamed"][1][3]
    elif fault == "doubled":
        broken["streamed"][2].insert(4, broken["streamed"][2][4])
    else:  # the request ended under its budget, on no stop token
        del broken["streamed"][0][-1], broken["returned"][0][-1]
    numbers = dict(check.engine_numbers(reference, s, broken), finite=True)
    ok, lines = check.decide(numbers, PATH_LIMITS)
    assert not ok and numbers["stream_mismatch"] == 1, lines


def test_a_request_that_stops_early_is_no_fault(path_case):
    """The engine hands over no stop token: a request that random weights
    end early has fewer tokens, or none, and the rest are still judged."""
    reference, s, path = path_case
    early = dict(path, streamed=[[], path["streamed"][1][:3]] + path["streamed"][2:],
                 returned=[[], path["returned"][1][:3]] + path["returned"][2:],
                 finish=["stop", "stop"] + path["finish"][2:])
    numbers = dict(check.engine_numbers(reference, s, early), finite=True)
    ok, lines = check.decide(numbers, PATH_LIMITS)
    assert ok and numbers["engine_tokens"] == 3 + 2 * path["budget"], lines


def test_a_dropped_bias_is_not_correct(case):
    """The q, k and v biases are drawn non-zero so that this shows."""
    config, llama, mesh, params, s, want = case
    got = check.reference_logits(_reference(config, params), s, lower="nobias")
    numbers = check.compare(got, want)
    ok, lines = check.decide(numbers, LIMITS)
    assert not ok and numbers["logit_rel_rms"] > 2 * LIMITS["logit_rel_rms"], lines


def test_a_non_finite_logit_is_not_correct():
    ok, _ = check.decide({"finite": False, "logit_rel_rms": 0.0, "cache_excess": 0.0}, LIMITS)
    assert not ok


def test_reference_is_the_programs_function_in_float32(config):
    """Independent code, same mathematics: against the program's own
    full-sequence forward pass run in float32, the reference agrees to
    float32 rounding."""
    import dataclasses

    from agentcontrolplane_tpu.models.llama import forward
    from agentcontrolplane_tpu.parallel.mesh import make_mesh

    family = spec.family(config)
    llama = dataclasses.replace(family.program_config(config), dtype=jnp.float32)
    params = family.weights(config, llama, make_mesh({"tp": 1}, devices=jax.devices()[:1]), 7)
    tokens = np.random.default_rng(0).integers(0, config["vocab_size"], size=(2, 24)).astype(np.int32)
    rows = np.tile(np.arange(24), (2, 1))
    with jax.default_matmul_precision("highest"):
        theirs = forward(params, jnp.asarray(tokens), llama)
    ours = family.reference_logits(config, params, tokens, rows)
    assert float(jnp.max(jnp.abs(ours - theirs))) < 1e-4 * float(jnp.max(jnp.abs(theirs)))


def test_weights_are_the_seeds(config):
    from agentcontrolplane_tpu.parallel.mesh import make_mesh

    family = spec.family(config)
    llama = family.program_config(config)
    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1])
    a, b, c = (family.weights(config, llama, mesh, s) for s in (2**31 + 9, 2**31 + 9, 9))
    same = jax.tree_util.tree_map(lambda x, y: bool(jnp.all(x == y)), a, b)
    assert all(jax.tree_util.tree_leaves(same))
    assert not bool(jnp.all(a["layers"]["w1"].q == c["layers"]["w1"].q))
    q = a["layers"]["w1"].q
    assert q.dtype == jnp.int8 and int(q.min()) >= -127 and a["embed"].dtype == llama.dtype
    assert abs(float(jnp.std(q.astype(jnp.float32))) - llama_weights.UNIFORM_INT8_STD) < 2.0


def test_the_weight_precision_is_the_files(config):
    """`engine.quantize` is read by the family and by `Engine`; the llama
    family draws int8 and says so of anything else."""
    family = spec.family(config)
    bf16 = dict(config, engine={k: v for k, v in config["engine"].items() if k != "quantize"})
    with pytest.raises(ValueError, match="int8 weights only"):
        family.weights(bf16, family.program_config(bf16), None, 1)
    with pytest.raises(ValueError, match="no control"):
        family.reference_logits(config, {}, [[0]], [[0]], lower="int4")


PINNED = spec.load_json(os.path.join(DATA, "pinned-parent.json"))


@pytest.fixture(scope="module", params=sorted(PINNED["seeds"], key=int))
def pinned(request, config):
    """One of the parent commit's seeds, built by this tree's harness."""
    from acpbench.systems.engine import System

    seed = int(request.param)
    system = System(config, seed)
    try:
        numbers = runner.output_numbers(system, config, seed)
        leaves = {jax.tree_util.keystr(k): np.asarray(v)
                  for k, v in jax.tree_util.tree_flatten_with_path(system.params)[0]}
        return PINNED["seeds"][request.param], leaves, numbers
    finally:
        system.stop()


def test_the_seeded_weights_are_the_parents(pinned):
    """Recorded from the parent commit (PR 28's `weights.make`) before the
    family seam was cut: the int8 leaves bit for bit, the float leaves'
    sums to 1e-3 of their absolute sums, so that another CPU's vector
    units cannot fail it."""
    want, leaves, _ = pinned
    assert set(leaves) == set(want["leaves"])
    for name, entry in want["leaves"].items():
        a = leaves[name]
        assert str(a.dtype) == entry["dtype"] and list(a.shape) == entry["shape"], name
        if "sha256" in entry:
            assert hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest() == entry["sha256"], name
        else:
            f = a.astype(np.float64)
            assert float(np.abs(f).sum()) == pytest.approx(entry["abs_sum"], rel=1e-3), name
            assert float(f.sum()) == pytest.approx(entry["sum"], abs=1e-3 * entry["abs_sum"]), name


def test_the_checks_numbers_are_the_parents(pinned):
    """What `check.compare` and `engine_numbers` printed at the parent
    commit for this seed, to 1e-3 relative; counts exactly."""
    want, _, numbers = pinned
    assert set(numbers) == set(want["numbers"])
    for name, value in want["numbers"].items():
        if isinstance(value, (bool, int)):
            assert numbers[name] == value, name
        else:
            assert numbers[name] == pytest.approx(value, rel=1e-3), name
