"""The device vocabulary over every family's programs, on the CPU at tiny
sizes: what each compiled instruction carries in its `op_name`, and that a
scope is metadata and nothing else.

`engine.make_decode_block` around a family's `decode_step_paged`, and the
engine's `prefill_and_sample` body around its `prefill_paged_batch`, are
lowered and compiled; the compiled text's `op_name` is what the profiler
records as an op's `tf_op` on the chip (`acpbench/device_scopes.py`).
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from agentcontrolplane_tpu import models
from agentcontrolplane_tpu.engine import engine
from agentcontrolplane_tpu.engine.lanes import DECODE, PREFILL
from agentcontrolplane_tpu.observability import scopes

SLOTS, PAGE, PAGES, CTX, BLOCK = 4, 16, 33, 128, 4
FAMILIES = ["tiny", "moe-tiny", "lfm2-tiny", "jamba-tiny", "mellum-tiny", "kanana-tiny", "nemotron-h-tiny", "keye-tiny"]

INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\S+) ([a-z][\w\-]*)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
SCOPE = re.compile(r"acp\.(\w+)")
# the compiler's own wiring: no traced op made these
WIRING = ("parameter", "get-tuple-element", "tuple", "constant", "bitcast", "while", "call", "conditional")
# ops that move or multiply a layer's data wherever they stand
HEAVY = ("dot", "gather", "scatter", "custom-call", "convolution")


@pytest.fixture(scope="module", autouse=True)
def fresh_compiles():
    """The persistent compile cache off around this file: jax keys a program
    without its locations, so a cache that holds the program under other
    scopes (an older tree's, or this file's own unscoped twin) would hand
    back an executable with THOSE names."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _operands(preset):
    config = models.preset(preset)
    family = models.programs(config)
    params = family.init_params(config, jax.random.key(0))
    cache = family.init_paged_cache(config, PAGES, PAGE, max_slots=SLOTS)
    aux = (jax.random.key(0), jnp.zeros((1, config.vocab_size), jnp.int32), jnp.zeros((1,), jnp.int32))
    return config, family, params, cache, aux


def decode_block(preset):
    """The engine's decode block around the family's step, lowered."""
    config, family, params, cache, (key, table, min_close) = _operands(preset)
    block = engine.make_decode_block(
        lambda p, ca, tok, n, active, tables: family.decode_step_paged(p, ca, tok, n, tables, active, config),
        (1,), CTX, BLOCK)
    lanes = jnp.zeros((len(DECODE.kinds), SLOTS), jnp.int32)
    return jax.jit(block).lower(params, cache, lanes, key, table, min_close, jnp.zeros((SLOTS, CTX // PAGE), jnp.int32))


def prefill(preset):
    """`Engine._build_jitted`'s `prefill_and_sample` body, lowered."""
    config, family, params, cache, (key, table, min_close) = _operands(preset)
    rows, bucket = 2, 32

    def prefill_and_sample(params, pages, tokens, lanes, page_ids, key, table, min_close):
        ln = PREFILL.unpack(lanes)
        ids = (page_ids, (ln["slots"], ln["snap_at"])) if family.has_state else page_ids
        pages, logits = family.prefill_paged_batch(params, pages, tokens, ln["lengths"], ids, config)
        return (pages, *engine.sample_lanes(logits, key, ln, table, min_close))

    return jax.jit(prefill_and_sample).lower(
        params, cache, jnp.zeros((rows, bucket), jnp.int32), jnp.ones((len(PREFILL.kinds), rows), jnp.int32),
        jnp.zeros((rows, bucket // PAGE), jnp.int32), key, table, min_close)


def instructions(text):
    """(name, result type, opcode, op_name or None) of each compiled instruction."""
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            named = OP_NAME.search(line)
            yield (*m.groups(), named.group(1) if named else None)


def top_levels(op_name):
    """The distinct `acp.*` layers on an op's path (a merged instruction
    carries several paths, `;` between them)."""
    return set(SCOPE.findall(op_name or ""))


def in_a_layer_loop(op_name, depth):
    """Traced by a layer's body: under `depth` nested loop bodies at least
    (the block's scan of steps is the first), and not a loop's own: the
    slicing, stacking and counting `lax.scan` emits beside the body it
    calls (`.../while/body/dynamic_slice`), the counter an inner loop runs
    over and the buffer its results are stacked in (`iota`,
    `broadcast_in_dim` straight under a body), or an instruction the
    compiler made for no traced op (the path ends at the call)."""
    first = op_name.split(";")[0]
    if first.count("while/body") < depth:
        return False
    last = first.rsplit("while/", 1)[1]  # "body/closed_call/<...>/<primitive>", "body/add", "cond/lt"
    parts = last.split("/")
    return (len(parts) > 2 and parts[0] == "body"
            and not re.fullmatch(r"closed_call|jit\(.*\)|iota|broadcast_in_dim", parts[-1]))


def unscoped(text, depth):
    """Instructions that should name their layer and do not. A loop
    counter's arithmetic (one integer or predicate, as a scalar or as the
    index vector a dynamic slice takes) is the loop's; an instruction with
    no `op_name` at all is the compiler's (XLA:CPU rewrites a batched dot
    into one that has lost its metadata)."""
    out = []
    for name, result, op, op_name in instructions(text):
        if op in WIRING or op_name is None:
            continue
        counter = re.match(r"(s32|u32|pred)\[1?\]", result) is not None
        if (op in HEAVY or (in_a_layer_loop(op_name, depth) and not counter)) and not top_levels(op_name):
            out.append(f"{name} = {result} {op} {op_name}")
    return out


@pytest.mark.parametrize("preset", FAMILIES)
@pytest.mark.parametrize("program", ["decode_block", "prefill"])
def test_every_op_of_a_layer_names_its_layer(preset, program):
    """Every instruction traced inside a layer loop, and every dot, gather,
    scatter and custom call anywhere in the program, lies under a scope of
    the vocabulary, and under one only: no instruction's path names two."""
    depth = 2 if program == "decode_block" else 1
    text = (decode_block if program == "decode_block" else prefill)(preset).compile().as_text()
    assert not unscoped(text, depth)
    seen = set()
    for _, _, op, op_name in instructions(text):
        levels = top_levels(op_name)
        assert len(levels) <= 1, op_name
        seen |= levels
    assert seen <= set(scopes.LAYERS)
    mixer = {"lfm2-tiny", "jamba-tiny", "nemotron-h-tiny"}
    assert seen == set(scopes.LAYERS) - (set() if preset in mixer else {"mixer"})


STRIPPED = re.compile(r", metadata=\{[^}]*\}|^(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:\d+ .*\n)+\n?", re.M)


def instruction_for_instruction(text):
    """The compiled text without its metadata and stack-frame table, every
    instruction and computation named by where it first appears: the
    number the compiler hangs on a name (`broadcast_in_dim.414`) counts the
    names it has given out, and a scope's own name is among them."""
    names = {}
    return re.sub(r"%[\w.\-]+", lambda m: names.setdefault(m.group(0), f"%{len(names)}"), STRIPPED.sub("", text))


@pytest.mark.parametrize("preset", FAMILIES)
def test_a_scope_is_metadata_and_nothing_else(preset, monkeypatch):
    """The compiled decode block with its scopes and with every scope a
    no-op: the same instructions with the same operands in the same
    order, once `metadata={...}` and the stack-frame table are taken out."""
    scoped = decode_block(preset).compile().as_text()
    assert "acp.attn" in scoped
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = decode_block(preset).compile().as_text()
    assert "acp." not in bare and "attn_qkv" not in bare
    assert instruction_for_instruction(scoped) == instruction_for_instruction(bare)


def test_a_name_outside_the_vocabulary_is_refused():
    with pytest.raises(ValueError, match="nonsense"):
        scopes.layer("nonsense")
    with scopes.layer("attn"):
        pass
    assert all(re.fullmatch(r"[a-z]+", name) for name in scopes.LAYERS)
