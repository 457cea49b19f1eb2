"""Compile the main path's kernels for a DESCRIBED v5e — no chip attached.

The TPU compiler is installed in the CPU sandbox and compiles for a
topology that is described, not attached; it raises what the chip's
compiler would raise. Interpret mode cannot: the page walk passed every
interpret-mode test while Mosaic refused it at every real geometry
("unsupported shape cast" on the grouped 4-D reshape; scale-row DMAs under
a lane tile). These cases guard that at no chip time, ~2 s each.

A compile that passes is not a chip run: nothing executes here.
`chip_smoke.py` and `tests/engine/test_tpu_hardware.py` run the same
kernels on the device against the reference.
"""

from __future__ import annotations

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from agentcontrolplane_tpu.models.llama import PRESETS

# MAX_PAGES over 128 // PAGE: a walk of eight pages a turn has several turns
PAGE, SLOTS, MAX_PAGES, NUM_PAGES = 16, 8, 24, 256


@pytest.fixture(scope="module")
def v5e():
    """Four described v5e devices, with the persistent compile cache off
    around the module: a compile for an unattached chip is written to the
    cache but cannot be read back without one (the next run would warn)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _walk_args(sharding, H, H_kv, d, dtype, int8):
    """Abstract operands of the serving hot-path form (read-only pages +
    the new token's self term), every one placed by ``sharding(spec)``."""

    def sds(shape, dt, *spec):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding(P(*spec)))

    # pages as every pool stores them: a row its KV heads side by side,
    # split over tp as H_kv / tp heads of d contiguous lanes
    pages = sds(
        (NUM_PAGES, PAGE, H_kv * d), jnp.int8 if int8 else dtype,
        None, None, "tp",
    )
    new = sds((SLOTS, H_kv, d), dtype, None, "tp", None)
    args = [
        sds((SLOTS, H, d), dtype, None, "tp", None),
        pages, pages,
        sds((SLOTS, MAX_PAGES), jnp.int32),
        sds((SLOTS,), jnp.int32),
        new, new,
    ]
    if int8:
        scales = sds((NUM_PAGES, PAGE, H_kv), jnp.float32, None, None, "tp")
        args += [scales, scales]
    return args


def _compile_walk(v5e, H, H_kv, d, dtype, int8, tp=1):
    from agentcontrolplane_tpu.ops.pallas import paged_attention as pa

    if tp == 1:
        one_chip = SingleDeviceSharding(v5e[0])
        args = _walk_args(lambda spec: one_chip, H, H_kv, d, dtype, int8)
        fn = pa.paged_decode_attention_cache_plus_new
    else:
        mesh = Mesh(v5e[:tp], ("tp",))
        args = _walk_args(
            lambda spec: NamedSharding(mesh, spec), H, H_kv, d, dtype, int8
        )
        fn = lambda *a, **kw: pa.paged_decode_attention_cache_plus_new_sharded(  # noqa: E731
            mesh, *a, **kw
        )
    if int8:
        call = lambda *a: fn(*a[:7], k_scales=a[7], v_scales=a[8])  # noqa: E731
    else:
        call = fn
    return jax.jit(call).lower(*args).compile()


def _compile_slot_decode_step(v5e):
    """`models.llama.decode_step` (the CLI-default slot layout) at
    Qwen2.5-7B widths and depth with int8 weights, 8 slots x 1,024 ctx."""
    from agentcontrolplane_tpu.models.llama import decode_step, init_kv_cache, init_params
    from agentcontrolplane_tpu.ops.quant import quantize_params

    c = PRESETS["qwen2.5-7b"]
    one_chip = SingleDeviceSharding(v5e[0])
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree
    )
    params = place(jax.eval_shape(
        lambda: quantize_params(init_params(c, jax.random.key(0)))
    ))
    cache = place(jax.eval_shape(lambda: init_kv_cache(c, SLOTS, 1024)))
    vec = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    return jax.jit(
        lambda p, kv, tok, lens: decode_step(p, kv, tok, lens, c)
    ).lower(params, cache, vec, vec).compile()


# (id, query heads, KV heads, head_dim, dtype, int8 pages, tp)
_WALKS = [
    ("walk-qwen2.5-7b-bf16", 28, 4, 128, jnp.bfloat16, False, 1),
    ("walk-qwen2.5-7b-int8", 28, 4, 128, jnp.bfloat16, True, 1),
    ("walk-llama3-8b-bf16", 32, 8, 128, jnp.bfloat16, False, 1),
    ("walk-gemma-2b-mqa", 8, 1, 256, jnp.bfloat16, False, 1),
    ("walk-mha-f32", 8, 8, 128, jnp.float32, False, 1),
    # head width 64, two KV heads to a lane window (LFM2-24B-A2B: 32/8 heads)
    ("walk-lfm2-24b-bf16-d64", 32, 8, 64, jnp.bfloat16, False, 1),
    ("walk-gqa-f32-d64", 8, 2, 64, jnp.float32, False, 1),
    # one KV head per chip: the divisibility edge of the shard_map wrapper
    ("walk-qwen2.5-7b-bf16-tp4", 28, 4, 128, jnp.bfloat16, False, 4),
    ("walk-qwen2.5-7b-int8-tp4", 28, 4, 128, jnp.bfloat16, True, 4),
    # what the q32b-tp4-decode cell runs: 2 KV heads, n_rep 5 a chip
    ("walk-qwen2.5-32b-bf16-tp4", 40, 8, 128, jnp.bfloat16, False, 4),
]


@pytest.mark.parametrize(
    "case", _WALKS + [("slot-decode-step-qwen2.5-7b-int8w",)], ids=lambda c: c[0]
)
def test_compiles_for_described_v5e(v5e, case):
    if len(case) == 1:
        compiled = _compile_slot_decode_step(v5e)
        # the slot layout has no kernel; it must fit one chip's 16 GB
        mem = compiled.memory_analysis()
        resident = (
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes
        )
        assert resident < 16e9, f"slot decode step needs {resident / 1e9:.1f} GB"
        return
    _, H, H_kv, d, dtype, int8, tp = case
    text = _compile_walk(v5e, H, H_kv, d, dtype, int8, tp).as_text()
    assert "tpu_custom_call" in text, "the Pallas page walk is not in the program"


# (id, slots, query heads, KV heads (a chip), head_dim, table entries a slot, the window's ring or 0)
_CELL_WALKS = [
    ("qwen2.5-7b", 32, 28, 4, 128, 1792 // PAGE, 0),
    ("qwen2.5-32b-a-chip-of-tp4", 24, 10, 2, 128, 1792 // PAGE, 0),
    ("lfm2-24b-a2b", 32, 32, 8, 64, 2048 // PAGE, 0),
    ("jamba2-3b", 128, 20, 1, 128, 2048 // PAGE, 0),
    ("mellum2-full-layers", 32, 32, 4, 128, 8192 // PAGE, 0),
    ("mellum2-window-layers", 32, 32, 4, 128, 1024 // PAGE + 1, 1024 // PAGE + 1),
    # no cell: a batch whose q and outputs do not fit one program's VMEM
    ("llama3-8b-at-256-slots", 256, 32, 8, 128, 2048 // PAGE, 0),
    # plain multi-head attention: sixteen KV heads a chip, a query group of ONE (sixteen chains of one query row a turn)
    ("ouro-2.6b", 8, 16, 16, 128, 640 // PAGE, 0),
]
_SLOTS_A_PROGRAM = {128: 64, 256: 16}  # every cell's slots in one program; jamba2's 128 in two


def _padded(shape, dtype) -> int:
    """Bytes of an array held whole in VMEM: its last two axes on the dtype's tile."""
    itemsize = jnp.dtype(dtype).itemsize
    *lead, rows, lanes = shape
    sub = 8 * 4 // itemsize
    n = -(-rows // sub) * sub * -(-lanes // 128) * 128 * itemsize
    for x in lead:
        n *= x
    return n


@pytest.mark.parametrize("case", _CELL_WALKS, ids=lambda c: c[0])
def test_the_walk_compiles_at_each_cells_geometry(v5e, case):
    """One program walks as many slots as fit, so their q and three outputs
    sit in VMEM (twice: pipelined) beside the ring of turn buffers: at each
    cell's slots and heads the chip's compiler takes it (it refuses a kernel
    over its scoped VMEM), the ring holds to its budget, and the whole is
    reckoned against the 16 MiB a kernel gets."""
    from agentcontrolplane_tpu.ops.pallas import paged_attention as pa

    _, S, H, H_kv, d, entries, ring = case
    one_chip = SingleDeviceSharding(v5e[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    pages = sds((4096, PAGE, H_kv * d), jnp.bfloat16)
    args = [sds((S, H, d), jnp.bfloat16), pages, pages, sds((S, entries), jnp.int32), sds((S,), jnp.int32)]
    if ring:
        walk = lambda q, k, v, t, n, first: pa._paged_state(q, k, v, t, n, kv_heads=H_kv, starts=first, ring=ring)  # noqa: E731
        args.append(sds((S,), jnp.int32))
    else:
        walk = lambda q, k, v, t, n: pa._paged_state(q, k, v, t, n, kv_heads=H_kv)  # noqa: E731
    text = jax.jit(walk).lower(*args).compile().as_text()
    assert ("paged_window_walk" if ring else "paged_page_walk") in text and "tpu_custom_call" in text
    pack = pa.heads_per_window(d, H_kv)  # narrow heads: `pack` to a lane window
    W, rows, lanes = H_kv // pack, pack * (H // H_kv), pack * d
    G = pa.pages_per_turn(PAGE, jnp.bfloat16, W, lanes)
    turns, in_flight = pa.fetches_in_flight(PAGE, jnp.bfloat16, W, lanes)
    turn = 2 * G * PAGE * W * lanes * 2
    assert G == 8 and (turns, in_flight) == (pa.RING - 1, (pa.RING - 1) * turn)
    scratch = pa.RING * turn
    assert scratch <= pa._SCRATCH_BUDGET
    blk = pa.slots_per_program(S, W, rows, lanes, jnp.bfloat16)
    assert blk == _SLOTS_A_PROGRAM.get(S, S)
    whole = (_padded((blk, W, rows, lanes), jnp.bfloat16) + _padded((blk, W, rows, lanes), jnp.float32)
             + 2 * _padded((blk, W, rows, 1), jnp.float32))
    assert whole <= pa._SLOTS_BUDGET and scratch + 2 * whole < 12 << 20, (
        f"{(scratch + 2 * whole) / 2**20:.1f} MiB of the kernel's 16")


# (pages a turn, turns in flight, bytes in flight) of every K/V walk a cell runs, as PR 43 left them: PR 45 widened
# the turn of a pool of ONE leaf alone, so these walks' kernels are the parent's text (same shapes, same body)
_KV_WALKS_AS_PR43_LEFT_THEM = {
    "qwen2.5-7b": (8, 3, 786_432), "qwen2.5-32b-a-chip-of-tp4": (8, 3, 393_216), "lfm2-24b-a2b": (8, 3, 786_432),
    "jamba2-3b": (8, 3, 196_608), "mellum2-full-layers": (8, 3, 786_432), "mellum2-window-layers": (8, 3, 786_432),
}


@pytest.mark.parametrize("case", _CELL_WALKS[:6], ids=lambda c: c[0])
def test_the_kv_walks_geometry_is_what_it_was_before_the_latent_turn_widened(case):
    from agentcontrolplane_tpu.ops.pallas import paged_attention as pa

    name, _, H, H_kv, d, _, _ = case
    pack = pa.heads_per_window(d, H_kv)
    geometry = (PAGE, jnp.bfloat16, H_kv // pack, pack * d)
    assert (pa.pages_per_turn(*geometry), *pa.fetches_in_flight(*geometry)) == _KV_WALKS_AS_PR43_LEFT_THEM[name]
    assert (pa.RING, pa.LANES, pa._SCRATCH_BUDGET) == (4, 128, 8 << 20)


# -- the llama decode step over the pool stored as the walk reads it -----------

# (id, preset, widths the preset lacks, the cell's pages, slots, tp)
_QWEN_32B = dict(dim=5120, n_heads=40, n_kv_heads=8, ffn_dim=27648)
_DECODE_STEPS = [
    ("qwen2.5-7b-v5e1", {}, 3585, 32, 1),
    ("qwen2.5-32b-v5e4-tp4", _QWEN_32B, 2689, 24, 4),
]
_DEPTH = 4  # of 28 / 64 layers: the scan's body is compiled once whatever the depth


def _llama_operands(v5e, widths, pages, tp, depth, int8_pages=False):
    """A Qwen2.5 configuration's widths at ``depth`` layers on one described
    chip or a tp mesh of them: the config, the mesh, abstract int8 weights
    and a pool of the cell's pages placed as the engine places them, and a
    maker of replicated int32 (or other) operands."""
    import dataclasses

    from agentcontrolplane_tpu.models.llama import init_paged_cache, init_params
    from agentcontrolplane_tpu.ops.quant import QuantizedTensor, quantize_params
    from agentcontrolplane_tpu.parallel.mesh import param_shardings

    c = dataclasses.replace(PRESETS["qwen2.5-7b"], n_layers=depth, **widths)
    mesh = Mesh(v5e[:tp], ("tp",))
    named = lambda *spec: NamedSharding(mesh, P(*spec))  # noqa: E731
    plain = jax.eval_shape(lambda: init_params(c, jax.random.key(0)))
    params = jax.eval_shape(lambda: quantize_params(init_params(c, jax.random.key(0))))

    def int8_leaf(sharding, leaf):  # values as the matrix; scales [.., 1, out] keep the output axis
        if not isinstance(leaf, QuantizedTensor):
            return sharding
        spec = tuple(sharding.spec) + (None,) * (leaf.q.ndim - len(sharding.spec))
        return QuantizedTensor(q=sharding, scale=named(*spec[:-2], None, spec[-1]))

    shardings = jax.tree_util.tree_map(
        int8_leaf, param_shardings(mesh, c, plain), params, is_leaf=lambda x: isinstance(x, NamedSharding))
    place = lambda tree, sh: jax.tree_util.tree_map(  # noqa: E731
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), tree, sh)
    cache = jax.eval_shape(lambda: init_paged_cache(c, pages, PAGE, quantize_kv=int8_pages))
    # engine.py's page_spec: axis 3 (the row's heads, or a scale a head) over tp
    cache = place(cache, {name: named(None, None, None, "tp") for name in cache})
    vec = lambda *shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=named())  # noqa: E731
    return c, mesh, place(params, shardings), cache, vec


def _compile_llama_decode_step(v5e, widths, pages, slots, tp, int8_pages):
    """`models.llama.decode_step_paged` with the Pallas walk at a Qwen2.5
    configuration's widths (int8 weights, depth cut to `_DEPTH`), over a
    pool of the cell's pages, on one described chip or a tp mesh of them."""
    from agentcontrolplane_tpu.models.llama import decode_step_paged

    c, mesh, params, cache, vec = _llama_operands(v5e, widths, pages, tp, _DEPTH, int8_pages)
    compiled = jax.jit(
        lambda p, ca, tok, n, tables, active: decode_step_paged(
            p, ca, tok, n, tables, active, c, use_pallas=True, mesh=mesh),
        donate_argnums=(1,),
    ).lower(params, cache, vec(slots), vec(slots), vec(slots, 1792 // PAGE),
            vec(slots, dt=jnp.bool_)).compile()
    return c, compiled


def _computations(hlo: str) -> dict:
    """An HLO module's text split into its computations, by name."""
    import re

    out, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            name = ("ENTRY " if head.group(1) else "") + head.group(2)
            out[name] = []
        elif name is not None:
            out[name].append(line)
    return out


@pytest.mark.parametrize("int8_pages", [False, True], ids=["bf16-pages", "int8-pages"])
@pytest.mark.parametrize("case", _DECODE_STEPS, ids=lambda c: c[0])
def test_llama_decode_step_compiles_and_moves_no_pool_it_does_not_read(v5e, case, int8_pages):
    """The pool is stored `[L, pages, P, H_kv * d]` and handed to the walk
    whole, flattened over its layers, with block tables offset by the
    layer: the compiled step holds no value of one layer's pool (the old
    layout's `reshape`, `dynamic-slice` and, at tp=4, `copy` of the stacked
    pool: 42-46% of a decode step, PERF.md PR 33) and its temporaries are a
    fraction of the pool. int8 pages: the scale rows are laid out for the
    kernel once a step, outside the layer scan, not once a layer over the
    whole pool's scales."""
    import re

    _, widths, pages, slots, tp = case
    c, compiled = _compile_llama_decode_step(v5e, widths, pages, slots, tp, int8_pages)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1, "one walk, in the layer scan's body"
    assert "paged_page_walk" in text, "the benchmark's readers find the kernel by this name"
    heads, width = c.n_kv_heads // tp, c.n_kv_heads // tp * c.head_dim
    kv = "s8" if int8_pages else "bf16"
    for shape in (f"[{pages},{PAGE},{width}]", f"[1,{pages},{PAGE},{width}]", f"[{pages},{PAGE},{heads},{c.head_dim}]",
                  f"[1,{pages},{PAGE},{heads},{c.head_dim}]", f"[{_DEPTH},{pages},{PAGE},{heads},{c.head_dim}]"):
        assert f"{kv}{shape}" not in text, f"a value of a layer's pool or of the five-axis pool: {kv}{shape}"
    pool = 2 * _DEPTH * pages * PAGE * width * (1 if int8_pages else 2)
    temp = compiled.memory_analysis().temp_size_in_bytes
    if int8_pages:
        # the token commit of the scale twins is made in a copy whose four
        # (two at tp=4) scales a row are padded to a lane tile, as on the
        # parent (PERF.md section 7): known, and not the pool's K or V
        temp -= _DEPTH * pages * PAGE * 128 * 4
        rows = re.compile(rf"= f32\[{_DEPTH * pages},1,\d+\]\S* (copy|transpose|pad|fusion|reshape|bitcast)\(")
        inside = [name for name, lines in _computations(text).items()
                  if not name.startswith("ENTRY") and any(rows.search(line) for line in lines)]
        assert not inside, f"scale rows laid out inside a loop's body: {inside}"
        assert re.search(rf"f32\[{_DEPTH * pages},1,\d+\]", text), "the kernel is handed no laid-out scale rows"
    assert temp < pool // 4, f"temporaries {temp / 1e6:.0f} MB beside a {pool / 1e6:.0f} MB pool"


# -- the decode block as the engine nests it: step, constraint, sampler, the scan ----------------

_BLOCKS = [("qwen2.5-7b-v5e1", {}, 3585, 32, 1, 28), ("qwen2.5-32b-v5e4-tp4", _QWEN_32B, 2689, 24, 4, 64)]
_COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter", "collective-permute")


def _compile_llama_decode_block(v5e, widths, pages, slots, tp, depth, monkeypatch=None, sampler=None):
    """`engine.make_decode_block` around `decode_step_paged` with the walk,
    at a cell's shapes and full depth (int8 weights, bf16 pages, a block of
    8, the lanes as one packed buffer): the program the ledger's op names
    are read from. ``sampler`` stands in for `ops.sampling.sample`."""
    from agentcontrolplane_tpu.engine import engine
    from agentcontrolplane_tpu.engine.lanes import DECODE
    from agentcontrolplane_tpu.models.llama import decode_step_paged

    c, mesh, params, cache, vec = _llama_operands(v5e, widths, pages, tp, depth)
    key = jax.eval_shape(lambda: jax.random.key(0))
    if sampler is not None:
        monkeypatch.setattr(engine, "sample", sampler)
    block = engine.make_decode_block(
        lambda p, pool, tok, n, active, tables: decode_step_paged(
            p, pool, tok, n, tables, active, c, use_pallas=True, mesh=mesh),
        (151643, 151645), 1792, 8)
    return jax.jit(block, donate_argnums=(1, 2)).lower(
        params, cache, vec(len(DECODE.kinds), slots), vec(*key.shape, dt=key.dtype),
        vec(1, c.vocab_size), vec(1), vec(slots, 1792 // PAGE)).compile()


def _ops(lines):
    """(name, result type, opcode, the rest) of each instruction of a computation."""
    import re

    for line in lines:
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\((.*)", line)
        if m:
            yield m.groups()


def _called(rest: str) -> list:
    import re

    return re.findall(r"(?:calls|body|condition|true_computation|false_computation)=%?([\w.\-]+)", rest) + [
        c.strip(" %") for group in re.findall(r"branch_computations=\{([^}]*)\}", rest) for c in group.split(",")]


class _Step:
    """The compiled block's one decode step: the body of the entry's scan."""

    def __init__(self, compiled, wide: str):
        self.comps = _computations(compiled.as_text())
        entry = next(lines for name, lines in self.comps.items() if name.startswith("ENTRY"))
        (scan,) = [rest for _, _, op, rest in _ops(entry) if op == "while"]
        self.body = self.comps[_called(scan)[1]]  # condition=, body=
        self.wide = wide  # the logits' shape a chip, as the text writes it
        self.temp = compiled.memory_analysis().temp_size_in_bytes

    def wide_values(self):
        """Instructions of the step's own body whose result holds [S, V]."""
        return [(name, shape, op) for name, shape, op, _ in _ops(self.body)
                if self.wide in shape and op not in ("get-tuple-element", "tuple", "parameter")]

    def loops_over_the_logits(self, lines=None):
        """(condition's lines, where) of every loop that carries a float32
        [S, V] operand: the two threshold searches."""
        out = []
        for _, shape, op, rest in _ops(self.body if lines is None else lines):
            called = _called(rest)
            if op == "while" and f"f32{self.wide}" in shape:
                out.append((self.comps[called[0]], "step" if lines is None else "conditional"))
            elif op == "conditional" and lines is None:
                for branch in called:
                    out += self.loops_over_the_logits(self.comps[branch])
        return out

    def collectives(self):
        """Collectives of the step's own body: outside the layer loop, the
        threshold loops and any conditional."""
        return sorted(f"{op} {shape.split('{')[0]}" for _, shape, op, _ in _ops(self.body)
                      if op.removesuffix("-start") in _COLLECTIVES)


@pytest.mark.parametrize("case", _BLOCKS, ids=lambda c: c[0])
def test_the_decode_block_runs_its_threshold_searches_only_when_asked(v5e, case, monkeypatch):
    """The engine's decode block compiled for the described v5e at the
    7B's widths on one chip and the 32B's at tp=4, beside the same block
    with the sampler that ran both searches for every batch. The top-p
    loop is inside a conditional whose result is a threshold a row; the
    top-k loop turns by a count read from the lanes (a conditional around
    it moved the float32 logits out of the fast memory, `S(1)`, that every
    later pass reads them from); the step's body holds no [S, V] value the
    parent's did not, every one of them in that memory, in no more
    temporaries; at tp=4, where every reduction over the sharded vocabulary
    is a collective, 9 are left in the step's body of the parent's 12, and
    the two loops' chained ones run only when asked. It also keeps the
    finding of ISSUE 39: the chip's op names (`PERF_LEDGER.jsonl`
    `breakdown.device_ops`) can be looked up here, at no chip time."""
    import re

    from .test_sampling import always_both

    _, widths, pages, slots, tp, depth = case
    wide = f"[{slots},{152064 // tp}]"
    change = _Step(_compile_llama_decode_block(v5e, widths, pages, slots, tp, depth), wide)
    parent = _Step(_compile_llama_decode_block(v5e, widths, pages, slots, tp, depth, monkeypatch, always_both), wide)

    # the ledger's names: the walk, the two loops' reductions on the parent
    text = "\n".join(line for lines in parent.comps.values() for line in lines)
    assert "paged_page_walk" in text and f"f32{wide}" in text

    # both searches are loops of 32 on the parent; here one is gated by its count, one by a conditional
    assert [where for _, where in parent.loops_over_the_logits()] == ["step", "step"]
    assert all(any("constant(32)" in line for line in cond) for cond, _ in parent.loops_over_the_logits())
    (topk, at_k), (topp, at_p) = change.loops_over_the_logits()
    assert (at_k, at_p) == ("step", "conditional")
    assert not any(" constant(" in line for line in topk), "the top-k loop's count of turns is a constant"
    assert any("constant(32)" in line for line in topp)

    # what crosses the conditional's edge is rank 1, and its skipping branch computes nothing
    (cond,) = [(shape, _called(rest)) for _, shape, op, rest in _ops(change.body) if op == "conditional"]
    assert re.fullmatch(rf"\(?f32\[{slots}\]\{{[^}}]*\}}\)?", cond[0]), cond[0]
    skipping = min((change.comps[b] for b in cond[1]), key=len)
    assert {op for _, _, op, _ in _ops(skipping)} <= {"parameter", "get-tuple-element", "tuple"}

    # the step's body: no [S, V] value the parent's did not hold, all in the fast memory, no more temporaries
    kinds = lambda step: sorted(f"{op} {shape.split('{')[0]}" for _, shape, op in step.wide_values())  # noqa: E731
    assert not set(kinds(change)) - set(kinds(parent)), (kinds(change), kinds(parent))
    assert len(change.wide_values()) < len(parent.wide_values())
    assert not [v for v in change.wide_values() if v[2] == "copy"]
    for name, shape, _ in change.wide_values() + parent.wide_values():
        assert all("S(1)" in part for part in shape.split("], ") if wide in part), f"{name} leaves the fast memory: {shape}"
    assert change.temp < parent.temp + (1 << 20), f"{change.temp / 1e6:.1f} MB against {parent.temp / 1e6:.1f} MB"

    if tp > 1:
        # the four all-gathers of the two argmaxes, the embedding's all-reduce, the constraint's two,
        # and the top-k loop's first bounds (the row's min and max, which on one chip the head's fusion writes)
        assert len(parent.collectives()) == 12 and len(change.collectives()) == 9, change.collectives()
        assert not set(change.collectives()) - set(parent.collectives())


# -- the q and k projections: plain matmuls, their weights read where they lie -------------------


def _staged_projections(text: str, stacks) -> list:
    """Instructions of a compiled program that stage a projection's weight:
    a `fusion` or `copy` whose result is ONE layer's slice of a stack
    (`[1, in, out]`) in fast memory (memory space 1, `S(1)`), or a `copy`
    of a whole stack. ``stacks``: the `[L, in, out]` shapes a chip holds."""
    import re

    out = []
    for L, rows, cols in set(stacks):
        one, whole = rf"(?:s8|bf16)\[1,{rows},{cols}\]", rf"(?:s8|bf16)\[{L},{rows},{cols}\]"
        out += re.findall(rf"%?([\w.\-]+ = {one}\{{[^}}]*S\(1\)\}}) (?:fusion|copy)\(", text)
        out += re.findall(rf"%?([\w.\-]+ = {whole}\S*) copy\(", text)
    return sorted(out)


def _projection_stacks(c, tp: int = 1) -> list:
    """`wq`'s and `wk`'s stacks as one chip of ``tp`` holds them, either way round."""
    q, kv = c.n_heads * c.head_dim // tp, c.n_kv_heads * c.head_dim // tp
    return [(c.n_layers, c.dim, q), (c.n_layers, q, c.dim), (c.n_layers, c.dim, kv), (c.n_layers, kv, c.dim)]


@pytest.mark.parametrize("case", _BLOCKS + [("ouro-2.6b-v5e1",)], ids=lambda c: c[0])
def test_no_decode_block_stages_a_layers_wq_or_wk_in_fast_memory(v5e, case):
    """The q and k products are plain matmuls: `attn_mlp` keeps q and k as
    `[B, T, heads * head_dim]` behind one `optimization_barrier` before the
    split to heads, so the compiler cannot fold the split into the product
    and has no reason to want the weight heads-major. Held here for the
    7B's block, the 32B's at tp=4 and `ouro`'s, each at its cell's shapes
    and full depth: no `fusion` or `copy` whose result is one layer's slice
    of a projection's stack in fast memory (`S(1)`), no `copy` of a whole
    stack, temporaries under 50 MB. The parent
    (PR 46's tree) fails in all three blocks, by the ledger's own op names: on the 7B
    `constant_dynamic-slice_fusion.9 s8[1,3584,3584]{..S(1)}` (0.5075 ms a
    step) and `.8 s8[1,3584,512]` (0.1139), `copy.44 s8[28,3584,3584]`
    (0.1608) and `copy.43 s8[28,3584,512]`, temporaries 422.4 MB; at tp=4
    `.12 s8[1,5120,1280]` (0.627), `.11 s8[1,5120,256]`, `copy.41
    s8[64,5120,1280]`, `copy.42`, 429.7 MB; on `ouro`
    `constant_dynamic-slice_fusion.4` / `.5 bf16[1,2048,2048]{..S(1)}`
    (2.339 + 2.297 ms of a 38.3 ms step). With the barrier the blocks hold
    11.2, 3.5 and 1.3 MB of temporaries and the weights are read inside the
    matmul fusions, from HBM, as `w1` / `w3` / `w2` are (PERF.md, PR 48)."""
    if case[0].startswith("ouro"):
        (c, _, compiled), tp = _ouro_decode_block(v5e), 1
    else:
        import dataclasses

        _, widths, pages, slots, tp, depth = case
        compiled = _compile_llama_decode_block(v5e, widths, pages, slots, tp, depth)
        c = dataclasses.replace(PRESETS["qwen2.5-7b"], n_layers=depth, **widths)
    text = compiled.as_text()
    assert "paged_page_walk" in text
    staged = _staged_projections(text, _projection_stacks(c, tp))
    assert not staged, f"a projection's weight staged in fast memory or its stack copied: {staged}"
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 50e6, f"temporaries {temp / 1e6:.1f} MB: a stack of weights is copied"


# -- the grouped expert matmul and the model that runs it ---------------------


def _compiled_expert_layer(v5e, tokens, k, Eh, E, D, F, layers, gated=True) -> str:
    """`ops.moe.routed_experts` with its kernels, `Eh` of `E` experts held out
    of a stack of `layers` layers' experts, compiled for one described chip
    (`gated` False: experts of two matrices, `w2 act(w1 .)`)."""
    from agentcontrolplane_tpu.ops.moe import routed_experts

    one_chip = SingleDeviceSharding(v5e[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    bf16 = jnp.bfloat16

    def layer(x, router, bias, w1, w3, w2, index):
        return routed_experts(x, router, w1, w3 if gated else None, w2, k, held=tuple(range(Eh)), score="sigmoid",
                              bias=bias, kernel=True, expert_base=index * Eh)

    args = [sds((tokens, D), bf16), sds((D, E), bf16), sds((E,), jnp.float32), sds((layers * Eh, D, F), bf16),
            sds((layers * Eh, D, F), bf16), sds((layers * Eh, F, D), bf16), sds((), jnp.int32)]
    return jax.jit(layer).lower(*args).compile().as_text()


# (id, tokens, k, experts held, E, D, F, layers): the five expert cells' layer at a decode step's rows (`kexaone`'s
# two rows a lane) and at a prefill's (`models/mellum.py MOE_CHUNK` tokens a call; `kexaone`'s 3,072-token bucket whole);
# `nemotron3s`'s experts are latent and ungated (D the latent width)
_EXPERT_LAYERS = [
    ("lfm2-decode-32-lanes", 32, 4, 8, 64, 2048, 1536, 38), ("lfm2-prefill-8x512", 4096, 4, 8, 64, 2048, 1536, 38),
    ("mellum2-decode-32-lanes", 32, 8, 16, 64, 2304, 896, 28), ("mellum2-prefill-2048", 2048, 8, 16, 64, 2304, 896, 28),
    ("kanana2-decode-16-lanes", 16, 6, 8, 128, 2048, 768, 47), ("kanana2-prefill-2048", 2048, 6, 8, 128, 2048, 768, 47),
    ("kexaone-decode-64-lanes-2-rows", 128, 8, 16, 128, 6144, 2048, 4), ("kexaone-prefill-3072", 3072, 8, 16, 128, 6144, 2048, 4),
    ("nemotron3s-decode-128-lanes", 128, 22, 64, 512, 1024, 2688, 5), ("nemotron3s-prefill-2048", 2048, 22, 64, 512, 1024, 2688, 5),
]


def _kernel_vmem(text: str, kernel: str = "moe_gmm") -> list:
    """(asked, used) scoped VMEM bytes of each `tpu_custom_call` of a compiled
    program whose name begins with `kernel`, in the program's order (`asked`
    None where the call states no limit); `used` less what the compiler itself
    keeps live under the kernel (the offset it places the kernel's share at)."""
    import re

    sized = r'\{"memory_space":"1","offset":"(\d+)","size":"(\d+)"\}'
    found = re.findall(rf'%{kernel}\S* = [^\n]*?custom_call_target="tpu_custom_call"[^\n]*?"scoped_memory_configs":\[(?:{sized})?\]'
                       rf'[^\n]*?"used_scoped_memory_configs":\[{sized}\]', text)
    return [(int(asked) if asked else None, int(used) - int(base or 0)) for base, asked, _, used in found]


@pytest.mark.parametrize("case", _EXPERT_LAYERS, ids=lambda c: c[0])
def test_routed_expert_layer_compiles_with_its_kernel_for_described_v5e(v5e, case):
    """`ops.moe.routed_experts` at each expert cell's widths, its share of
    the experts held out of a stack of the layers' experts: route, the rows'
    plan and the two `moe_gmm` kernels, at a decode step's rows and at a
    prefill's. Each kernel states what it holds and 2 MiB, never over the
    16 MiB a kernel gets unasked (a claim over that is taken from the
    compiler's own plan: PR 53), and the chip's compiler places the ring
    `chunk_plan` reckons, a run's rows and its own scratch inside it."""
    import re

    from agentcontrolplane_tpu.ops.moe import row_tile
    from agentcontrolplane_tpu.ops.pallas.moe_gmm import _DEFAULT_VMEM_BYTES, chunk_plan, vmem_bytes

    _, tokens, k, Eh, E, D, F, layers = case
    gated = E != 512
    text = _compiled_expert_layer(v5e, tokens, k, Eh, E, D, F, layers, gated)
    assert text.count("tpu_custom_call") == len(re.findall(r"%moe_gmm\S* = ", text)) == 2, "gate-and-up (or the one matrix in) and down"
    assert f"bf16[{Eh},{D},{F}]" not in text, "a layer's experts were sliced out of the stack (a copy a call)"
    tm = row_tile(tokens * k, E)
    kernels = ((1 + gated, D, *chunk_plan(D, F, 1 + gated, 2, tm)), (1, F, *chunk_plan(F, D, 1, 2, tm)))
    vmem = _kernel_vmem(text)
    assert len(vmem) == 2
    for (asked, used), (weights, K, chunk, depth) in zip(vmem, kernels):
        ring = depth * weights * K * chunk * 2
        assert asked == vmem_bytes(K, weights, 2, tm, chunk, depth) <= _DEFAULT_VMEM_BYTES, "what the call holds and 2 MiB"
        assert ring < used <= asked, f"{used} bytes of scoped VMEM for a ring of {ring}, {asked} asked"


def test_a_ring_over_the_vmem_a_kernel_gets_unasked_is_refused_by_the_compiler(v5e, monkeypatch):
    """Why the plan stops where it does: `kexaone`'s gate and up in units of
    512 columns is a ring of 25 MB, and under the 16 MiB of scoped VMEM a
    kernel gets by default the chip's compiler refuses it (PR 51 met this
    with the grid's blocks; PR 53 claimed a limit for them; the stream's
    units are 128 columns there and claim nothing)."""
    from agentcontrolplane_tpu.ops.pallas import moe_gmm

    monkeypatch.setattr(moe_gmm, "chunk_plan", lambda *a: (512, 2))
    moe_gmm._stream.cache_clear()  # a geometry's call is built once a process
    try:
        with pytest.raises(Exception, match="vmem"):
            _compiled_expert_layer(v5e, 128, 8, 16, 128, 6144, 2048, 4)
    finally:
        moe_gmm._stream.cache_clear()


# (name, tokens, k, experts held, E, D, F, layers, entry ops that run at most): a decode step's rows in the three
# expert cells and a prefill's in `lfm2`; the parent of PR 49 compiled to 85 / 96 / 78 / 100 such ops
_EXPERT_ROWS = [
    ("lfm2-32x4-8-of-64", 32, 4, 8, 64, 2048, 1536, 38, 56),
    ("mellum2-32x8-16-of-64", 32, 8, 16, 64, 2304, 896, 28, 61),
    ("kanana2-16x6-8-of-128", 16, 6, 8, 128, 2048, 768, 47, 49),
    # 78 since PR 53 (71 before): beside kernels that state their VMEM the compiler prefetches the combine's weights
    # `f32[4096,4]` as four slices of 16 KB where it made one copy (four `slice-start` / `slice-done` and their join); PR
    # 55's stream states what it holds (12.3 and 12.9 MB here) and the count stays (71 under kernels that state nothing)
    ("lfm2-prefill-4096x4", 4096, 4, 8, 64, 2048, 1536, 38, 78),
]
_NOT_RUN = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast")


@pytest.mark.parametrize("case", _EXPERT_ROWS, ids=lambda c: c[0])
def test_the_expert_layers_rows_are_grouped_without_sort_loop_or_scatter(v5e, case):
    """Between the router and the grouped matmul the compiled layer counts
    (`ops.moe.group_rows`): no `sort` and no `while` under `moe_sort`, no
    scatter at a decode step's rows (a prefill's keep ONE, the inverse),
    no chain of one-element ops for a floor-divide, and no more ops in the
    entry computation than stated. The chip runs a sort, a scatter and a
    loop an element at a time, and an op of a few hundred integers costs
    what its launch costs: the count is the cost (PERF.md, PR 49)."""
    *rows, most = case[1:]
    comps = _computations(_compiled_expert_layer(v5e, *rows))
    entry = next(lines for name, lines in comps.items() if name.startswith("ENTRY"))
    ran = [(name, shape.split("{")[0], op, rest) for name, shape, op, rest in _ops(entry) if op not in _NOT_RUN]
    plan = [(name, shape, op) for name, shape, op, rest in ran if "/moe_sort/" in rest]
    assert sum("tpu_custom_call" in rest for _, _, _, rest in ran) == 2, "gate-and-up and down: two grouped matmuls"
    serial = [o for o in plan if o[2] in ("sort", "while")]
    assert not serial, f"the plan of the grouped matmul sorts or loops: {serial}"
    scatters = sorted(name for name, lines in comps.items() for _, _, op, _ in _ops(lines) if op == "scatter")
    assert len(scatters) <= (rows[0] * rows[1] >= 2048), f"scatters {scatters}: a decode step's rows take none, a prefill's one"
    single = [o for o in plan if o[1].endswith("[1]") or o[1].endswith("[]")]
    assert len(single) <= 2 and not any(op in ("sign", "negate", "select", "divide") for _, _, op in single), (
        f"one-element ops under moe_sort (a floor-divide written out is eighteen): {single}")
    assert len(ran) <= most, f"{len(ran)} entry ops for {most}: {sorted(op for _, _, op, _ in ran)}"


def _lfm2(v5e, monkeypatch):
    """The chip's eighth of the published config, abstract weights and cache
    (32 slots over 2,049 pages) placed on one described chip, the expert
    layer steered onto its kernel."""
    import functools

    from agentcontrolplane_tpu.models import experts, lfm2

    monkeypatch.setattr(experts, "routed_experts", functools.partial(experts.routed_experts, kernel=True))
    c = lfm2.PRESETS["lfm2-24b-a2b-ep8"]
    one_chip = SingleDeviceSharding(v5e[0])
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    params = place(jax.eval_shape(lambda: lfm2.init_params(c, jax.random.key(0))))
    cache = place(jax.eval_shape(lambda: lfm2.init_paged_cache(c, 2049, PAGE, max_slots=32)))
    vec = lambda *shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    return lfm2, c, params, cache, vec


def _kernels_by_body(compiled) -> list:
    """The kernels (`tpu_custom_call`) each computation of the program
    holds itself, fusions and loops under it apart: sorted, zeros left out.
    One entry a place where a layer body is traced."""
    comps = _computations(compiled.as_text())
    return sorted(n for n in (sum("tpu_custom_call" in rest for _, _, op, rest in _ops(lines) if op == "custom-call")
                              for lines in comps.values()) if n)


@pytest.mark.parametrize("batch,tokens", [(1, 256), (8, 512)])
def test_lfm2_prefill_compiles_under_the_engines_own_sampler(v5e, monkeypatch, batch, tokens):
    """The ENGINE's prefill program (`prefill_and_sample`: the model's
    prefill through `models.programs`, then `sample_lanes`) at the cell's
    one row of 256 tokens and at its widest batch. Under `moe_gmm` kernels
    that claimed any VMEM over the default the chip's compiler itself died
    on the first of them (SIGSEGV in its memory-space repacker, PR 53),
    while the model's prefill alone compiled at every shape: a kernel's
    rehearsal is every program that holds it, wrappers included. No kernel
    of the program states a limit over the default."""
    from agentcontrolplane_tpu import models
    from agentcontrolplane_tpu.engine import engine
    from agentcontrolplane_tpu.engine.lanes import PREFILL

    lfm2, c, params, cache, vec = _lfm2(v5e, monkeypatch)
    prog = models.programs(c)

    def prefill_and_sample(params, pages, tokens, lanes, page_ids, key, table, min_close):
        ln = PREFILL.unpack(lanes)
        pages, logits = prog.prefill_paged_batch(params, pages, tokens, ln["lengths"], (page_ids, (ln["slots"], ln["snap_at"])), c)
        toks, states = engine.sample_lanes(logits, key, ln, table, min_close)
        return pages, toks, states

    key = jax.eval_shape(lambda: jax.random.key(0))
    text = jax.jit(prefill_and_sample, donate_argnums=(1,)).lower(
        params, cache, vec(batch, tokens), vec(len(PREFILL.kinds), batch), vec(batch, tokens // PAGE),
        vec(*key.shape, dt=key.dtype), vec(1, 256), vec(1)).compile().as_text()
    vmem = _kernel_vmem(text)
    assert vmem and all(used <= asked <= 16 << 20 for asked, used in vmem), vmem


def test_lfm2_decode_step_compiles_and_moves_no_pool_it_does_not_read(v5e, monkeypatch):
    """`models.lfm2.decode_step_paged` at the published widths and full
    depth, this chip's eighth of the experts, 32 slots over 2,049 pages: it
    fits one chip and holds a layer body a kind a place (`segments`): the
    loop over the nine periods has the attention layer's (the walk and two
    grouped matmuls: 3 kernels), the loop over a period's three conv layers
    theirs (2), and the entry the tail's two layers written out (3 + 2): four
    layer bodies in three computations. Its temporaries are a fraction of
    the pool: no layer of the pool, and no layer's experts, is copied out
    for a kernel."""
    lfm2, c, params, cache, vec = _lfm2(v5e, monkeypatch)
    compiled = jax.jit(
        lambda p, ca, tok, n, tables, active: lfm2.decode_step_paged(p, ca, tok, n, tables, active, c, use_pallas=True),
        donate_argnums=(1,),
    ).lower(params, cache, vec(32), vec(32), vec(32, 64), vec(32, dt=jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    pool = 2 * 10 * 2049 * PAGE * 8 * 64 * 2
    assert _kernels_by_body(compiled) == [2, 3, 5]
    assert mem.temp_size_in_bytes < pool // 4, f"temporaries {mem.temp_size_in_bytes / 1e6:.0f} MB beside a {pool / 1e6:.0f} MB pool"
    resident = mem.argument_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    assert 0.25 * 16e9 < resident < 16e9, f"{resident / 1e9:.2f} GB"


# -- the jamba family: the walk at one KV head, the recurrence's two kernels, the programs' memory -----

_JAMBA_SLOTS, _JAMBA_PAGES = 128, 16385  # acpbench/configs/jamba2-3b-bf16-v5e1.json


def _jamba(v5e, monkeypatch):
    """The published config, abstract weights and cache placed on one
    described chip, and the programs steered onto their kernels (here the
    backend is the CPU, which they would serve by the XLA reference)."""
    from agentcontrolplane_tpu.models import jamba

    import functools

    monkeypatch.setattr(jamba.ssm, "scan", functools.partial(jamba.ssm.scan, kernel=True))
    monkeypatch.setattr(jamba.ssm, "update", functools.partial(jamba.ssm.update, kernel=True))
    c = jamba.PRESETS["jamba2-3b"]
    one_chip = SingleDeviceSharding(v5e[0])
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    params = place(jax.eval_shape(lambda: jamba.init_params(c, jax.random.key(0))))
    cache = place(jax.eval_shape(lambda: jamba.init_paged_cache(c, _JAMBA_PAGES, PAGE, max_slots=_JAMBA_SLOTS)))
    vec = lambda *shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    return jamba, c, params, cache, vec


def _resident(compiled) -> int:
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes
            + mem.temp_size_in_bytes)


def test_jamba_walk_compiles_at_one_kv_head_and_a_group_of_twenty(v5e):
    text = _compile_walk(v5e, 20, 1, 128, jnp.bfloat16, False).as_text()
    assert "tpu_custom_call" in text


def test_jamba_decode_block_updates_the_state_in_place(v5e, monkeypatch):
    """128 lanes of the published model, steps in a loop as the engine's
    decode block nests them: three kernels in three layer bodies (the loop
    over the two periods holds the attention layer's page walk, each of a
    period's two runs of Mamba layers, seven and six, the update kernel), the
    whole state (2.40 GB with its snapshot) is aliased from argument to
    result through all three loops, no op copies the stack of `h` (a
    conditional that handed it through unchanged did, once a layer: 6.6 ms a
    step on the chip, PERF.md PR 37; one step alone compiled without it) and
    the block's temporaries are a small fraction of the state."""
    import re

    jamba, c, params, cache, vec = _jamba(v5e, monkeypatch)
    S = _JAMBA_SLOTS

    def block(p, ca, tok, n, tables, active):
        def step(carry, _):
            ca, tok, n = carry
            ca, logits = jamba.decode_step_paged(p, ca, tok, n, tables, active, c, use_pallas=True)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            return (ca, tok, n + 1), tok

        (ca, _, _), toks = jax.lax.scan(step, (ca, tok, n), None, length=4)
        return ca, toks

    compiled = jax.jit(block, donate_argnums=(1,)).lower(
        params, cache, vec(S), vec(S), vec(S, 2048 // PAGE), vec(S, dt=jnp.bool_)).compile()
    text = compiled.as_text()
    assert "ssm_update" in text and "paged_page_walk" in text and _kernels_by_body(compiled) == [1, 1, 1]
    state = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(cache["state"]))
    mem = compiled.memory_analysis()
    assert state > 2.3e9 and mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < state // 6, f"temporaries {mem.temp_size_in_bytes / 1e6:.0f} MB"
    stack = rf"f32\[{c.n_mamba},{S + 1},{c.d_state},{c.d_inner}\]"
    assert re.search(stack, text)
    assert not re.search(rf"= {stack}\S* copy\(", text), "a copy of the whole stack of h"
    assert f"f32[{S},{c.d_state},{c.d_inner}]" not in text, "a layer's lanes of the state as a value of their own"
    assert _resident(compiled) < 10.5e9


def test_jamba_prefill_fits_beside_the_resident_set(v5e, monkeypatch):
    """The widest prefill the file admits (4 rows of 512) with the scan kernel: weights,
    state and pool resident, the temporaries beside them, under the chip's
    16 GB; and no [T, d_state, d_inner] array of the recurrence anywhere."""
    jamba, c, params, cache, vec = _jamba(v5e, monkeypatch)
    B, T = 4, 512
    compiled = jax.jit(
        lambda p, ca, tok, n, ids, slots, snap: jamba.prefill_paged_batch(p, ca, tok, n, ids, (slots, snap), c),
        donate_argnums=(1,),
    ).lower(params, cache, vec(B, T), vec(B), vec(B, T // PAGE), vec(B), vec(B)).compile()
    text = compiled.as_text()
    assert "ssm_scan" in text
    assert f"{T},{c.d_state},{c.d_inner}]" not in text and f"{T},{c.d_inner},{c.d_state}]" not in text
    assert _resident(compiled) < 11e9, f"{_resident(compiled) / 1e9:.1f} GB"


@pytest.mark.parametrize("family", ["jamba", "lfm2"])
def test_a_family_with_state_keeps_its_logits_in_fast_memory_around_the_searches(v5e, family, monkeypatch):
    """The other two cells' decode blocks (128 lanes x 65,536 on Jamba,
    the widest logits of any cell; 32 x 65,536 on LFM2), nested by the
    engine's own `make_decode_block`: the same placement as the Qwen
    blocks above. The top-k loop in the step's body, the top-p loop under
    the conditional, and every [S, V] value of the step in `S(1)`."""
    from agentcontrolplane_tpu.engine import engine
    from agentcontrolplane_tpu.engine.lanes import DECODE

    if family == "jamba":
        model, c, params, cache, vec = _jamba(v5e, monkeypatch)
        slots, ctx, block = _JAMBA_SLOTS, 2048, 32
    else:
        model, c, params, cache, vec = _lfm2(v5e, monkeypatch)
        slots, ctx, block = 32, 1024, 16
    key = jax.eval_shape(lambda: jax.random.key(0))
    fn = engine.make_decode_block(
        lambda p, ca, tok, n, active, tables: model.decode_step_paged(p, ca, tok, n, tables, active, c, use_pallas=True),
        (7,), ctx, block)
    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(
        params, cache, vec(len(DECODE.kinds), slots), vec(*key.shape, dt=key.dtype),
        vec(1, c.vocab_size), vec(1), vec(slots, ctx // PAGE)).compile()
    step = _Step(compiled, f"[{slots},{c.vocab_size}]")
    assert [where for _, where in step.loops_over_the_logits()] == ["step", "conditional"]
    assert step.wide_values(), "no [S, V] value found in the step's body"
    for name, shape, _ in step.wide_values():
        assert all("S(1)" in part for part in shape.split("], ") if step.wide in part), f"{name} leaves the fast memory: {shape}"



# -- a layer is handed its own row, not its kind's whole stack ----------------------------------------

_MB = 1 << 20
_PASSED_ON = ("parameter", "get-tuple-element", "tuple", "while", "call", "conditional", "bitcast")


def _arrays(result: str):
    """(text, leading dimension, bytes) of each array in an instruction's result type."""
    import re

    width = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4}
    for dtype, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]+)\]", result):
        dims = [int(d) for d in dims.split(",")]
        size = width.get(dtype, 4)
        for d in dims:
            size *= d
        yield f"{dtype}[{','.join(map(str, dims))}]", dims[0], size


class _Loops:
    """A compiled program's loops: what each `while` is handed and what the
    computations under its body do."""

    def __init__(self, compiled):
        self.comps = {name.removeprefix("ENTRY "): lines for name, lines in _computations(compiled.as_text()).items()}
        # (the operands' types, the body) of every loop, wherever it stands
        self.loops = [(result, _called(rest)[1]) for lines in self.comps.values()
                      for _, result, op, rest in _ops(lines) if op == "while"]

    def under(self, body: str, fusions: bool) -> list:
        """`body` and every computation it reaches; with `fusions`, the fused ones too."""
        seen, todo = [], [body]
        while todo:
            name = todo.pop()
            if name in seen or name not in self.comps:
                continue
            seen.append(name)
            todo += [c for _, _, op, rest in _ops(self.comps[name]) if fusions or op != "fusion" for c in _called(rest)]
        return seen

    def in_place(self, op: str, rest: str) -> bool:
        """An update of a carried array where it lies: a `dynamic-update-slice`,
        alone or as a fusion's root, or a kernel whose result is its operand."""
        if op == "fusion":
            return any(line.lstrip().startswith("ROOT") and " dynamic-update-slice(" in line
                       for c in _called(rest) for line in self.comps.get(c, ()))
        return op == "dynamic-update-slice" or (op == "custom-call" and "output_to_operand_aliasing" in rest)

    def stacks_made(self, leads) -> list:
        """Instructions under a loop's body that produce an array over 1 MB
        whose leading dimension is one of `leads`, in-place updates apart. A
        fusion counts by what it writes, not by what it holds inside."""
        out = []
        for name in sorted({c for _, body in self.loops for c in self.under(body, fusions=False)}):
            for ins, result, op, rest in _ops(self.comps[name]):
                if op in _PASSED_ON or self.in_place(op, rest):
                    continue
                out += [f"{ins} = {text} {op}" for text, lead, size in _arrays(result) if lead in leads and size > _MB]
        return list(dict.fromkeys(out))

    def marked(self, name: str, marks) -> bool:
        """Whether anything under computation `name` carries one of `marks` (a scope or kernel name)."""
        return any(mark in line for c in self.under(name, fusions=True) for line in self.comps[c] for mark in marks)

    def handed(self, shapes, marks) -> list:
        """Loops that are handed an array of one of `shapes` although nothing
        under their body carries one of `marks`."""
        return [f"{body}: {shape}" for result, body in self.loops if not self.marked(body, marks)
                for shape in sorted(shapes) if shape in result]

    def switches(self, marks_a, marks_b) -> list:
        """Conditionals of which one branch carries `marks_a` and another `marks_b`."""
        out = []
        for lines in self.comps.values():
            for ins, _, op, rest in _ops(lines):
                branches = _called(rest) if op == "conditional" else []
                if any(self.marked(b, marks_a) for b in branches) and any(self.marked(b, marks_b) for b in branches):
                    out.append(ins)
        return out


def _stack_shapes(*trees) -> set:
    names = {jnp.dtype(jnp.bfloat16): "bf16", jnp.dtype(jnp.float32): "f32"}
    return {f"{names[jnp.dtype(x.dtype)]}[{','.join(map(str, x.shape))}]"
            for tree in trees for x in jax.tree_util.tree_leaves(tree)}


@pytest.mark.parametrize("family", ["lfm2", "jamba"])
def test_a_decode_step_hands_a_layer_its_own_row_not_its_kinds_stack(v5e, family, monkeypatch):
    """`lfm2` and `jamba` decode steps at the published widths and the cells'
    slots, compiled for the described v5e. A layer's kind is fixed when the
    step is traced, so each loop's body has one kind and reads its row of
    the closed-over stacks by the loop's counter. (a) Over the computations a
    `while` body reaches, no instruction produces an array over 1 MB whose
    leading dimension is a stack's (the attention layers, the scanned conv or
    Mamba layers, all of them), a carried state's update in place apart: a
    fusion that takes a stack and reads one row is the form wanted. (`jamba`
    has two attention layers: a stack of two rows, which the compiler may
    fetch whole into fast memory beside the layer that reads one, twice a
    step; it is held out of the Mamba loops by (b) and not counted here.) (b)
    A loop under whose body no attention layer runs is handed nothing with
    the shape of an attention stack, and one that runs no conv or Mamba layer
    nothing of theirs or of their state. (c) No conditional has a branch of
    each kind. One body that switched on the kind (`lax.cond`) made every
    stack an operand of every layer: on `lfm2` the chip evicted and
    refetched `bf16[10,2048,512]` (`wk`, 21 MB) and relaid
    `bf16[30,32,2,2048]` (the conv state) 38 times a step, on `jamba` it cut
    `bf16[1,2560,2560]` rows out of `wq` and `wo` in 28 layers for the 2 that
    use them (PERF.md, PR 41). The programs over rows of tokens keep that
    body, and why: `models/lfm2.py`'s module text."""
    if family == "lfm2":
        model, c, params, cache, vec = _lfm2(v5e, monkeypatch)
        slots, ctx = 32, 1024
        leads = {c.n_attention, c.n_conv, int(model.plan(c)["is_attn"].sum()), int((~model.plan(c)["is_attn"]).sum())}
        other, other_marks = "conv", ("short_conv",)
    else:
        model, c, params, cache, vec = _jamba(v5e, monkeypatch)
        slots, ctx = _JAMBA_SLOTS, 2048
        leads = {c.n_mamba, c.n_layers}
        other, other_marks = "mamba", ("mamba_in_proj",)
    compiled = jax.jit(
        lambda p, ca, tok, n, tables, active: model.decode_step_paged(p, ca, tok, n, tables, active, c, use_pallas=True),
        donate_argnums=(1,),
    ).lower(params, cache, vec(slots), vec(slots), vec(slots, ctx // PAGE), vec(slots, dt=jnp.bool_)).compile()
    loops = _Loops(compiled)
    attention = ("acp.attn",)  # the program's own scope around an attention layer's mixer (observability/scopes.py)
    assert loops.loops and any(loops.marked(body, attention) and loops.marked(body, other_marks) for _, body in loops.loops)
    assert not loops.stacks_made(leads), "a stack, or its like, is made inside a loop"
    state = {k: v for k, v in cache["state"].items() if k in ("conv", "ssm")}
    assert not loops.handed(_stack_shapes(params["attn"]), attention)
    assert not loops.handed(_stack_shapes(params[other], state), other_marks)
    assert not loops.switches(attention, other_marks), "a conditional chooses a layer's kind on the chip"


# -- the mellum family: two caches a slot, the window walk beside the page walk ----------------------------

_MELLUM_SLOTS, _MELLUM_PAGES = 32, 16385  # acpbench/configs/mellum2-12b-a2.5b-bf16-v5e1-ep4.json


def _mellum(v5e, monkeypatch):
    """The chip's share of the published config (16 of 64 experts), abstract
    weights and both caches placed on one described chip, the expert layer
    steered onto its kernel."""
    import functools

    from agentcontrolplane_tpu.models import experts, mellum

    monkeypatch.setattr(experts, "routed_experts", functools.partial(experts.routed_experts, kernel=True))
    c = mellum.PRESETS["mellum2-12b-a2.5b-ep4"]
    one_chip = SingleDeviceSharding(v5e[0])
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    params = place(jax.eval_shape(lambda: mellum.init_params(c, jax.random.key(0))))
    cache = place(jax.eval_shape(lambda: mellum.init_paged_cache(c, _MELLUM_PAGES, PAGE, max_slots=_MELLUM_SLOTS)))
    vec = lambda *shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    return mellum, c, params, cache, vec


def test_mellum_decode_block_walks_two_caches_and_copies_neither(v5e, monkeypatch):
    """32 lanes of the published model at full depth, steps in a loop as the
    engine's decode block nests them: six kernels in all (the window layer's
    body: its walk and two grouped matmuls; the full layer's: the page walk
    and two), both pools aliased from argument to result (5.23 GB), no op
    copies either pool, a layer of one or a slot's ring out of it, and the
    block's temporaries are a fraction of the pools."""
    import re

    mellum, c, params, cache, vec = _mellum(v5e, monkeypatch)
    S = _MELLUM_SLOTS

    def block(p, ca, tok, n, tables, active):
        def step(carry, _):
            ca, tok, n = carry
            ca, logits = mellum.decode_step_paged(p, ca, tok, n, tables, active, c, use_pallas=True)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            return (ca, tok, n + 1), tok

        (ca, _, _), toks = jax.lax.scan(step, (ca, tok, n), None, length=4)
        return ca, toks

    compiled = jax.jit(block, donate_argnums=(1,)).lower(
        params, cache, vec(S), vec(S), vec(S, 8192 // PAGE), vec(S, dt=jnp.bool_)).compile()
    text = compiled.as_text()
    assert "paged_window_walk" in text and "paged_page_walk" in text and text.count("tpu_custom_call") == 6
    pools = sum(cache[name].size * 2 for name in ("k", "v", "wk", "wv"))
    mem = compiled.memory_analysis()
    assert 5.2e9 < pools < 5.3e9 and mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < pools // 6, f"temporaries {mem.temp_size_in_bytes / 1e6:.0f} MB"
    ring = 1024 // PAGE + 1
    for pool in (rf"bf16\[7,{_MELLUM_PAGES},{PAGE},512\]", rf"bf16\[21,{(S + 1) * ring},{PAGE},512\]"):
        assert re.search(pool, text)
        assert not re.search(rf"= {pool}\S* copy\(", text), f"a copy of the whole pool {pool}"
    assert f"bf16[{_MELLUM_PAGES},{PAGE},512]" not in text and f"bf16[{(S + 1) * ring},{PAGE},512]" not in text, (
        "one layer of a pool as a value of its own")
    # what the compiler stages in VMEM under the kernels stays there: the window layers' stack of k (or v) weights,
    # 49.5 MB, went to HBM under grouped matmuls that stated no VMEM limit and so were given the whole default (PR 55)
    staged = re.findall(r"= (bf16\[21,2304,512\]\S*) copy\(", text)
    assert all("S(1)" in layout for layout in staged), f"the window layers' k/v weights staged outside VMEM: {staged}"
    assert 0.75 * 16e9 < _resident(compiled) < 14e9, f"{_resident(compiled) / 1e9:.2f} GB"


def test_mellum_prefill_of_2048_tokens_fits_beside_the_resident_set(v5e, monkeypatch):
    """The check's bucket (one row of 2,048 tokens, as the engine prefills
    it): weights and both caches resident, the temporaries beside them, under
    the chip's 16 GB; the expert layer's kernels are there (a chunk of 2,048
    tokens at a time) and the window layers stack a ring's worth of rows a
    layer (1,040), not the bucket's."""
    mellum, c, params, cache, vec = _mellum(v5e, monkeypatch)
    B, T = 1, 2048
    compiled = jax.jit(
        lambda p, ca, tok, n, ids, slots, snap: mellum.prefill_paged_batch(p, ca, tok, n, ids, (slots, snap), c),
        donate_argnums=(1,),
    ).lower(params, cache, vec(B, T), vec(B), vec(B, T // PAGE), vec(B), vec(B)).compile()
    text = compiled.as_text()
    assert "moe_gmm" in text and "paged_window_walk" not in text  # a prefill attends over its own rows
    assert f"bf16[7,3,{B},1040,4,128]" in text and f"bf16[7,3,{B},{T},4,128]" not in text
    assert _resident(compiled) < 14.5e9, f"{_resident(compiled) / 1e9:.1f} GB"


# -- the kanana family: a latent row a token in the pool, the latent walk ---------------------------------

_KANANA_SLOTS, _KANANA_PAGES = 16, 5121  # acpbench/configs/kanana2-30b-a3b-bf16-v5e1-ep16.json


def _kanana(v5e, monkeypatch):
    """The chip's share of the published config (8 of 128 experts), abstract
    weights and the latent pool placed on one described chip, the expert
    layer steered onto its kernel."""
    import functools

    from agentcontrolplane_tpu.models import experts, kanana

    monkeypatch.setattr(experts, "routed_experts", functools.partial(experts.routed_experts, kernel=True))
    c = kanana.PRESETS["kanana-2-30b-a3b-ep16"]
    one_chip = SingleDeviceSharding(v5e[0])
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    params = place(jax.eval_shape(lambda: kanana.init_params(c, jax.random.key(0))))
    cache = place(jax.eval_shape(lambda: kanana.init_paged_cache(c, _KANANA_PAGES, PAGE, max_slots=_KANANA_SLOTS)))
    vec = lambda *shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    return kanana, c, params, cache, vec


def test_kanana_decode_block_walks_latent_rows_and_copies_no_pool(v5e, monkeypatch):
    """16 lanes of the published model at full depth, steps in a loop as the
    engine's decode block nests them: four kernels (the dense layer's walk,
    the expert layers' walk and two grouped matmuls), the pool aliased from
    argument to result (5.03 GB: a row of 576 values is stored on 640 lanes),
    no op copies the pool or a layer of it, and NO weight is relaid before
    the first step: with `q_proj`, `kv_a_proj` and `kv_b_proj` each one matrix
    the block copied 1.73 GB of them, with `q_proj`'s halves inputs first
    still 1.18 GB (PERF.md, PR 44)."""
    import re

    kanana, c, params, cache, vec = _kanana(v5e, monkeypatch)
    S = _KANANA_SLOTS

    def block(p, ca, tok, n, tables, active):
        def step(carry, _):
            ca, tok, n = carry
            ca, logits = kanana.decode_step_paged(p, ca, tok, n, tables, active, c, use_pallas=True)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            return (ca, tok, n + 1), tok

        (ca, _, _), toks = jax.lax.scan(step, (ca, tok, n), None, length=4)
        return ca, toks

    compiled = jax.jit(block, donate_argnums=(1,)).lower(
        params, cache, vec(S), vec(S), vec(S, 5120 // PAGE), vec(S, dt=jnp.bool_)).compile()
    text = compiled.as_text()
    assert "paged_latent_walk" in text and "paged_page_walk" not in text and text.count("tpu_custom_call") == 4
    from agentcontrolplane_tpu.ops.pallas import paged_attention as pa

    G = pa.pages_per_turn(PAGE, jnp.bfloat16, 1, c.row_stored, leaves=1)  # four lane tiles of rows a turn
    assert G == 32 and pa.RING * G * PAGE * c.row_stored * 2 == 2_621_440 <= pa._SCRATCH_BUDGET
    pool = cache["kv"].size * 2
    mem = compiled.memory_analysis()
    assert cache["kv"].shape == (48, _KANANA_PAGES, PAGE, 640) and 5.0e9 < pool < 5.1e9 and mem.alias_size_in_bytes >= pool
    assert mem.temp_size_in_bytes < 100e6, f"temporaries {mem.temp_size_in_bytes / 1e6:.0f} MB: a weight is relaid"
    shape = rf"bf16\[48,{_KANANA_PAGES},{PAGE},640\]"
    assert re.search(shape, text) and not re.search(rf"= {shape}\S* copy\(", text), "a copy of the whole pool"
    assert f"bf16[{_KANANA_PAGES},{PAGE},640]" not in text, "one layer of the pool as a value of its own"
    assert 0.8 * 16e9 < _resident(compiled) < 13.5e9, f"{_resident(compiled) / 1e9:.2f} GB"


def test_kanana_prefill_of_4096_tokens_fits_beside_the_resident_set(v5e, monkeypatch):
    """The mix's widest prompt (one row of 4,096 tokens, as the engine
    prefills it), expanded: weights and the pool resident, the temporaries
    beside them, under 14.5 GB; the expert layer's kernels are there (a chunk
    of 2,048 tokens at a time) and no walk is."""
    kanana, c, params, cache, vec = _kanana(v5e, monkeypatch)
    B, T = 1, 4096
    fn = lambda p, ca, t, n, ids: kanana.prefill_paged_batch(p, ca, t, n, ids, c)  # noqa: E731
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(params, cache, vec(B, T), vec(B), vec(B, T // PAGE)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 and "paged_latent_walk" not in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache["kv"].size * 2
    assert mem.temp_size_in_bytes < 1.2e9, f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB"
    assert _resident(compiled) < 14.5e9, f"{_resident(compiled) / 1e9:.2f} GB"


def test_the_latent_walk_refuses_a_row_that_is_not_whole_lane_tiles(v5e):
    """Why the pool's row is 640 wide and not 576: Mosaic slices no HBM
    operand whose minor axis is not whole lane tiles."""
    from agentcontrolplane_tpu.ops.pallas import paged_attention as pa

    one_chip = SingleDeviceSharding(v5e[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731

    def walk(width):
        args = [sds((16, 32, width), jnp.bfloat16), sds((4096, PAGE, width), jnp.bfloat16),
                sds((16, 320), jnp.int32), sds((16,), jnp.int32)]
        return jax.jit(lambda q, p, t, n: pa.paged_latent_state(q, p, t, n, 512, 192)).lower(*args).compile()

    assert "paged_latent_walk" in walk(640).as_text()
    with pytest.raises(Exception, match="aligned to tiling"):
        walk(576)


# -- the ouro family: a stack run four times over shared weights, a pool four times as deep ---------------

_OURO_SLOTS, _OURO_PAGES = 8, 321  # acpbench/configs/ouro-2.6b-bf16-v5e1.json


def _ouro(v5e):
    """The published config whole, abstract weights and the pool of 192 cache
    layers placed on one described chip."""
    from agentcontrolplane_tpu.models import ouro

    c = ouro.PRESETS["ouro-2.6b"]
    one_chip = SingleDeviceSharding(v5e[0])
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    params = place(jax.eval_shape(lambda: ouro.init_params(c, jax.random.key(0))))
    cache = place(jax.eval_shape(lambda: ouro.init_paged_cache(c, _OURO_PAGES, PAGE, max_slots=_OURO_SLOTS)))
    vec = lambda *shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    return ouro, c, params, cache, vec


def _ouro_decode_block(v5e):
    """8 lanes of the published model, steps in a loop as the engine's decode
    block nests them (steps, loops, layers: three scans deep)."""
    ouro, c, params, cache, vec = _ouro(v5e)
    S = _OURO_SLOTS

    def block(p, ca, tok, n, tables, active):
        def step(carry, _):
            ca, tok, n = carry
            ca, logits = ouro.decode_step_paged(p, ca, tok, n, tables, active, c, use_pallas=True)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            return (ca, tok, n + 1), tok

        (ca, _, _), toks = jax.lax.scan(step, (ca, tok, n), None, length=4)
        return ca, toks

    compiled = jax.jit(block, donate_argnums=(1,)).lower(
        params, cache, vec(S), vec(S), vec(S, 640 // PAGE), vec(S, dt=jnp.bool_)).compile()
    return c, cache, compiled


def test_ouro_decode_block_walks_192_cache_layers_and_copies_neither_pool_nor_weight(v5e):
    """8 lanes of the published model, steps in a loop as the engine's decode
    block nests them (steps, loops, layers: three scans deep): ONE kernel,
    the page walk at 16 KV heads and a query group of one, which the chip's
    compiler takes as it stands; the pool (8.08 GB) aliased from argument to
    result, no op copies it or a layer of it; and no weight is relaid before
    the first step: with `wq` and `wk` inputs first the block copied both
    stacks transposed, 0.81 GB of temporaries (PERF.md, PR 46); and no layer's
    `wq` or `wk` slice is read into fast memory as an op of its own (4.64 ms
    of the step before the products were kept plain: PERF.md, PR 48)."""
    import re

    c, cache, compiled = _ouro_decode_block(v5e)
    text = compiled.as_text()
    assert "paged_page_walk" in text and text.count("tpu_custom_call") == 1
    pool = 2 * cache["k"].size * 2
    mem = compiled.memory_analysis()
    assert cache["k"].shape == (192, _OURO_PAGES, PAGE, 2048) and 8.07e9 < pool < 8.09e9 and mem.alias_size_in_bytes >= pool
    assert mem.temp_size_in_bytes < 50e6, f"temporaries {mem.temp_size_in_bytes / 1e6:.0f} MB: a weight is relaid"
    shape = rf"bf16\[192,{_OURO_PAGES},{PAGE},2048\]"
    assert re.search(shape, text) and not re.search(rf"= {shape}\S* copy\(", text), "a copy of the whole pool"
    assert f"bf16[{_OURO_PAGES},{PAGE},2048]" not in text, "one cache layer of the pool as a value of its own"
    assert not re.search(r"= bf16\[48,\d+,\d+\]\S* copy\(", text), "a stack of weights copied"
    assert not _staged_projections(text, _projection_stacks(c)), "a layer's wq or wk slice staged in fast memory"
    assert 0.83 * 16e9 < _resident(compiled) < 13.6e9, f"{_resident(compiled) / 1e9:.2f} GB"


@pytest.mark.parametrize("program", ["prefill", "continuation"])
def test_ouro_prefill_of_two_rows_of_256_fits_beside_the_resident_set(v5e, program):
    """The widest dispatch the configuration allows (`prefill_batch_max` 2 x
    the 256 bucket): weights and the pool resident, the stacked new rows of
    192 cache layers (0.81 GB) and their way into whole pages beside them,
    under 15.2 GB; no walk is in it."""
    ouro, c, params, cache, vec = _ouro(v5e)
    B, T = 2, 256
    if program == "prefill":
        fn = lambda p, ca, t, n, ids: ouro.prefill_paged_batch(p, ca, t, n, ids, c)  # noqa: E731
        args = (vec(B, T), vec(B), vec(B, T // PAGE))
    else:
        fn = lambda p, ca, t, n, st, ids, tb: ouro.prefill_paged_continue(p, ca, t, n, st, ids, tb, c)  # noqa: E731
        args = (vec(B, T), vec(B), vec(B), vec(B, T // PAGE), vec(B, 640 // PAGE))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(params, cache, *args).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * cache["k"].size * 2
    assert mem.temp_size_in_bytes < 1.7e9, f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB"
    assert _resident(compiled) < 15.2e9, f"{_resident(compiled) / 1e9:.2f} GB"


# -- the nemotron_h family: the Mamba-2 kernels, the latent experts, the programs' memory (PR 54) ------------------

_NEMOTRON_SLOTS, _NEMOTRON_PAGES = 128, 32769  # acpbench/configs/nemotron3-super-120b-a12b-bf16-v5e1-ep8.json


def _nemotron(v5e, monkeypatch):
    """The benchmark's cut of the published config (its first 11 blocks, 64
    of 512 experts, an eighth of the vocabulary), abstract weights and cache
    placed on one described chip, the programs steered onto their kernels."""
    import dataclasses
    import functools

    from agentcontrolplane_tpu.models import nemotron_h as nh

    monkeypatch.setattr(nh.ssd, "scan", functools.partial(nh.ssd.scan, kernel=True))
    monkeypatch.setattr(nh.ssd, "update", functools.partial(nh.ssd.update, kernel=True))
    monkeypatch.setattr(nh, "routed_experts", functools.partial(nh.routed_experts, kernel=True))
    c = dataclasses.replace(nh.PRESETS["nemotron-3-super-120b-a12b"], vocab_size=16384,
                            layer_types=nh.pattern(nh.PUBLISHED[:11]), experts_held=tuple(range(64)))
    one_chip = SingleDeviceSharding(v5e[0])
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    params = place(jax.eval_shape(lambda: nh.init_params(c, jax.random.key(0))))
    cache = place(jax.eval_shape(lambda: nh.init_paged_cache(c, _NEMOTRON_PAGES, PAGE, max_slots=_NEMOTRON_SLOTS)))
    vec = lambda *shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    return nh, c, params, cache, vec


def test_nemotron_walk_compiles_at_two_kv_heads_and_a_group_of_sixteen(v5e):
    text = _compile_walk(v5e, 32, 2, 128, jnp.bfloat16, False).as_text()
    assert "tpu_custom_call" in text


def test_nemotron_decode_block_updates_the_state_in_place(v5e, monkeypatch):
    """128 lanes of the benchmark's cut, steps in a loop as the engine's
    decode block nests them: the resident set is the issue's arithmetic
    (5.50 GB of weights, 5.49 GB of state with its snapshot), the whole state
    is DONATED and aliased from argument to result through every layer loop,
    no op copies the stack of `S` (4 MiB a slot and layer: 2.7 GB a copy) or
    makes a layer's lanes of it a value of their own, and the block's
    temporaries are a small fraction of the state."""
    import re

    nh, c, params, cache, vec = _nemotron(v5e, monkeypatch)
    S = _NEMOTRON_SLOTS

    def block(p, ca, tok, n, tables, active):
        def step(carry, _):
            ca, tok, n = carry
            ca, logits = nh.decode_step_paged(p, ca, tok, n, tables, active, c, use_pallas=True)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            return (ca, tok, n + 1), tok

        (ca, _, _), toks = jax.lax.scan(step, (ca, tok, n), None, length=4)
        return ca, toks

    compiled = jax.jit(block, donate_argnums=(1,)).lower(
        params, cache, vec(S), vec(S), vec(S, 4096 // PAGE), vec(S, dt=jnp.bool_)).compile()
    text = compiled.as_text()
    assert all(name in text for name in ("ssm_update", "moe_gmm", "paged_page_walk"))
    # the recurrence is ONE kernel a Mamba layer, from the conv's rows to the gated, normed row: a custom call where a
    # Mamba layer's body is traced (the scan's period and the two written out), and no `ssd_gate_norm` op in the block
    updates = [line for line in text.splitlines() if "custom-call(" in line and "ssm_update" in line.split(" = ")[0]]
    assert len(updates) == 3, [line.split(" = ")[0].strip() for line in updates]
    tiles = f"bf16[{S},{c.n_groups},{c.d_inner // c.n_groups // 128},128]"  # a lane's channels a group, a block, a block's lanes
    assert all(tiles in line for line in updates), "the row leaves the kernel in the model's dtype"
    assert "ssd_gate_norm" not in text
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params))
    state = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(cache["state"]))
    assert abs(weights - 5.50e9) < 0.01e9 and abs(state - 5.49e9) < 0.01e9
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < state // 20, f"temporaries {mem.temp_size_in_bytes / 1e6:.0f} MB"
    stack = rf"f32\[{c.n_mamba},{S + 1},64,128,128\]"
    assert re.search(stack, text)
    assert not re.search(rf"= {stack}\S* copy\(", text), "a copy of the whole stack of S"
    assert f"f32[{S},64,128,128]" not in text, "a layer's lanes of the state as a value of their own"
    assert 0.6 * 16e9 < _resident(compiled) < 12.5e9, f"{_resident(compiled) / 1e9:.2f} GB"


@pytest.mark.parametrize("program", ["prefill", "continuation"])
def test_nemotron_prefill_of_2048_tokens_fits_beside_the_resident_set(v5e, monkeypatch, program):
    """The widest prefill the file admits (one row of 2,048) with the chunked
    scan, and its continuation over 4,096 tokens of pages: weights, state and
    pool resident, the temporaries beside them, under the chip's 16 GB; no
    per-token array of the state anywhere."""
    nh, c, params, cache, vec = _nemotron(v5e, monkeypatch)
    B, T = 1, 2048
    if program == "prefill":
        fn = lambda p, ca, tok, n, ids, slots, snap: nh.prefill_paged_batch(p, ca, tok, n, ids, (slots, snap), c)  # noqa: E731
        args = (params, cache, vec(B, T), vec(B), vec(B, T // PAGE), vec(B), vec(B))
    else:
        fn = lambda p, ca, tok, n, st, ids, tb, slots, snap: nh.prefill_paged_continue(  # noqa: E731
            p, ca, tok, n, st, ids, tb, (slots, snap), c)
        args = (params, cache, vec(B, T), vec(B), vec(B), vec(B, T // PAGE), vec(B, 4096 // PAGE), vec(B), vec(B))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    text = compiled.as_text()
    assert "ssm_scan" in text and "moe_gmm" in text
    assert f"f32[{B},{T},64,128,128]" not in text and f"f32[{T},64,128,128]" not in text
    assert _resident(compiled) < 13e9, f"{_resident(compiled) / 1e9:.1f} GB"
