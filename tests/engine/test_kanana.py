"""Kanana-2 through the normal path: the latent walk (Pallas interpret mode)
against its XLA reference; the absorbed decode against the expanded form in
float32; the program against the plain reference
(acpbench/families/kanana_reference.py, which imports nothing of the program)
for the forward pass, prefill then decode through the latent pool across page
and turn boundaries, and continuation; the source's interleaved rotary pairs
against the de-interleaved weights; the shares of a layer's experts, the
shared expert counted once, against the uncut layer. The engine serving it,
and the seam it is asked through: `test_kanana_engine.py`.

CPU, tiny sizes (a dense layer and 3 expert layers, 4 heads, latent 32, rope
8, 16 experts top-2 of which 2 held, one shared), float32, seeded weights.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from acpbench import check, spec
from acpbench.families import kanana as family_module
from acpbench.families import kanana_reference
from agentcontrolplane_tpu.models import kanana, preset, programs
from agentcontrolplane_tpu.ops import paged
from agentcontrolplane_tpu.ops.moe import routed_experts
from agentcontrolplane_tpu.ops.pallas import paged_attention as pa
from agentcontrolplane_tpu.ops.rope import apply_rope, deinterleave_pairs
from agentcontrolplane_tpu.parallel.mesh import make_mesh

FILE = spec.load_json(spec.os.path.join(spec.ROOT, "tests/acpbench/data/tiny-config-kanana.json"))
PAGE = FILE["engine"]["page_size"]
ONE_CHIP = lambda: make_mesh({"tp": 1}, devices=jax.devices()[:1])  # noqa: E731


def tiny(**over):
    config = dict(FILE)
    config["check"] = dict(FILE["check"], **over.pop("check", {}))
    return {**config, **over}


def built(config, seed=5):
    family = spec.family(config)
    pc = dataclasses.replace(family.program_config(config), dtype=jnp.float32)
    return family, pc, ONE_CHIP(), family.weights(config, pc, ONE_CHIP(), seed)


# -- the latent walk -----------------------------------------------------------------


_TURN = 512  # rows a turn of the committed latent walk covers at page 16 (`pages_per_turn(..., leaves=1)`: 32 pages)


@pytest.mark.parametrize("lens", [
    (0, 1, 15, 16, 17, 127),  # nothing to walk, inside a page, across the first page boundaries, a lane tile less one
    (128, 129, 255, 256, 257, 1000),  # across lane tiles inside a turn, a second turn
    # every boundary of the turn: 1, T - 1, T, T + 1, 2T, 2T + 1 rows, empty slots first, between and last,
    # a slot of one turn after a long one
    (0, 1, _TURN - 1, 0, _TURN, _TURN + 1, 2 * _TURN, 2 * _TURN + 1, 100, 0),
], ids=["pages", "tiles", "turns"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_latent_walk_agrees_with_its_reference(lens, dtype):
    """One fetch a page, the row both key and value: the interpreted kernel
    against `ops/paged.py`'s reference in the same absorbed form, at the
    published row (640 stored, value 512, scores over sqrt(192)), in float32
    (exact) and in bfloat16 (the two-pass second product). Every page no
    slot's rows reach is NaN: a turn always fetches its 32 pages, past the
    walk's end the last live page again and never the table's next."""
    S, H, W, V, P, L, M = len(lens), 8, 640, 512, 16, 2, 2 * _TURN // 16 + 1
    assert pa.pages_per_turn(P, dtype, 1, W, leaves=1) * P == _TURN
    NP = 1 + S * M
    key = jax.random.key(0)
    clean = jax.random.normal(jax.random.fold_in(key, 1), (L * NP, P, W), jnp.float32).astype(dtype)
    q = (jax.random.normal(jax.random.fold_in(key, 2), (S, H, W), jnp.float32) * 0.3).astype(dtype)
    new = jax.random.normal(jax.random.fold_in(key, 3), (S, W), jnp.float32).astype(dtype)
    n = jnp.asarray(lens, jnp.int32)
    tables = paged.layer_tables(1 + jnp.arange(S * M, dtype=jnp.int32).reshape(S, M), 1, NP)
    named = np.zeros((L * NP,), bool)
    for s, rows_held in enumerate(lens):
        named[np.asarray(tables)[s, : -(-rows_held // P)]] = True
    pages = jnp.where(jnp.asarray(named)[:, None, None], clean, jnp.nan)
    want = paged.latent_decode_attention_reference_cache_plus_new(q, clean, tables, n, new, V, 192)
    got = pa.paged_latent_attention_cache_plus_new(q, pages, tables, n, new, V, 192, interpret=True)
    assert got.shape == (S, H, V)
    assert np.isfinite(np.asarray(got, np.float32)).all(), "a walk read a page that is not its own"
    tol = 5e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol)
    # and against plain attention over the rows laid out by position
    rows = np.asarray(clean.astype(jnp.float32))[np.asarray(tables)].reshape(S, M * P, W)
    for s in range(S):
        ctx = np.concatenate([rows[s, : lens[s]], np.asarray(new.astype(jnp.float32))[s][None]])
        logits = np.asarray(q.astype(jnp.float32))[s] @ ctx.T * 192 ** -0.5
        p = np.exp(logits - logits.max(-1, keepdims=True))
        np.testing.assert_allclose(np.asarray(got, np.float32)[s], (p / p.sum(-1, keepdims=True)) @ ctx[:, :V],
                                   atol=5e-5 if dtype == jnp.float32 else 3e-2)


def test_the_walk_reports_one_fetch_a_page_and_serves_the_published_row():
    """The engine asks `models.programs(...).walk` for the geometry: a latent
    row is one leaf and its turn is four lane tiles of rows (32 pages of 16:
    one chain a turn, so the turn is wide; `paged_attention.py`, "Latent
    walk"), which keeps twice the bytes in flight that K and V pages of the
    same width keep at a tile a turn; a row whose value is not whole lane
    tiles has no kernel (the tiny config: the reference serves)."""
    full, small = preset("kanana-2-30b-a3b-ep16"), preset("kanana-tiny")
    assert (full.row_width, full.row_stored, full.head_dim, full.n_kv_heads) == (576, 640, 640, 1)
    G, turns, in_flight = programs(full).walk(full, 16, jnp.bfloat16, 1, False)
    assert (G, turns) == (32, pa.RING - 1) and G * 16 == _TURN and in_flight == turns * 32 * 16 * 640 * 2 == 1_966_080
    assert pa.RING * 32 * 16 * 640 * 2 <= pa._SCRATCH_BUDGET
    assert pa.fetches_in_flight(16, jnp.bfloat16, 1, 640) == (turns, in_flight // 2)  # K and V pages: 8 a turn, two leaves
    assert programs(small).walk(small, 8, jnp.float32, 1, False) is None
    # every other family's walk is the parent's: a lane tile a turn
    for name, page_rows, want in [("qwen2.5-7b", 16, (8, 3, 786_432)), ("lfm2-24b-a2b-ep8", 16, (8, 3, 786_432)),
                                  ("jamba2-3b", 16, (8, 3, 196_608)), ("mellum2-12b-a2.5b-ep4", 16, (8, 3, 786_432))]:
        cfg = preset(name)
        assert programs(cfg).walk(cfg, page_rows, jnp.bfloat16, 1, False) == want, name
    big = preset("qwen2.5-7b")
    assert programs(big).walk(big, 16, jnp.bfloat16, 2, False) == (8, 3, 393_216)  # two KV heads a chip


# -- the program against the plain reference ------------------------------------------


def test_forward_agrees_with_the_plain_reference():
    config = tiny()
    family, pc, mesh, params = built(config)
    tokens = np.random.default_rng(1).integers(0, 256, (2, 40)).astype(np.int32)
    rows = np.tile(np.arange(40), (2, 1))
    want = family.reference_logits(config, params, tokens, rows)
    got = kanana.forward(params, jnp.asarray(tokens), pc)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("seed", [11, 2**31 + 7], ids=["seed-11", "seed-over-31-bits"])
def test_program_agrees_with_the_plain_reference_through_the_latent_pool(seed):
    """The family's cache check as every run of the cell makes it: expanded
    prefills, then absorbed decode steps through the pool, across page
    boundaries (page 8, prompts of 24-56 and 8 steps)."""
    config = tiny()
    family, pc, mesh, params = built(config, seed=seed)
    s = check.sample(config["check"], config["vocab_size"], PAGE, seed)
    want = check.reference_logits(functools.partial(family.reference_logits, config, params), s)
    numbers = check.compare(family.cached_logits(config, pc, params, mesh, s, False), want)
    assert numbers["finite"] and numbers["top1_agree"] == 1.0
    assert numbers["prefill_rel_rms"] < 2e-5 and numbers["decode_rel_rms"] < 2e-5, numbers


def test_absorbed_and_expanded_agree_in_float32_and_decode_expands_no_row():
    """Prefill of 16, a continuation of 8 (gathered latent rows expanded with
    its own), then decode steps: each gives `forward`'s logits, the rows
    they leave are the rows one prefill of all leaves, and the counters say
    which path ran."""
    cfg = preset("kanana-tiny")
    params = kanana.init_params(cfg, jax.random.key(3))
    # compiled, as the engine runs them (eagerly, every op of every layer is a program of its own)
    jit = lambda f, **kw: jax.jit(functools.partial(f, config=cfg, **kw))  # noqa: E731
    forward, prefill, continuation = jit(kanana.forward), jit(kanana.prefill_paged_batch), jit(kanana.prefill_paged_continue)
    step = {walk: jit(kanana.decode_step_paged, interpret=walk) for walk in (False, True)}
    B, P, cut, mid, T = 2, 8, 16, 24, 28
    tokens = np.random.default_rng(4).integers(0, cfg.vocab_size, (B, 32)).astype(np.int32)
    want = forward(params, jnp.asarray(tokens[:, :T]))
    close = functools.partial(np.testing.assert_allclose, rtol=2e-5, atol=2e-5)
    i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    full = lambda v: jnp.full((B,), v, jnp.int32)  # noqa: E731
    pages = i32([[1, 2, 3, 4], [5, 6, 7, 8]])
    empty = kanana.init_paged_cache(cfg, 9, P, max_slots=B)
    assert set(empty) == {"kv", "state"} and empty["kv"].shape == (cfg.n_layers, 9, P, cfg.row_stored)
    whole, _ = prefill(params, empty, tokens, full(T), pages)
    padded = lambda rows: np.pad(rows, ((0, 0), (0, 32 - rows.shape[1])))  # noqa: E731
    cache, got = prefill(params, empty, padded(tokens[:, :cut]), full(cut), pages.at[:, cut // P:].set(0))
    close(got, want[:, cut - 1])
    ids = jnp.zeros((B, 4), jnp.int32).at[:, 0].set(pages[:, cut // P])
    cache, got = continuation(params, cache, padded(tokens[:, cut:mid]), full(mid - cut), full(cut), ids, pages)
    close(got, want[:, mid - 1])
    for t in range(mid, T):
        cache, got = step[t % 2 == 0](params, cache, i32(tokens[:, t]), full(t), pages, jnp.ones((B,), bool))
        close(got, want[:, t])
    rows = lambda tree: np.asarray(tree["kv"])[:, np.asarray(pages)].reshape(cfg.n_layers, B, 32, -1)[:, :, :T]  # noqa: E731
    close(rows(cache), rows(whole))
    assert float(np.abs(rows(cache)[..., cfg.row_width:]).max()) == 0.0  # the padding columns stay zero
    latent = kanana.describe_counters(cfg, np.asarray(cache["state"]["counts"]))["latent"]
    # whole turns where the kernel walked (the even steps; a turn here is 64 pages of 8), the table of 4 pages where the reference gathered
    fetched = B * sum(64 * P if t % 2 == 0 else 4 * P for t in range(mid, T))
    assert latent["decode"] == {"steps": T - mid, "rows_read": B * sum(range(mid + 1, T + 1)), "rows_expanded": 0,
                                "rows_fetched": fetched}
    # rows as `_expand` took them: the prefill's 32 with its padding, the continuation's whole table of 4 pages and its 32
    assert latent["prefill"]["rows_read"] == B * (cut + mid) and latent["row_values"] == cfg.row_width
    assert latent["prefill"]["rows_expanded"] == B * (32 + 4 * P + 32)
    assert latent["prefill"]["rows_fetched"] == B * 4 * P  # the continuation's gathered table; a whole prompt fetches none


@pytest.mark.parametrize("program,expanded", [("prefill", 2 * 32), ("continuation", 2 * (4 * 8 + 16)), ("decode", 0)])
def test_rows_expanded_is_what_the_attention_path_that_ran_put_through_expand(program, expanded):
    """The count comes from `_expand` by way of the path (`attend`), not
    from the program's entry: a dispatch of each program adds what its path
    expanded, padding and a continuation's whole table among it, and the
    absorbed decode step, which calls no `_expand`, adds nothing."""
    cfg = preset("kanana-tiny")
    params = kanana.init_params(cfg, jax.random.key(3))
    B, P = 2, 8
    pages = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    full = lambda v: jnp.full((B,), v, jnp.int32)  # noqa: E731
    cache = kanana.init_paged_cache(cfg, 9, P, max_slots=B)
    if program == "prefill":
        cache, _ = kanana.prefill_paged_batch(params, cache, jnp.ones((B, 32), jnp.int32), full(20), pages, cfg)
    elif program == "continuation":
        cache, _ = kanana.prefill_paged_continue(params, cache, jnp.ones((B, 16), jnp.int32), full(8), full(16),
                                                 pages[:, 2:], pages, cfg)
    else:
        cache, _ = kanana.decode_step_paged(params, cache, full(1), full(20), pages, jnp.ones((B,), bool), cfg)
    latent = kanana.describe_counters(cfg, np.asarray(cache["state"]["counts"]))["latent"]
    row = latent["decode" if program == "decode" else "prefill"]
    assert row["steps"] == 1 and row["rows_expanded"] == expanded


def test_the_interleaved_pairs_and_the_deinterleaved_weights_are_one_rotation():
    """`from_published` against the source's own form: neighbours rotated on
    the published columns give the scores halves rotated on the
    de-interleaved columns give, and the layout's other parts are the
    source's matrices' parts."""
    cfg = preset("kanana-tiny")
    H, nope, rope, r, D = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank, cfg.dim
    keys = jax.random.split(jax.random.key(7), 4)
    q_proj = jax.random.normal(keys[0], (D, H * (nope + rope)))
    kv_a = jax.random.normal(keys[1], (D, r + rope))
    kv_b = jax.random.normal(keys[2], (r, H * (nope + cfg.v_head_dim)))
    x = jax.random.normal(keys[3], (1, 9, D))
    served = kanana.from_published(q_proj, kv_a, kv_b, cfg)
    positions = jnp.arange(9)[None]
    # the source: rotate neighbours of the published columns
    q_pub = (x @ q_proj).reshape(1, 9, H, nope + rope)[..., nope:]
    k_pub = (x @ kv_a)[..., None, r:]
    theta = cfg.rope_theta
    want = jnp.einsum("bqhd,bkd->bhqk", kanana_reference._rope(q_pub, theta), kanana_reference._rope(k_pub, theta)[:, :, 0])
    # the program: rotate halves of the de-interleaved columns
    q_pe = apply_rope((x @ served["wq_pe"].T).reshape(1, 9, H, rope), positions, theta)
    k_pe = apply_rope((x @ served["wk_pe"])[..., None, :], positions, theta)[:, :, 0]
    np.testing.assert_allclose(jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe), want, atol=2e-4, rtol=2e-5)
    np.testing.assert_allclose(kanana_reference._as_published(deinterleave_pairs(q_pub)), q_pub)
    kvb = kv_b.reshape(r, H, nope + cfg.v_head_dim)
    np.testing.assert_allclose(served["wuk"], jnp.transpose(kvb[..., :nope], (1, 2, 0)))
    np.testing.assert_allclose(served["wuv"], jnp.transpose(kvb[..., nope:], (1, 0, 2)))
    np.testing.assert_allclose(served["wq_nope"].T.reshape(D, H, nope), q_proj.reshape(D, H, -1)[..., :nope])
    np.testing.assert_allclose(served["wkv_c"], kv_a[:, :r])


def test_the_eight_shares_and_the_shared_expert_once_sum_to_the_uncut_layer():
    """A layer's FF summed over eight chips' routed shares (each told which
    16 of 128 it holds, each routing over all 128 by the sigmoid, the bias
    and the scaling factor) plus the shared expert ONCE is the uncut
    reference's layer; with the shared expert in every share it is not."""
    N, D, F, E, k, SW = 24, 64, 32, 128, 6, 48
    keys = jax.random.split(jax.random.key(3), 9)
    x = jax.random.normal(keys[0], (N, D))
    layer = {"router": jax.random.normal(keys[1], (D, E)) * D ** -0.5,
             "router_bias": 0.03 * jax.random.normal(keys[2], (E,)),
             "w1": jax.random.normal(keys[3], (E, D, F)) * D ** -0.5,
             "w3": jax.random.normal(keys[4], (E, D, F)) * D ** -0.5,
             "w2": jax.random.normal(keys[5], (E, F, D)) * F ** -0.5,
             "sw1": jax.random.normal(keys[6], (D, SW)) * D ** -0.5,
             "sw3": jax.random.normal(keys[7], (D, SW)) * D ** -0.5,
             "sw2": jax.random.normal(keys[8], (SW, D)) * SW ** -0.5}
    model = {"experts_per_token": k, "held": tuple(range(E)), "norm_topk_prob": True, "routed_scaling_factor": 2.448}
    whole = kanana_reference._experts(x[None], layer, model, None)[0][0]
    shared = (jax.nn.silu(x @ layer["sw1"]) * (x @ layer["sw3"])) @ layer["sw2"]
    total, landed = jnp.zeros_like(whole), 0
    for share in range(8):
        held = tuple(range(16 * share, 16 * share + 16))
        ids = np.array(held)
        y, counts = routed_experts(x, layer["router"], layer["w1"][ids], layer["w3"][ids], layer["w2"][ids], k,
                                   held=held, score="sigmoid", bias=layer["router_bias"], scale=2.448,
                                   interpret=share % 2 == 0)
        total, landed = total + y, landed + int(counts[1])
    assert landed == N * k  # every (token, choice) pair landed on exactly one share
    np.testing.assert_allclose(total + shared, whole, atol=3e-5)
    assert float(jnp.abs(total + 8 * shared - whole).max()) > 0.1


@pytest.mark.parametrize("control,least", [
    ("int8_matmul_inputs", 5e-3), ("scale_128", 0.02), ("rope_all", 0.1), ("kv_norm_off", 0.1),
    ("k_pe_unroped", 0.05), ("shared_off", 0.05), ("route_scale_off", 0.01), ("bias_off", 0.01),
])
def test_each_reference_control_moves_the_logits(control, least):
    config = tiny()
    family, pc, mesh, params = built(config)
    s = check.sample(config["check"], config["vocab_size"], PAGE, 3)
    reference = functools.partial(family.reference_logits, config, params)
    want = check.reference_logits(reference, s)
    moved = check.compare(check.reference_logits(reference, s, lower=control), want)["logit_rel_rms"]
    assert moved > least, (control, moved)


def test_the_stated_precision_passes_and_an_unknown_control_is_an_error():
    config = tiny()
    family, pc, mesh, params = built(config)
    s = check.sample(config["check"], config["vocab_size"], PAGE, 3)
    reference = functools.partial(family.reference_logits, config, params)
    want = check.reference_logits(reference, s)
    # (at this size a flipped choice of experts leads either reading: the order of the precisions is the chip's to show)
    assert check.compare(check.reference_logits(reference, s, lower="bf16"), want)["logit_rel_rms"] < 0.1
    with pytest.raises(ValueError, match="no control"):
        reference(s["tokens"], s["rows"], lower="fp4")
    for name in kanana_reference.CONTROLS:
        assert f'"{name}"' in family_module.__doc__ or f"`\"{name}\"`" in family_module.__doc__, name


@pytest.mark.parametrize("control,key,least", [({"kv_int8": True}, "cache_excess", 0.5)])
def test_each_cache_control_is_seen(control, key, least):
    config = tiny()
    family, pc, mesh, params = built(config)
    s = check.sample(config["check"], config["vocab_size"], PAGE, 3)
    want = check.reference_logits(functools.partial(family.reference_logits, config, params), s)
    sound = check.compare(family.cached_logits(config, pc, params, mesh, s, False), want)
    seen = check.compare(family.cached_logits(config, pc, params, mesh, s, False, **control), want)
    assert seen[key] > least > abs(sound[key]), (sound[key], seen[key])


def test_the_value_policy_makes_a_wrong_path_show():
    """`kanana_weights`: the raw latent's RMS is away from 1, the norm's
    gains too, the rope part carries a real share of a score, and the bias
    changes the choice in a fair share of rows."""
    config = tiny()
    family, pc, mesh, params = built(config)
    x = jax.random.normal(jax.random.key(0), (64, pc.dim))
    x = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True))
    layer = jax.tree_util.tree_map(lambda a: a[0], params["attn"])
    raw = x @ layer["wkv_c"]
    assert 1.5 < float(jnp.sqrt(jnp.mean(raw * raw))) < 2.5
    assert float(jnp.std(params["attn"]["kv_norm"])) > 0.2
    assert 0.7 < float(jnp.sqrt(jnp.mean((x @ layer["wk_pe"]) ** 2))) < 1.3
    ff = jax.tree_util.tree_map(lambda a: a[0], params["ff"])
    s = jax.nn.sigmoid(x @ ff["router"])
    with_bias = jax.lax.top_k(s + ff["router_bias"], pc.experts_per_token)[1]
    without = jax.lax.top_k(s, pc.experts_per_token)[1]
    changed = float(jnp.mean(jnp.any(jnp.sort(with_bias) != jnp.sort(without), axis=-1)))
    assert 0.1 < changed < 0.95, changed
    routed, dense = params["ff"]["w2"], params["pro"][0]["w2"]
    assert float(jnp.std(routed)) * routed.shape[-2] ** 0.5 > 1.5 * float(jnp.std(dense)) * dense.shape[-2] ** 0.5
