"""The llama family's cache check takes the pool in whatever layout the
program stores it (`acpbench/families/__init__.py`, "The pool").

`cached_logits` runs twice at the tiny size of `test_outputs_control.py`:
against the program as it is (`k` / `v` as `[L, pages, P, H_kv, d]`), and
against a test double, the three `models.llama` functions wrapped where the
family imports them so that `k` / `v` live as `[L, pages, P, H_kv*d]`
between calls: merged on the way out, split on the way in. That is the
layout the page walk reads and the `lfm2` family already stores; a
`perf_opt` PR that stores the llama pool so changes those three functions
and may edit nothing here. The two `(pre, dec)` pairs are equal bit for
bit, on one device and on a `tp=2` mesh, with bf16 and with int8 pages
(whose scale leaves are of rank 4 in both layouts).

On the parent's harness (PR 31, `families/llama.py:77-80`) the double's run
raises before a logit is made, at any `tp`: `out_shardings` of
`PartitionSpec(None, None, None, "tp", None)` for `k` / `v` is "only valid
for values of rank at least 5", and the merged leaves are of rank 4 (shown
once by hand with that file checked out; PERF.md, Findings, PR 32). That is
what this test is for."""

import os

import numpy as np
import pytest

from acpbench import check, spec, study

DATA = os.path.join(os.path.dirname(__file__), "data")
SEED = 2**31 + 32
POOLED = ("k", "v")


def _merged(pool: dict) -> dict:
    return {name: a.reshape(*a.shape[:3], -1) if name in POOLED else a for name, a in pool.items()}


def _split(pool: dict, c) -> dict:
    return {name: a.reshape(*a.shape[:3], c.n_kv_heads, c.head_dim) if name in POOLED else a
            for name, a in pool.items()}


def store_the_pool_merged(monkeypatch) -> list:
    """`models.llama`'s three pool functions with `k` / `v` merged between
    calls. Returns the list the double appends each pool's shapes to, so
    that a test can see which layout went through the harness."""
    from agentcontrolplane_tpu.models import llama

    init, prefill, decode = llama.init_paged_cache, llama.prefill_paged_batch, llama.decode_step_paged
    seen: list = []

    def init_merged(config, *args, **kw):
        pool = _merged(init(config, *args, **kw))
        seen.append({name: a.shape for name, a in pool.items()})
        return pool

    def prefill_merged(params, pages, tokens, lengths, page_ids, config):
        assert all(pages[name].ndim == 4 for name in POOLED)
        pages, logits = prefill(params, _split(pages, config), tokens, lengths, page_ids, config)
        return _merged(pages), logits

    def decode_merged(params, pages, tokens, seq_lens, block_tables, active, config, **kw):
        assert all(pages[name].ndim == 4 for name in POOLED)
        pages, logits = decode(params, _split(pages, config), tokens, seq_lens, block_tables, active, config, **kw)
        return _merged(pages), logits

    monkeypatch.setattr(llama, "init_paged_cache", init_merged)
    monkeypatch.setattr(llama, "prefill_paged_batch", prefill_merged)
    monkeypatch.setattr(llama, "decode_step_paged", decode_merged)
    return seen


@pytest.fixture(scope="module", params=[1, 2], ids=["one-device", "tp2"])
def system(request):
    tp = request.param
    config = spec.load_json(os.path.join(DATA, "tiny-config.json"))
    config = dict(config, engine=dict(config["engine"], tensor_parallelism=tp))
    program_config, mesh, params = study._engine_free_system(config, SEED)
    assert mesh.shape["tp"] == tp
    s = check.sample(config["check"], config["vocab_size"], config["engine"]["page_size"], SEED)
    return config, program_config, mesh, params, s


@pytest.mark.parametrize("quantize_kv", [False, True], ids=["bf16-pages", "int8-pages"])
def test_a_merged_pool_gives_the_same_logits_bit_for_bit(system, monkeypatch, quantize_kv):
    config, program_config, mesh, params, s = system
    family = spec.family(config)
    as_it_is = family.cached_logits(config, program_config, params, mesh, s, False, quantize_kv=quantize_kv)
    seen = store_the_pool_merged(monkeypatch)
    merged = family.cached_logits(config, program_config, params, mesh, s, False, quantize_kv=quantize_kv)
    c = program_config
    rows = (c.n_layers, s["pool_pages"], s["P"])
    want = {"k": rows + (c.n_kv_heads * c.head_dim,), "v": rows + (c.n_kv_heads * c.head_dim,)}
    if quantize_kv:
        want.update(ks=rows + (c.n_kv_heads,), vs=rows + (c.n_kv_heads,))
    assert want in seen  # the double's pool is the one the harness built, sharded and handed on
    for name, a, b in zip(("pre", "dec"), as_it_is, merged):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and np.isfinite(a).all() and a.std() > 0, name
        assert np.array_equal(a, b), (name, float(np.abs(a - b).max()))

