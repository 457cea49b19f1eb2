"""Page-walk parity: the compiled kernel against the XLA gather reference.

One definition shared by ``chip_smoke.py`` and
``tests/engine/test_tpu_hardware.py`` (and usable in interpret mode on the
CPU): seeded ragged pages at a named geometry, the kernel and
``ops.paged``'s reference run on the default device, the largest absolute
difference judged against a tolerance set from the page dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.paged import (
    TRASH_PAGE,
    PageAllocator,
    paged_decode_attention_reference,
    paged_decode_attention_reference_cache_plus_new,
)
from ..ops.pallas.paged_attention import (
    paged_decode_attention,
    paged_decode_attention_cache_plus_new,
)
from ..ops.quant import kv_quantize

# Both sides accumulate in f32 (the kernel at f32 contract precision, the
# reference under default_matmul_precision("highest")); what differs is the
# order of reduction and, for bf16 q/pages, the final cast of the output.
TOLERANCE = {"float32": 2e-4, "bfloat16": 2e-2}


def make_paged_case(
    seed: int, *, S: int, H: int, H_kv: int, d: int, P: int = 16,
    max_pages: int = 8, num_pages: int = 128, dtype=jnp.float32,
    int8: bool = False,
) -> dict:
    """Seeded ragged sequences scattered over an allocator's pages (page 0
    stays the trash page). ``int8`` quantizes the pools with
    ``ops.quant.kv_quantize`` and adds the f32 scale twins."""
    rng = np.random.default_rng(seed)
    seq_lens = rng.integers(1, max_pages * P, size=S).astype(np.int32)
    k_pages = np.zeros((num_pages, P, H_kv, d), dtype=np.float32)
    v_pages = np.zeros((num_pages, P, H_kv, d), dtype=np.float32)
    alloc = PageAllocator(num_pages)
    tables = np.full((S, max_pages), TRASH_PAGE, dtype=np.int32)
    for s in range(S):
        n = -(-int(seq_lens[s]) // P)
        pages = alloc.alloc(n)
        tables[s, :n] = pages
        kv = rng.normal(size=(2, int(seq_lens[s]), H_kv, d)).astype(np.float32)
        for j, page in enumerate(pages):
            lo, hi = j * P, min((j + 1) * P, int(seq_lens[s]))
            k_pages[page, : hi - lo] = kv[0][lo:hi]
            v_pages[page, : hi - lo] = kv[1][lo:hi]
    case = {
        "q": jnp.asarray(rng.normal(size=(S, H, d)), dtype=dtype),
        "k_pages": jnp.asarray(k_pages, dtype=dtype),
        "v_pages": jnp.asarray(v_pages, dtype=dtype),
        "block_tables": jnp.asarray(tables),
        "seq_lens": jnp.asarray(seq_lens),
        "k_new": jnp.asarray(rng.normal(size=(S, H_kv, d)), dtype=dtype),
        "v_new": jnp.asarray(rng.normal(size=(S, H_kv, d)), dtype=dtype),
        "scales": {},
    }
    if int8:
        case["k_pages"], ks = kv_quantize(case["k_pages"])
        case["v_pages"], vs = kv_quantize(case["v_pages"])
        case["scales"] = {"k_scales": ks, "v_scales": vs}
    return case


def page_walk_parity(
    case: dict, *, plus_new: bool = True, interpret: bool = False
) -> dict:
    """Run the kernel (compiled unless ``interpret``) and the reference on
    the same operands; returns ``{max_abs_err, tolerance, ok, ...}``.
    ``plus_new`` selects the serving hot-path form (read-only pages + the
    new token's self term) over the classic written-pages-only form."""
    args = [
        case["q"], case["k_pages"], case["v_pages"],
        case["block_tables"], case["seq_lens"],
    ]
    if plus_new:
        args += [case["k_new"], case["v_new"]]
        kernel, reference = (
            paged_decode_attention_cache_plus_new,
            paged_decode_attention_reference_cache_plus_new,
        )
    else:
        kernel, reference = paged_decode_attention, paged_decode_attention_reference
    scales = case["scales"]
    out = jax.jit(
        lambda *a, **kw: kernel(*a, interpret=interpret, **kw)
    )(*args, **scales)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(reference)(*args, **scales)
    out = np.asarray(out.astype(jnp.float32))
    ref = np.asarray(ref.astype(jnp.float32))
    err = float(np.max(np.abs(out - ref)))
    tol = TOLERANCE[jnp.dtype(case["q"].dtype).name]
    finite = bool(np.isfinite(out).all())
    return {
        "max_abs_err": err,
        "tolerance": tol,
        "finite": finite,
        "shape": tuple(out.shape),
        "seq_lens": [int(n) for n in np.asarray(case["seq_lens"])],
        "ok": finite and out.shape == ref.shape and err <= tol,
    }
