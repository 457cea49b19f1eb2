"""The compiled text of every program the engine builds from a family, as a digest.

    PYTHONPATH=. JAX_PLATFORMS=cpu python scripts/program_text.py [family ...] [--long]

prints one line a program: ``<family> <program> <sha256>`` of its compiled CPU
HLO text (``jax.jit(f).lower(...).compile().as_text()``) with the source
locations stripped: the tables of file names, function names, lines and stack
frames in the module's head, and each operation's ``stack_frame_id=`` (older
texts: ``source_file=`` / ``source_line=``). What stays is every operation, its
order, its shapes and dtypes and its ``op_name=`` metadata, so ``jax.named_scope``
and jitted functions' names are held too. Two trees whose tables are equal
compile the same programs at these sizes; a refactor that claims to change no
program runs it on both: the package is whichever tree ``PYTHONPATH`` names
(``git archive <parent> | tar -x -C _parent``, then ``PYTHONPATH=_parent``).

Each family is its tiny preset, abstract weights and caches (nothing is
allocated or run). ``--long`` adds the shapes whose trace differs by SIZE: a
prefill of 4,096 rows (past ``MOE_CHUNK`` and the attention's blocks) and a
continuation of 1,024 (past ``CONTINUE_BLOCK`` query rows and, over a table of
3,072 rows and the rows themselves, whole ``KEY_BLOCK``s of keys).
"""

from __future__ import annotations

import argparse
import hashlib
import re
import sys
from types import SimpleNamespace

FAMILIES = {
    "llama": "tiny", "lfm2": "lfm2-tiny", "jamba": "jamba-tiny", "mellum": "mellum-tiny", "kanana": "kanana-tiny",
    "ouro": "ouro-tiny", "exaone": "exaone-tiny", "nemotron_h": "nemotron-h-tiny", "keye": "keye-tiny",
    "dots": "dots-tiny",
}
PAGE, SLOTS = 16, 4
_TABLES = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:\d+ .*\n)*\n?", re.M)
_FIELDS = re.compile(r' ?(?:stack_frame_id=\d+|source_file="[^"]*"|source_line=\d+|source_end_line=\d+|'
                     r'source_column=\d+|source_end_column=\d+)')


def stripped(text: str) -> str:
    """Compiled HLO text without what says where in the source it came from."""
    return _FIELDS.sub("", _TABLES.sub("", text)).replace("metadata={}", "")


def program_text(fn, *args) -> str:
    import jax

    return stripped(jax.jit(fn).lower(*args).compile().as_text())


def digest(fn, *args) -> str:
    return hashlib.sha256(program_text(fn, *args).encode()).hexdigest()


def _greedy_sampler() -> SimpleNamespace:
    """What a drafting family's step asks of the engine, at its plainest: the draft is the drafted logits'
    largest, kept where the verified row agrees."""
    import jax.numpy as jnp

    def accept(logits, draft, q_logits):
        first = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        kept = first == draft
        second = jnp.where(kept, jnp.argmax(logits[:, 1], axis=-1).astype(jnp.int32), -1)
        return jnp.stack([first, second], axis=1), 1 + kept.astype(jnp.int32), kept

    return SimpleNamespace(propose=lambda q: (jnp.argmax(q, axis=-1).astype(jnp.int32), q), accept=accept)


def programs(family: str, long: bool = False):
    """(name, function, abstract arguments) of every program the engine builds from ``family``'s tiny preset."""
    import jax
    import jax.numpy as jnp

    from agentcontrolplane_tpu import models

    c = models.preset(FAMILIES[family])
    p = models.programs(c)
    arr = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype)  # noqa: E731
    params = jax.eval_shape(lambda: p.init_params(c, jax.random.PRNGKey(0)))
    out = []

    def paged(B, T, M, tag="", whole=True, continued=True):
        cache = jax.eval_shape(lambda: p.init_paged_cache(c, B * M + 1, PAGE, max_slots=SLOTS))
        ids = arr((B, T // PAGE))
        if p.has_state:
            ids = (ids, (arr((B,)), arr((B,))))
        rows = (arr((B, T)), arr((B,)))
        if whole:
            out.append((f"prefill_paged_batch{tag}", lambda pr, ca, t, n, i: p.prefill_paged_batch(pr, ca, t, n, i, c),
                        (params, cache, *rows, ids)))
        for name in ("prefill_paged_continue", "prefill_paged_continue_kv") if continued else ():
            fn = getattr(p, name)
            out.append((f"{name}{tag}", lambda pr, ca, t, n, s, i, tb, fn=fn: fn(pr, ca, t, n, s, i, tb, c),
                        (params, cache, *rows, arr((B,)), ids, arr((B, M)))))
        return cache

    cache = paged(2, 32, 8)
    step = (arr((SLOTS,)), arr((SLOTS,)), arr((SLOTS, 8)), arr((SLOTS,), jnp.bool_))
    out.append(("decode_step_paged", lambda pr, ca, t, n, tb, a: p.decode_step_paged(pr, ca, t, n, tb, a, c),
                (params, cache, *step)))
    if p.draft_step is not None:
        out.append(("draft_step", lambda pr, ca, t, n, tb, a: p.draft_step(pr, ca, t, n, tb, a, _greedy_sampler(), c),
                    (params, cache, *step)))
    if p.has_state and not p.window_cache:
        state = jax.eval_shape(lambda ca: p.saved_state(ca, 0), cache)
        out.append(("saved_state", p.saved_state, (cache, arr(()))))
        out.append(("install_state", p.install_state, (cache, arr(()), state)))
    if family == "llama":
        rows = (arr((2, 32)), arr((2,)))
        out.append(("verify_paged_continue", lambda pr, ca, t, n, s, tb: p.verify_paged_continue(pr, ca, t, n, s, tb, c),
                    (params, cache, *rows, arr((2,)), arr((2, 8)))))
        slot = jax.eval_shape(lambda: p.init_kv_cache(c, SLOTS, 128))
        out.append(("prefill_batch", lambda pr, ca, t, n, s: p.prefill_batch(pr, ca, t, n, s, c),
                    (params, slot, *rows, arr((2,)))))
        for name in ("prefill_continue", "prefill_continue_kv"):
            fn = getattr(p, name)
            out.append((name, lambda pr, ca, t, n, s, sl, fn=fn: fn(pr, ca, t, n, s, sl, c),
                        (params, slot, *rows, arr((2,)), arr((2,)))))
        out.append(("verify_continue", lambda pr, ca, t, n, s: p.verify_continue(pr, ca, t, n, s, c),
                    (params, slot, *rows, arr((2,)))))
        out.append(("decode_step", lambda pr, ca, t, n: p.decode_step(pr, ca, t, n, c),
                    (params, slot, arr((SLOTS,)), arr((SLOTS,)))))
    if long:
        paged(1, 4096, 256, "@4096", continued=False)
        paged(1, 1024, 192, "@1024", whole=False)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("families", nargs="*", default=[], help=f"of {', '.join(FAMILIES)}; none: all")
    ap.add_argument("--long", action="store_true", help="also the shapes past the blocks' sizes (module text)")
    args = ap.parse_args(argv)
    unknown = [f for f in args.families if f not in FAMILIES]
    if unknown:
        ap.error(f"no family {unknown[0]!r}; there are {', '.join(FAMILIES)}")
    for family in args.families or FAMILIES:
        for name, fn, a in programs(family, args.long):
            print(family, name, digest(fn, *a), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
