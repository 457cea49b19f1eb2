"""`scripts/program_text.py`: the digest a refactor that changes no program is
held to. One program (`ouro-tiny`'s decode step): its stripped text is the
same from call to call, and the same when the family's source stands a line
lower, where the program as traced is not."""

import importlib.util
import sys
import types
from pathlib import Path

import jax

from agentcontrolplane_tpu.models import ouro

SCRIPT = Path(__file__).resolve().parents[2] / "scripts" / "program_text.py"


def test_the_digest_is_stable_and_blind_to_a_blank_line_above_the_function():
    spec = importlib.util.spec_from_file_location("program_text", SCRIPT)
    pt = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pt)
    (fn, args), = [(fn, args) for name, fn, args in pt.programs("ouro") if name == "decode_step_paged"]
    assert pt.digest(fn, *args) == pt.digest(fn, *args)

    lower = types.ModuleType("agentcontrolplane_tpu.models._ouro_a_line_lower")
    lower.__package__ = "agentcontrolplane_tpu.models"
    sys.modules[lower.__name__] = lower  # a dataclass looks its module up
    try:
        exec(compile("\n" + Path(ouro.__file__).read_text(), ouro.__file__, "exec"), lower.__dict__)
    finally:
        del sys.modules[lower.__name__]
    c = lower.PRESETS["ouro-tiny"]
    moved = lambda pr, ca, t, n, tb, a: lower.decode_step_paged(pr, ca, t, n, tb, a, c)  # noqa: E731
    lowered = [jax.jit(f).lower(*args) for f in (fn, moved)]
    as_traced = [low.as_text(debug_info=True) for low in lowered]  # the lines stand apart (no cache stands between)
    assert as_traced[0] != as_traced[1]
    compiled = [pt.stripped(low.compile().as_text()) for low in lowered]
    assert compiled[0] == compiled[1]
    assert 'op_name="' in compiled[0] and "stack_frame_id" not in compiled[0]
