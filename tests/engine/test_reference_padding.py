"""What the tests' greedy reference rests on.

`agentcontrolplane_tpu.testing.greedy_reference` runs a family's plain
`forward` over one row padded to a fixed width and reads the last real row,
so that every prompt length shares one compiled program. That is right only
while a family's forward is blind to what lies to the right of a position:
attention, the short conv and the Mamba scans (both) are causal, and the routed
layers choose a token's experts from that token alone (a capacity rule
would break it). Here each family's padded rows are held to its forward at
the exact length. A family that fails keeps exact lengths in its own file.

CPU, float32, the tiny presets, seeded weights.
"""

import jax
import numpy as np
import pytest

from agentcontrolplane_tpu.models import jamba, kanana, keye, lfm2, llama, mellum, nemotron_h, ouro, preset
from agentcontrolplane_tpu.testing import compiled, greedy_reference, padded_logits

FAMILIES = {"llama": ("tiny", llama), "lfm2": ("lfm2-tiny", lfm2), "jamba": ("jamba-tiny", jamba),
            "mellum": ("mellum-tiny", mellum), "kanana": ("kanana-tiny", kanana), "ouro": ("ouro-tiny", ouro),
            "nemotron_h": ("nemotron-h-tiny", nemotron_h), "keye": ("keye-tiny", keye)}
WIDTH = 128


@pytest.mark.parametrize("name", list(FAMILIES))
def test_a_padded_row_reads_as_the_exact_length_forward(name):
    preset_name, model = FAMILIES[name]
    config = preset(preset_name)
    params = model.init_params(config, jax.random.key(0))
    exact = compiled(model.forward, config)  # a program a length
    tokens = np.random.default_rng(11).integers(0, 256, 61).tolist()
    for length in (9, 61):  # under mellum-tiny's window of 32 and past it
        want = np.asarray(exact(params, np.asarray([tokens[:length]], np.int32)))[0]
        got = padded_logits(model.forward, params, config, tokens[:length], WIDTH)
        assert got.shape == want.shape
        assert np.abs(got - want).max() < 2e-4 * np.abs(want).max(), (name, length)
        assert (got.argmax(-1) == want.argmax(-1)).all(), (name, length)
    # and the greedy walk over the padded row is the walk at exact lengths
    toks = tokens[:9]
    for _ in range(3):
        toks.append(int(np.asarray(exact(params, np.asarray([toks], np.int32)))[0, -1].argmax()))
    assert greedy_reference(model.forward, params, config, tokens[:9], 3, WIDTH) == toks[9:]
