"""The weights a decode step of a looped model must read.

A stack of `layers` layers run `loops` times over the same weights reads
every layer's matrices once a loop: the weights do not fit the chip's fast
memory (103 MB a layer at the published sizes against 128 MiB for all of
it), so a loop cannot keep them, and a decode step's matmuls at 8 rows are
bound by those bytes (8 operations a byte of bfloat16 where the chip's
peaks meet at 240). A layer's matrices: `wq`, `wk`, `wv`, `wo` (`hidden x
heads x head_dim` each, K and V at their own head count) and the SwiGLU's
three (`hidden x intermediate`). The head (`hidden x vocab`) is read once a
step. Norm gains, the gate and the embedding's rows are not counted: a
thousandth of it. By this count no reading can pass 100%: what the program
reads beside these (activations, K and V written) only adds to its time.
"""

from __future__ import annotations


def layer_values(*, hidden: int, heads: int, kv_heads: int, head_dim: int, intermediate: int) -> int:
    """Values of one layer's seven matrices."""
    return hidden * head_dim * (2 * heads + 2 * kv_heads) + 3 * hidden * intermediate


def bytes_per_step(*, hidden: int, heads: int, kv_heads: int, head_dim: int, intermediate: int, vocab: int,
                   layers: int, loops: int, bytes_per_element: int = 2) -> int:
    """HBM bytes of weights one decode step's matmuls must read on one chip."""
    layer = layer_values(hidden=hidden, heads=heads, kv_heads=kv_heads, head_dim=head_dim, intermediate=intermediate)
    return (loops * layers * layer + hidden * vocab) * bytes_per_element


def from_config(c: dict) -> int:
    """`bytes_per_step` of a configuration's file (the source's keys; the
    precision from `engine.quantize`: absent is bfloat16, "int8" a byte)."""
    return bytes_per_step(
        hidden=c["hidden_size"], heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], intermediate=c["intermediate_size"], vocab=c["vocab_size"],
        layers=c["num_hidden_layers"], loops=c["total_ut_steps"],
        bytes_per_element=1 if c["engine"].get("quantize") == "int8" else 2)
