"""The prefill's attention over the rows a learned indexer keeps (today the
kernel `masked_prefill_attention`, `ops/pallas/masked_attention.py`): the
operations it must do and the bytes it must read.

Query `t` of a prompt attends over the `min(t + 1, topk)` rows chosen for it,
so a prompt of `n` tokens has `topk (topk + 1) / 2 + (n - topk) topk` KEPT
pairs of a query and a key (`n (n + 1) / 2` where `n <= topk`), and a layer
takes, a kept pair and query head, a score and a value product: `4 *
head_dim` operations. That is the least ANY implementation must do, whatever
it does today: the kernel as written computes every causal pair, chosen or
not (it skips the key blocks wholly after a query block and no key the mask
hides: one row in six to thirteen is kept at the cell's contexts, so it
reads a fifth and less of what a count of causal pairs would give it; a
prefill that skips what the mask hides is ROADMAP M10 (b) and could not pass
100% by this count, as it would by that one). It reads q, k, v and writes
the result once a layer; a mask is the present implementation's and no part
of the least. Bound by operations everywhere the cell runs it.
"""

from __future__ import annotations


def pairs(prompt_lens, topk: int) -> int:
    """Kept pairs: query t keeps min(t + 1, topk) of its causal keys."""
    total = 0
    for n in prompt_lens:
        n, k = int(n), min(int(n), topk)
        total += k * (k + 1) // 2 + (n - k) * topk
    return total


def flops(prompt_lens, *, topk: int, heads: int, head_dim: int, n_layers: int) -> int:
    """q.k and p.v of every kept pair, every query head and layer."""
    return pairs(prompt_lens, topk) * 4 * head_dim * heads * n_layers


def bytes_(prompt_lens, *, heads: int, kv_heads: int, head_dim: int, n_layers: int, bytes_per_element: int = 2) -> int:
    """q and the result (`heads` wide), k and v (`kv_heads` wide), once a layer."""
    return sum(int(n) for n in prompt_lens) * (2 * heads + 2 * kv_heads) * head_dim * bytes_per_element * n_layers


def least_seconds(prompt_lens, peaks: dict, *, topk: int, heads: int, kv_heads: int, head_dim: int, n_layers: int) -> float:
    return max(flops(prompt_lens, topk=topk, heads=heads, head_dim=head_dim, n_layers=n_layers) / peaks["bf16_flops"],
               bytes_(prompt_lens, heads=heads, kv_heads=kv_heads, head_dim=head_dim, n_layers=n_layers)
               / peaks["hbm_bytes_per_s"])
