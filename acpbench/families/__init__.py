"""Model families: the one seam between the harness and the program's models.

A configuration's file states `"family": "<name>"`, and `spec.family(config)`
imports `<package>.<name>` from `spec.FAMILY_PACKAGES` (this package first).
A family module is the only place in the harness that imports
`agentcontrolplane_tpu.models.*` or knows a weight leaf by its name; what
belongs to it alone (its plain reference, its value policy for weights)
sits beside it under the family's name (`llama_reference.py`,
`llama_weights.py`). A later PR brings a family as new files and edits none.

`config` below is the configuration's file as a dict. A family gives four
functions:

`program_config(config) -> object`
    The program's own model config, built from the file's keys (the
    source's `config.json` names, plus whatever block of the program's own
    names the family documents). `System` hands it to `Engine(config=...)`
    and back to the two functions below; the harness reads nothing in it.

`weights(config, program_config, mesh, seed) -> pytree`
    The seeded weights on `mesh`, made on the device in one jitted call, in
    the types they are served in: the weight precision is the file's
    `engine.quantize`, which `Engine` is given too. The same seed gives the
    same bits. The engine serves these arrays and the reference reads them.

`reference_logits(config, params, tokens, rows, lower=None) -> [B, R, V] float32`
    The plain reference over `params`: logits of `tokens` [B, T] at
    positions `rows` [B, R], each predicting the token after it; sequences
    left-aligned and causal. It imports nothing of the program. `lower`
    names one of the family's controls, the same pass in a stated lower
    precision or with a stated fault; a name the family lacks is an error.

`cached_logits(config, program_config, params, mesh, sample, use_pallas, **control) -> (pre, dec)`
    The program's own prefill and then decode through its cache, on the
    engine's mesh, over `check.sample`'s sequences: `pre` [B, N+1, V] from
    prefills of the prompt and of the prompt plus 1..N forced tokens, `dec`
    [B, N, V] from N teacher-forced decode steps after the prompt's
    prefill, both float32. The family builds the cache and its shardings
    itself (`sample["B"]` sequences, `sample["pool_pages"]` pages of
    `sample["P"]` tokens, whatever per-slot state it keeps beside them).
    Keyword arguments beyond these are the family's own lower-precision
    paths, the controls of `cache_excess`.

The pool. What the harness holds the program's `init_paged_cache` to, and
all it holds it to: every leaf not under `"state"` is `[layers, pages, page
rows, ...]`, and axis 3 carries the KV heads: apart (`[L, pages, P, H_kv,
d]`), merged into the row (`[L, pages, P, H_kv*d]`, as the page walk reads
it) or as a scale column (`[L, pages, P, H_kv]`). Axis 3 is the one a
tensor-parallel mesh splits, so a family shards each such leaf
`PartitionSpec(None, None, None, "tp")`, which is valid at any rank of 4 or
more, and what is under `"state"` whole. Nothing else is asserted of a
leaf's rank or trailing shape: the program's own functions
(`prefill_paged_batch`, `decode_step_paged`) take the pool as
`init_paged_cache` made it, and a program that changes the layout changes
those three together and no file here.

Everything else (`check.sample`, `compare`, `engine_path`, `engine_numbers`,
`decide`, the generators, readers and the run itself) is shared and knows
no family.
"""
