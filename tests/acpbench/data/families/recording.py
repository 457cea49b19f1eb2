"""A second family, brought as files only: every part delegates to `llama`
and records that it was the one called. `tiny-config-recording.json` names
it; no file of `acpbench/` knows it."""

from acpbench.families import llama

CALLS: list[str] = []


def program_config(config):
    CALLS.append("program_config")
    return llama.program_config(config)


def weights(config, program_config, mesh, seed):
    CALLS.append("weights")
    return llama.weights(config, program_config, mesh, seed)


def reference_logits(config, params, tokens, rows, lower=None):
    CALLS.append("reference_logits")
    return llama.reference_logits(config, params, tokens, rows, lower=lower)


def cached_logits(config, program_config, params, mesh, sample, use_pallas, **control):
    CALLS.append("cached_logits")
    return llama.cached_logits(config, program_config, params, mesh, sample, use_pallas, **control)
