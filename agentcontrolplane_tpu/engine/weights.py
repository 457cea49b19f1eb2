"""Checkpoint loading: HF Llama weights -> our pytree, sharded at load.

The reference's llm-controller validates SaaS credentials; ours loads and
shards checkpoints (north star: "the llm-controller loads and shards HF
checkpoints across chips"). Supports:

- a directory of ``*.safetensors`` (HF format), loaded file-by-file and
  ``jax.device_put`` directly to each param's NamedSharding (never
  materializing the full model unsharded on one device);
- an in-memory HF state_dict (tests: convert a tiny random
  ``transformers.LlamaForCausalLM`` and compare logits).
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.llama import LlamaConfig, init_params

# our pytree path -> HF tensor name (per layer where {i})
_LAYER_MAP = {
    "wq": "model.layers.{i}.self_attn.q_proj.weight",
    "wk": "model.layers.{i}.self_attn.k_proj.weight",
    "wv": "model.layers.{i}.self_attn.v_proj.weight",
    "wo": "model.layers.{i}.self_attn.o_proj.weight",
    "w1": "model.layers.{i}.mlp.gate_proj.weight",
    "w3": "model.layers.{i}.mlp.up_proj.weight",
    "w2": "model.layers.{i}.mlp.down_proj.weight",
    "ln1": "model.layers.{i}.input_layernorm.weight",
    "ln2": "model.layers.{i}.post_attention_layernorm.weight",
}
_TRANSPOSED = {"wq", "wk", "wv", "wo", "w1", "w2", "w3"}
_BIAS_MAP = {
    "bq": "model.layers.{i}.self_attn.q_proj.bias",
    "bk": "model.layers.{i}.self_attn.k_proj.bias",
    "bv": "model.layers.{i}.self_attn.v_proj.bias",
}


def config_from_hf(config_path: str) -> LlamaConfig:
    with open(config_path) as f:
        hf = json.load(f)
    is_gemma2 = hf.get("model_type") == "gemma2"
    is_gemma = hf.get("model_type") == "gemma" or is_gemma2
    act = hf.get("hidden_activation") or hf.get("hidden_act") or "silu"
    rs = hf.get("rope_scaling") or {}
    rs_type = rs.get("rope_type") or rs.get("type")
    if rs and rs_type != "llama3":
        # linear/dynamic/yarn checkpoints would silently serve the wrong
        # function — refuse at load, not at generation quality
        raise ValueError(
            f"unsupported rope_scaling type {rs_type!r} (only 'llama3')"
        )
    return LlamaConfig(
        rope_scaling_factor=float(rs.get("factor", 1.0)) if rs_type == "llama3" else 1.0,
        rope_low_freq_factor=float(rs.get("low_freq_factor", 1.0)),
        rope_high_freq_factor=float(rs.get("high_freq_factor", 4.0)),
        rope_original_max_seq=int(rs.get("original_max_position_embeddings", 8192)),
        # Mixtral: routed experts replace the dense FFN
        n_experts=int(hf.get("num_local_experts", 0) or 0),
        experts_per_token=int(hf.get("num_experts_per_tok", 2) or 2),
        vocab_size=hf["vocab_size"],
        dim=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        ffn_dim=hf["intermediate_size"],
        norm_eps=hf.get("rms_norm_eps", 1e-5),
        rope_theta=hf.get("rope_theta", 500000.0),
        max_seq_len=hf.get("max_position_embeddings", 8192),
        # gemma ties embeddings unconditionally
        tie_embeddings=bool(hf.get("tie_word_embeddings", is_gemma)),
        # Qwen2 checkpoints set attention_bias (or are the qwen2 model_type)
        qkv_bias=bool(hf.get("attention_bias", hf.get("model_type") == "qwen2")),
        hidden_act="gelu_tanh" if ("gelu" in act or is_gemma) else "silu",
        norm_plus_one=is_gemma,
        embed_scale=is_gemma,
        head_dim_override=hf.get("head_dim") if is_gemma else None,
        # Gemma-2: tanh soft-caps, four-norm blocks, explicit query scale,
        # alternating sliding-window layers (see LlamaConfig.sliding_window
        # for the context bound the engine enforces)
        attn_logit_softcap=float(hf.get("attn_logit_softcapping") or 0.0) if is_gemma2 else 0.0,
        final_logit_softcap=float(hf.get("final_logit_softcapping") or 0.0) if is_gemma2 else 0.0,
        post_norms=is_gemma2,
        query_pre_attn_scalar=float(hf.get("query_pre_attn_scalar") or 0.0) if is_gemma2 else 0.0,
        sliding_window=int(hf.get("sliding_window") or 0) if is_gemma2 else 0,
    )


def params_from_state_dict(
    state_dict: dict[str, Any],
    config: LlamaConfig,
    put: Optional[Callable[[str, np.ndarray], jax.Array]] = None,
    quantize: Optional[str] = None,
    lora: Optional[tuple] = None,  # (lora_params_as_numpy, LoraConfig)
) -> dict:
    """Build the params pytree from HF-named tensors.

    ``state_dict`` values may be numpy arrays or torch tensors. ``put``
    receives (pytree_path, ndarray) and returns the placed jax array —
    the seam where sharded device_put happens. With ``quantize="int8"`` the
    layer matrices are quantized HOST-SIDE before placement, so the bf16
    copy of an 8B model never touches the device (16GB-chip serving path).
    """
    from ..ops.quant import QUANTIZABLE, QuantizedTensor

    c = config
    if quantize not in (None, "int8"):
        raise ValueError(f"unsupported quantization {quantize!r}")
    if put is None:
        # quantized leaves keep their exact dtypes (int8 values, f32 scales);
        # everything else is cast to the model compute dtype
        put = lambda path, arr: jnp.asarray(
            arr,
            dtype=arr.dtype if path.endswith((".q", ".scale")) else c.dtype,
        )

    def get(name: str) -> np.ndarray:
        t = state_dict[name]
        if hasattr(t, "detach"):  # torch tensor
            t = t.detach().to("cpu").float().numpy()
        return np.asarray(t)

    params: dict = {
        "embed": put("embed", get("model.embed_tokens.weight")),
        "norm": put("norm", get("model.norm.weight")),
        "layers": {},
    }
    layer_map = dict(_LAYER_MAP)
    if c.qkv_bias:
        layer_map.update(_BIAS_MAP)
    if c.post_norms:
        # Gemma-2's four-norm block: HF's post_attention_layernorm norms the
        # attention OUTPUT (unlike llama, where that name is the pre-MLP
        # norm), and pre/post_feedforward_layernorm bracket the MLP
        layer_map["ln1_post"] = "model.layers.{i}.post_attention_layernorm.weight"
        layer_map["ln2"] = "model.layers.{i}.pre_feedforward_layernorm.weight"
        layer_map["ln2_post"] = "model.layers.{i}.post_feedforward_layernorm.weight"
    if c.n_experts > 0:
        # Mixtral: the dense MLP keys are replaced by per-expert stacks
        # (HF names the expert projections literally w1/w2/w3) + the router
        for key in ("w1", "w2", "w3"):
            layer_map.pop(key)
        layer_map["router"] = "model.layers.{i}.block_sparse_moe.gate.weight"
        layer_map.update({
            key: "model.layers.{i}.block_sparse_moe.experts.{e}." + key + ".weight"
            for key in ("w1", "w2", "w3")
        })
    for key, pattern in layer_map.items():
        mats = []
        for i in range(c.n_layers):
            if "{e}" in pattern:
                # [E, in, out] expert stack for this layer
                m = np.stack([
                    get(pattern.format(i=i, e=e)).T for e in range(c.n_experts)
                ])
            else:
                m = get(pattern.format(i=i))
                if key in _TRANSPOSED or key == "router":
                    m = m.T  # HF stores [out, in]; we compute x @ W
            mats.append(m)
        stacked = np.stack(mats)
        if lora is not None and key in lora[0]["layers"]:
            # merge the adapter HOST-SIDE, before quantization and before
            # anything reaches the device — an on-device merge of an 8B
            # model would put bf16 params + merged copies on a 16GB chip
            ab = lora[0]["layers"][key]
            stacked = stacked + np.einsum(
                "lir,lro->lio",
                np.asarray(ab["a"], dtype=np.float32),
                np.asarray(ab["b"], dtype=np.float32),
            ) * lora[1].scale
        if quantize == "int8" and key in QUANTIZABLE:
            from ..ops.quant import quantize_np

            q, scale = quantize_np(stacked)
            params["layers"][key] = QuantizedTensor(
                q=put(f"layers.{key}.q", q),
                scale=put(f"layers.{key}.scale", scale),
            )
        else:
            params["layers"][key] = put(f"layers.{key}", stacked)
    if not c.tie_embeddings:
        params["lm_head"] = put("lm_head", get("lm_head.weight").T)
    return params


def load_safetensors_dir(
    path: str,
    config: Optional[LlamaConfig] = None,
    put: Optional[Callable[[str, np.ndarray], jax.Array]] = None,
    quantize: Optional[str] = None,
    lora_path: Optional[str] = None,
) -> tuple[dict, LlamaConfig]:
    """Load an HF checkpoint directory (config.json + *.safetensors).
    ``lora_path`` merges a trained adapter (train.lora.save_lora) host-side
    BEFORE quantization/placement, so adapter+int8 serving never
    materializes an unquantized model on device."""
    from safetensors import safe_open  # lazy: not all installs ship it

    if config is None:
        config = config_from_hf(os.path.join(path, "config.json"))
    lora = None
    if lora_path is not None:
        from ..train.lora import load_lora

        lora_params, lora_cfg = load_lora(lora_path, config)
        lora = (jax.tree_util.tree_map(np.asarray, lora_params), lora_cfg)
    tensors: dict[str, np.ndarray] = {}
    for fname in sorted(os.listdir(path)):
        if not fname.endswith(".safetensors"):
            continue
        with safe_open(os.path.join(path, fname), framework="np") as f:
            for name in f.keys():
                tensors[name] = f.get_tensor(name)
    params = params_from_state_dict(tensors, config, put, quantize=quantize, lora=lora)
    return params, config


def write_synthetic_checkpoint(
    path: str,
    config: LlamaConfig,
    seed: int = 0,
    max_shard_bytes: int = 1 << 30,
) -> int:
    """Write a random-weight HF-format checkpoint (config.json +
    sharded ``*.safetensors`` + ``model.safetensors.index.json``) with the
    same tensor names, bf16 dtype, and shard layout a real Llama-3
    checkpoint ships with (values are random). Exists to close the
    no-egress verification gap — the load/quantize/shard path can be
    exercised at full Llama-3-8B scale (~16 GiB on disk) without
    downloading weights. Memory-bounded: one tensor generated at a time,
    shards flushed at ``max_shard_bytes``. Returns total bytes written.

    Plain Llama/Mistral architecture only: the qkv-bias (Qwen2), MoE
    (Mixtral) and Gemma variants need extra/renamed tensors this
    generator does not emit, and serving a silently wrong-shaped
    checkpoint would be worse than refusing."""
    import ml_dtypes
    from safetensors.numpy import save_file

    c = config
    if (
        c.qkv_bias
        or c.n_experts
        or c.head_dim_override is not None
        or c.norm_plus_one
        or c.embed_scale
        or c.hidden_act != "silu"
    ):
        raise ValueError(
            "write_synthetic_checkpoint supports the plain Llama/Mistral "
            "architecture only (no qkv_bias / MoE experts / Gemma or "
            "non-silu variants)"
        )
    hd = c.head_dim
    os.makedirs(path, exist_ok=True)
    # A rerun into the same dir must not mix generations (the loader reads
    # EVERY *.safetensors in the directory) — but NEVER clobber a real
    # checkpoint: only a dir this generator marked (config.json carries
    # "synthetic": true; unknown keys are ignored by config_from_hf) or a
    # shard-free dir may be cleared. Deleting ~16 GiB of downloaded
    # weights in a no-egress environment would be irreversible.
    existing = [f for f in os.listdir(path) if f.endswith(".safetensors")]
    if existing:
        try:
            with open(os.path.join(path, "config.json")) as f:
                marked = bool(json.load(f).get("synthetic"))
        except (OSError, json.JSONDecodeError):
            marked = False
        if not marked:
            raise ValueError(
                f"{path} contains safetensors shards not written by this "
                "generator; refusing to overwrite a (possibly real) "
                "checkpoint — pick an empty/new directory"
            )
    for f in os.listdir(path):
        if f.endswith(".safetensors") or f == "model.safetensors.index.json":
            os.unlink(os.path.join(path, f))
    hf_config: dict[str, Any] = {
        "synthetic": True,  # marks the dir as regenerable (see above)
        "model_type": "llama",
        "vocab_size": c.vocab_size,
        "hidden_size": c.dim,
        "num_hidden_layers": c.n_layers,
        "num_attention_heads": c.n_heads,
        "num_key_value_heads": c.n_kv_heads,
        "intermediate_size": c.ffn_dim,
        "rms_norm_eps": c.norm_eps,
        "rope_theta": c.rope_theta,
        "max_position_embeddings": c.max_seq_len,
        "tie_word_embeddings": c.tie_embeddings,
    }
    if c.rope_scaling_factor != 1.0:  # llama3.1/3.2-style scaled checkpoints
        hf_config["rope_scaling"] = {
            "rope_type": "llama3",
            "factor": c.rope_scaling_factor,
            "low_freq_factor": c.rope_low_freq_factor,
            "high_freq_factor": c.rope_high_freq_factor,
            "original_max_position_embeddings": c.rope_original_max_seq,
        }
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf_config, f)

    # per-key HF shapes (HF stores linear weights (out, in)); NAMES come
    # from the loader's own _LAYER_MAP so generator/loader agreement is
    # structural, not a coincidence of two hand-typed lists
    hf_shape = {
        "wq": (c.n_heads * hd, c.dim),
        "wk": (c.n_kv_heads * hd, c.dim),
        "wv": (c.n_kv_heads * hd, c.dim),
        "wo": (c.dim, c.n_heads * hd),
        "w1": (c.ffn_dim, c.dim),
        "w3": (c.ffn_dim, c.dim),
        "w2": (c.dim, c.ffn_dim),
        "ln1": (c.dim,),
        "ln2": (c.dim,),
    }
    assert set(hf_shape) == set(_LAYER_MAP), "shape table drifted from _LAYER_MAP"

    def tensor_plan():
        yield "model.embed_tokens.weight", (c.vocab_size, c.dim), "normal"
        for i in range(c.n_layers):
            for key, pattern in _LAYER_MAP.items():
                kind = "ones" if key.startswith("ln") else "normal"
                yield pattern.format(i=i), hf_shape[key], kind
        yield "model.norm.weight", (c.dim,), "ones"
        if not c.tie_embeddings:
            yield "lm_head.weight", (c.vocab_size, c.dim), "normal"

    rng = np.random.default_rng(seed)
    shard: dict[str, np.ndarray] = {}
    shard_bytes = 0
    shard_files: list[str] = []  # temp names; renamed to -of- form at the end
    weight_map: dict[str, int] = {}  # tensor -> shard ordinal
    total = 0

    def flush():
        nonlocal shard, shard_bytes
        if not shard:
            return
        fname = f"model-{len(shard_files) + 1:05d}.safetensors.tmp"
        save_file(shard, os.path.join(path, fname))
        shard_files.append(fname)
        shard = {}
        shard_bytes = 0

    for name, shape, kind in tensor_plan():
        if kind == "ones":
            t = np.ones(shape, dtype=ml_dtypes.bfloat16)
        else:
            t = (rng.standard_normal(shape, dtype=np.float32) * 0.02).astype(
                ml_dtypes.bfloat16
            )
        shard[name] = t
        weight_map[name] = len(shard_files) + 1
        shard_bytes += t.nbytes
        total += t.nbytes
        if shard_bytes >= max_shard_bytes:
            flush()
    flush()

    # HF shard naming needs the total count, known only now; plus the
    # index HF's own loader requires for sharded checkpoints
    n = len(shard_files)
    final = {
        i + 1: f"model-{i + 1:05d}-of-{n:05d}.safetensors" for i in range(n)
    }
    for i, tmp in enumerate(shard_files):
        os.replace(os.path.join(path, tmp), os.path.join(path, final[i + 1]))
    with open(os.path.join(path, "model.safetensors.index.json"), "w") as f:
        json.dump({
            "metadata": {"total_size": total},
            "weight_map": {k: final[v] for k, v in weight_map.items()},
        }, f)
    return total


def sharded_init(
    config: LlamaConfig,
    key: jax.Array,
    shardings: Optional[dict] = None,
) -> dict:
    """Random params, placed per-leaf onto their shardings (benchmarks)."""
    params = init_params(config, key)
    if shardings is None:
        return params
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), params, shardings
    )


# std of an integer uniform on [-127, 127]: sqrt((255**2 - 1) / 12)
_UNIFORM_INT8_STD = 73.61


def random_quantized_init(config: LlamaConfig, seed: int = 0) -> dict:
    """Random int8 params built HOST-SIDE tensor-by-tensor (benchmarks,
    chip_smoke.py).

    The device-init-then-quantize path peaks at the full bf16 model plus
    one tensor — 16GB for Llama-3-8B, which alone fills a v5e chip. Here
    only the int8 values + f32 scales (plus the bf16 embeddings/norms/head)
    ever reach the device. Same pytree layout as
    ``models.llama.init_params``.

    The int8 values are drawn directly — raw generator bits, uniform over
    [-127, 127] — with one scale per output channel that gives the matrix
    the variance of a ``fan_in**-0.5`` normal init. Drawing float normals
    and quantizing them on the host is minutes of single-threaded CPU at
    7B parameters, for weights that are random either way."""
    from ..ops.quant import QUANTIZABLE, QuantizedTensor

    c = config
    rng = np.random.default_rng(seed)

    def put(arr: np.ndarray, keep_dtype: bool = False) -> jax.Array:
        return jnp.asarray(arr, dtype=arr.dtype if keep_dtype else c.dtype)

    # the schema (keys, shapes, optional qkv_bias / tied-head branches) is
    # DERIVED from init_params via eval_shape — one source of truth; only
    # the per-leaf value policy (ones for norms, zeros for biases, scaled
    # normal for matrices, int8 for quantizable layer matrices) lives here
    schema = jax.eval_shape(lambda: init_params(c, jax.random.key(0)))

    def leaf(path, sds) -> Any:
        name = str(path[-1].key)
        in_layers = len(path) >= 2 and str(path[-2].key) == "layers"
        shape = sds.shape
        if name.startswith("ln") or name == "norm":
            return put(np.ones(shape, dtype=np.float32))
        if name.startswith("b"):
            return put(np.zeros(shape, dtype=np.float32))
        fan_in = shape[-1] if name == "embed" else shape[-2]
        if in_layers and name in QUANTIZABLE:
            n = int(np.prod(shape))
            q = rng.bit_generator.random_raw(-(-n // 8)).view(np.int8)[:n]
            q = np.maximum(q, -127, out=q).reshape(shape)  # symmetric int8
            qscale = np.full(
                shape[:-2] + (1, shape[-1]),
                fan_in**-0.5 / _UNIFORM_INT8_STD,
                dtype=np.float32,
            )
            return QuantizedTensor(
                q=put(q, keep_dtype=True), scale=put(qscale, True)
            )
        return put(rng.standard_normal(shape, dtype=np.float32) * fan_in**-0.5)

    return jax.tree_util.tree_map_with_path(leaf, schema)
