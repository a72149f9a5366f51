"""GPT-2 (Radford et al. 2019; ``openai-community/gpt2``): learned positions,
LayerNorm with bias, fused qkv, ``gelu_new`` MLP at four times the width.
The program's side is ``ray_tpu/models/gpt2.py``."""

from __future__ import annotations

from typing import Any, Dict

from perfbench.harness.families import published


def shape(config: Dict[str, Any], chips: int) -> Dict[str, int]:
    d = config["n_embd"]
    return {"d_model": d, "n_layer": published(config, chips, "n_layer"),
            "n_head": config["n_head"], "n_kv_head": config["n_head"],
            "head_dim": d // config["n_head"],
            "vocab": config["vocab_size"],
            # fused qkv + out_proj + fc_in + fc_out at 4x width
            "layer_mm_params": 3 * d * d + d * d + 2 * d * 4 * d}


def model_config(config: Dict[str, Any], chips: int):
    """Activations bf16, parameters float32, flash attention: the program's
    defaults, stated in the configuration file."""
    from ray_tpu.models.gpt2 import GPT2Config

    remat = config["remat"]
    return GPT2Config(
        vocab_size=config["vocab_size"], n_positions=config["n_positions"],
        n_embd=config["n_embd"], n_layer=shape(config, chips)["n_layer"],
        n_head=config["n_head"], remat=bool(remat),
        remat_policy=remat or "full")


def logits(params, ids, config: Dict[str, Any]):
    import jax
    import jax.numpy as jnp

    from perfbench.harness.reference import (causal_attention, dense, heads,
                                             layer_norm, merge)

    eps = config["program_departures"]["layer_norm_epsilon"]["program"]
    h = config["n_head"]
    n_layer = sum(1 for k in params if k.startswith("h_"))
    x = params["wte"]["embedding"][ids] + \
        params["wpe"]["embedding"][: ids.shape[1]]
    for i in range(n_layer):
        p = params[f"h_{i}"]
        q, k, v = jnp.split(
            dense(layer_norm(x, p["ln_1"], eps), p["attn"]["qkv_proj"]),
            3, axis=-1)
        a = causal_attention(heads(q, h)[:, :, None], heads(k, h),
                             heads(v, h))
        x = x + dense(merge(a), p["attn"]["out_proj"])
        m = jax.nn.gelu(
            dense(layer_norm(x, p["ln_2"], eps), p["mlp"]["fc_in"]),
            approximate=True)   # gelu_new
        x = x + dense(m, p["mlp"]["fc_out"])
    x = layer_norm(x, params["ln_f"], eps)
    return (x @ params["lm_head"]["kernel"])[..., : config["vocab_size"]]
