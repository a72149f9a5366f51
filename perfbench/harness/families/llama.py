"""The Llama block — RoPE, RMSNorm, SwiGLU, grouped-query attention, untied
head, no biases — as ``ray_tpu/models/llama.py`` runs it.  Mistral-7B
(``mistralai/Mistral-7B-v0.3``) is not of the Llama family; it is the public
model of another family that this code runs unchanged."""

from __future__ import annotations

from typing import Any, Dict

from perfbench.harness.families import published


def shape(config: Dict[str, Any], chips: int) -> Dict[str, int]:
    d, h = config["hidden_size"], config["num_attention_heads"]
    kv, ff = config["num_key_value_heads"], config["intermediate_size"]
    hd = d // h
    return {"d_model": d,
            "n_layer": published(config, chips, "num_hidden_layers"),
            "n_head": h, "n_kv_head": kv, "head_dim": hd,
            "vocab": config["vocab_size"],
            # wq, wo; wk, wv at the kv heads; gate, up, down
            "layer_mm_params": 2 * d * h * hd + 2 * d * kv * hd + 3 * d * ff}


def model_config(config: Dict[str, Any], chips: int):
    """Activations bf16, parameters float32, flash attention: the program's
    defaults, stated in the configuration file."""
    from ray_tpu.models.llama import LlamaConfig

    remat = config["remat"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        n_positions=config["max_position_embeddings"],
        d_model=config["hidden_size"], n_layer=shape(config, chips)["n_layer"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]), remat=bool(remat),
        remat_policy=remat or "full")


def logits(params, ids, config: Dict[str, Any]):
    import jax

    from perfbench.harness.reference import (causal_attention, dense, heads,
                                             merge, rms_norm, rope)

    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    n_layer = sum(1 for k in params if k.startswith("h_"))
    x = params["wte"]["embedding"][ids]
    for i in range(n_layer):
        p = params[f"h_{i}"]
        y = rms_norm(x, p["attn_norm"], eps)
        q = rope(heads(dense(y, p["attn"]["wq"]), h), theta)
        k = rope(heads(dense(y, p["attn"]["wk"]), kv), theta)
        v = heads(dense(y, p["attn"]["wv"]), kv)
        b, _, s, d = q.shape
        # query head j reads key/value head j // (h // kv)
        a = causal_attention(q.reshape(b, kv, h // kv, s, d), k, v)
        x = x + dense(merge(a), p["attn"]["wo"])
        y = rms_norm(x, p["mlp_norm"], eps)
        x = x + dense(jax.nn.silu(dense(y, p["mlp"]["gate_proj"]))
                      * dense(y, p["mlp"]["up_proj"]), p["mlp"]["down_proj"])
    x = rms_norm(x, params["norm_f"], eps)
    return (x @ params["lm_head"]["kernel"])[..., : config["vocab_size"]]
