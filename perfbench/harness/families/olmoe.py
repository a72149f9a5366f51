"""OLMoE (Muennighoff et al. 2024; ``allenai/OLMoE-1B-7B-0125-Instruct``): the
Llama block with an RMSNorm over the query and key projections and, in every
layer, 64 routed SwiGLU experts of which each token takes 8, none dropped.
The program's side is ``ray_tpu/models/llama.py`` with ``models/moe.py``'s
``RoutedSwiGLU`` as ``moe``.  Per layer, with ``n1 = RMSNorm(x)``:

    h = x + Wo Attn(RoPE(heads(RMSNorm_q(Wq n1))), RoPE(heads(RMSNorm_k(Wk n1))),
                    heads(Wv n1))
    y = h + sum_{e in top8(p)} p_e W_down^e( silu(W_gate^e n2) * W_up^e n2 )
    n2 = RMSNorm(h),  p = softmax(W_r n2) over all 64 experts, in float32

The q and k norms have a learned scale over the whole projection and come
before the split into heads and before RoPE (rotate-half).  ``p`` is not
renormalised over the chosen eight (``norm_topk_prob`` false); no shared
expert, no bias, no capacity.  Then the final RMSNorm and an untied
``lm_head``.  Training adds to the cross entropy 0.01 x the load-balancing
loss ``E sum_e f_e P_e`` (``f_e``: assignments to expert ``e`` per token,
``P_e``: its mean probability; ``top_k`` at balance) and 0.001 x the router
z-loss, the mean of ``logsumexp(router logits) ** 2``, each averaged over the
layers (``assumed`` in the configuration file); ``aux_losses`` computes them.

Plain on purpose: no sort and no grouped matmul — every expert is applied to
every token and masked by the top-k set.  Where the program departs from the
published code (``program_departures``: the router's matmul in float32, the
initialisers) this follows the program.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

from perfbench.harness.families import llama


def shape(config: Dict[str, Any], chips: int) -> Dict[str, int]:
    d, f = config["hidden_size"], config["intermediate_size"]
    hd = d // config["num_attention_heads"]
    return dict(
        llama.shape(config, chips),
        # wq, wo; wk, wv; the router; gate, up, down of the experts a token
        # is routed to: the parameters active per token
        layer_mm_params=(2 * d * config["num_attention_heads"] * hd
                         + 2 * d * config["num_key_value_heads"] * hd
                         + d * config["num_experts"]
                         + config["num_experts_per_tok"] * 3 * d * f))


def model_config(config: Dict[str, Any], chips: int):
    """The Llama block's configuration with every layer routed and the q/k
    norm on.  Activations bf16, parameters and the router float32, flash
    attention, the Pallas grouped matmul: the program's defaults, stated in
    the configuration file."""
    assumed = config["assumed"]
    return dataclasses.replace(
        llama.model_config(config, chips),
        qk_norm=True, moe_every=1, n_experts=config["num_experts"],
        moe_top_k=config["num_experts_per_tok"],
        d_expert=config["intermediate_size"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        router_aux_weight=assumed["load_balancing_loss"]["weight"],
        router_z_weight=assumed["router_z_loss"]["weight"])


def _forward(params, ids, config: Dict[str, Any], router_dtype=None):
    """(logits, each layer's router logits).  ``router_dtype``: the dtype the
    router's softmax is computed in, float32 unless a wrong-model control
    asks for another."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness.reference import (causal_attention, dense, heads,
                                             merge, rms_norm, rope)

    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    n_experts, k = config["num_experts"], config["num_experts_per_tok"]
    n_layer = sum(1 for name in params if name.startswith("h_"))
    x = params["wte"]["embedding"][ids]
    routers = []
    for i in range(n_layer):
        p = params[f"h_{i}"]
        a, y = p["attn"], rms_norm(x, p["attn_norm"], eps)
        q = rope(heads(rms_norm(dense(y, a["wq"]), a["q_norm"], eps), h),
                 theta)
        key = rope(heads(rms_norm(dense(y, a["wk"]), a["k_norm"], eps), kv),
                   theta)
        v = heads(dense(y, a["wv"]), kv)
        b, _, s, d = q.shape
        att = causal_attention(q.reshape(b, kv, h // kv, s, d), key, v)
        x = x + dense(merge(att), a["wo"])

        m, y = p["moe"], rms_norm(x, p["mlp_norm"], eps)
        router = dense(y, m["router"])                      # (B, S, E)
        routers.append(router)
        prob = jax.nn.softmax(router.astype(router_dtype or jnp.float32),
                              axis=-1).astype(jnp.float32)
        _, chosen = jax.lax.top_k(prob, k)
        weight = prob * jnp.sum(jax.nn.one_hot(chosen, n_experts), axis=-2)
        if config["norm_topk_prob"]:
            weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
        # every expert on every token: (B, S, E, F), then masked
        hidden = jax.nn.silu(jnp.einsum("bsd,edf->bsef", y, m["gate_proj"])) \
            * jnp.einsum("bsd,edf->bsef", y, m["up_proj"])
        x = x + jnp.einsum("bsef,efd,bse->bsd", hidden, m["down_proj"],
                           weight)
    x = rms_norm(x, params["norm_f"], eps)
    return (x @ params["lm_head"]["kernel"])[..., : config["vocab_size"]], \
        routers


def logits(params, ids, config: Dict[str, Any]):
    return _forward(params, ids, config)[0]


def aux_losses(params, ids, config: Dict[str, Any]) -> Dict[str, Any]:
    """The training objective's auxiliary terms, unweighted, and the load
    statistic the program reports beside them: ``load_balance`` and ``z``
    averaged over the layers, ``max_load`` (the busiest expert's assignments
    over the mean) of the worst layer."""
    import jax
    import jax.numpy as jnp

    n_experts, k = config["num_experts"], config["num_experts_per_tok"]
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        routers = _forward(params, ids, config)[1]
    balance, z, load = [], [], []
    for router in routers:
        router = router.reshape(-1, n_experts)
        prob = jax.nn.softmax(router, axis=-1)
        _, chosen = jax.lax.top_k(prob, k)
        per_token = jnp.sum(jax.nn.one_hot(chosen, n_experts),
                            axis=(0, 1)) / router.shape[0]      # f_e
        balance.append(n_experts * jnp.sum(per_token * prob.mean(0)))
        z.append(jnp.mean(jax.nn.logsumexp(router, axis=-1) ** 2))
        load.append(jnp.max(per_token) * n_experts / k)
    return {"load_balance": sum(balance) / len(balance),
            "z": sum(z) / len(z), "max_load": jnp.max(jnp.stack(load))}
