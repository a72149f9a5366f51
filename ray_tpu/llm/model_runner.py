"""Cache-aware GPT-2 forward for inference: prefill + single-token decode.

The training stack (`models/gpt2.py`) computes full-sequence attention under
one jit — right for pretraining, wasteful for serving, where each decode
step needs exactly one new token's Q against the sequence's cached K/V.
This runner implements the SAME math (fused QKV, pre-LN blocks, tanh-GELU
MLP, tied layout, 1/sqrt(D) attention) against a `PagedKVCache`, in float32
numpy so the engine runs anywhere tier-1 runs (`JAX_PLATFORMS=cpu`, or no
accelerator at all).  `from_flax` initializes the weights through the actual
flax module so the serving path exercises `models/` end to end; parity with
`GPT2LMModel.apply` is asserted in tests/test_llm.py.

The TPU upgrade path keeps this module's interface: a Pallas paged-attention
kernel replaces `_attend`, and the cache's jax backend keeps pages in HBM.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

from ray_tpu.llm.kv_cache import PagedKVCache


def _layernorm(x: np.ndarray, scale: np.ndarray, bias: np.ndarray,
               eps: float = 1e-6) -> np.ndarray:
    mu = x.mean(axis=-1, keepdims=True)
    var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
    return (x - mu) / np.sqrt(var + eps) * scale + bias


def _gelu(x: np.ndarray) -> np.ndarray:
    # tanh approximation — jax.nn.gelu's default (approximate=True)
    return 0.5 * x * (1.0 + np.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _softmax(x: np.ndarray, axis: int = -1) -> np.ndarray:
    x = x - x.max(axis=axis, keepdims=True)
    e = np.exp(x)
    return e / e.sum(axis=axis, keepdims=True)


class _LayerParams:
    __slots__ = ("ln1_s", "ln1_b", "wqkv", "bqkv", "wout", "bout",
                 "ln2_s", "ln2_b", "w1", "b1", "w2", "b2")


class GPT2Runner:
    """Float32 numpy weights + cache-aware forward for one GPT-2 stack."""

    def __init__(self, config, params: Dict):
        """``params``: the flax param tree of `models/gpt2.GPT2LMModel`
        (the ``{"params": ...}`` wrapper optional), any array type —
        converted to float32 numpy here."""
        self.config = config
        if "params" in params and "wte" not in params:
            params = params["params"]

        def a(x):
            return np.asarray(x, np.float32)

        self.wte = a(params["wte"]["embedding"])          # [V, E]
        self.wpe = a(params["wpe"]["embedding"])          # [P, E]
        self.lnf_s = a(params["ln_f"]["scale"])
        self.lnf_b = a(params["ln_f"]["bias"])
        # [E, V]: the flax tables are padded to a lane multiple
        # (models/gpt2.padded_vocab); the pad columns are not tokens
        self.lm_head = a(params["lm_head"]["kernel"])[:, :config.vocab_size]
        self.layers: List[_LayerParams] = []
        for i in range(config.n_layer):
            blk = params[f"h_{i}"]
            lp = _LayerParams()
            lp.ln1_s = a(blk["ln_1"]["scale"])
            lp.ln1_b = a(blk["ln_1"]["bias"])
            lp.wqkv = a(blk["attn"]["qkv_proj"]["kernel"])
            lp.bqkv = a(blk["attn"]["qkv_proj"]["bias"])
            lp.wout = a(blk["attn"]["out_proj"]["kernel"])
            lp.bout = a(blk["attn"]["out_proj"]["bias"])
            lp.ln2_s = a(blk["ln_2"]["scale"])
            lp.ln2_b = a(blk["ln_2"]["bias"])
            lp.w1 = a(blk["mlp"]["fc_in"]["kernel"])
            lp.b1 = a(blk["mlp"]["fc_in"]["bias"])
            lp.w2 = a(blk["mlp"]["fc_out"]["kernel"])
            lp.b2 = a(blk["mlp"]["fc_out"]["bias"])
            self.layers.append(lp)
        self.n_head = config.n_head
        self.head_dim = config.n_embd // config.n_head

    # ------------------------------------------------------ constructors
    @classmethod
    def from_flax(cls, config, seed: int = 0) -> "GPT2Runner":
        """Initialize weights through the real `models/` flax module (the
        canonical path: serving uses the training stack's parameters)."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.gpt2 import GPT2LMModel

        model = GPT2LMModel(config)
        variables = model.init(jax.random.PRNGKey(seed),
                               jnp.zeros((1, 2), jnp.int32),
                               deterministic=True)
        params = jax.tree_util.tree_map(np.asarray, variables["params"])
        return cls(config, params)

    @classmethod
    def init_random(cls, config, seed: int = 0) -> "GPT2Runner":
        """Seeded numpy initialization with the flax tree layout — instant,
        jax-free; the default for tests/benchmarks where only determinism
        (not trained weights) matters."""
        rng = np.random.default_rng(seed)
        E, V, P = config.n_embd, config.vocab_size, config.n_positions

        def dense(i, o):
            return {"kernel": rng.normal(0, 0.02, (i, o)).astype(np.float32),
                    "bias": np.zeros(o, np.float32)}

        def ln():
            return {"scale": np.ones(E, np.float32),
                    "bias": np.zeros(E, np.float32)}

        params = {
            "wte": {"embedding":
                    rng.normal(0, 0.02, (V, E)).astype(np.float32)},
            "wpe": {"embedding":
                    rng.normal(0, 0.02, (P, E)).astype(np.float32)},
            "ln_f": ln(),
            "lm_head": {"kernel":
                        rng.normal(0, 0.02, (E, V)).astype(np.float32)},
        }
        for i in range(config.n_layer):
            params[f"h_{i}"] = {
                "ln_1": ln(),
                "attn": {"qkv_proj": dense(E, 3 * E),
                         "out_proj": dense(E, E)},
                "ln_2": ln(),
                "mlp": {"fc_in": dense(E, 4 * E),
                        "fc_out": dense(4 * E, E)},
            }
        return cls(config, params)

    # ---------------------------------------------------------- forward
    def _attend(self, q: np.ndarray, K: np.ndarray, V: np.ndarray,
                q_offset: int) -> np.ndarray:
        """q: [T, H, D]; K/V: [S, H, D] (cached prefix incl. this chunk).
        Causal: query at absolute position q_offset+t sees keys <= it."""
        T = q.shape[0]
        S = K.shape[0]
        scale = self.head_dim ** -0.5
        # [H, T, S]
        logits = np.einsum("thd,shd->hts", q, K) * scale
        qi = np.arange(T)[:, None] + q_offset
        ki = np.arange(S)[None, :]
        logits = np.where(qi >= ki, logits, -1e30)
        w = _softmax(logits, axis=-1)
        return np.einsum("hts,shd->thd", w, V)

    def _block(self, lp: _LayerParams, x: np.ndarray, layer: int,
               writes: Sequence[Tuple[str, int]], cache: PagedKVCache,
               lengths: Sequence[int]) -> np.ndarray:
        """One transformer block over a [N, E] batch of token states.
        ``writes[i] = (seq_id, position)`` assigns row i of the batch;
        consecutive rows of one seq (prefill) are grouped by the caller via
        equal seq_id and increasing positions.  ``lengths[i]`` is the total
        attention span for row i (position + 1)."""
        H, D = self.n_head, self.head_dim
        h = _layernorm(x, lp.ln1_s, lp.ln1_b)
        qkv = h @ lp.wqkv + lp.bqkv
        q, k, v = np.split(qkv, 3, axis=-1)
        N = x.shape[0]
        q = q.reshape(N, H, D)
        k = k.reshape(N, H, D)
        v = v.reshape(N, H, D)
        att = np.empty_like(q)
        i = 0
        while i < N:
            sid, start = writes[i]
            j = i + 1
            while j < N and writes[j][0] == sid:
                j += 1
            cache.write(sid, layer, start, k[i:j], v[i:j])
            K, Vc = cache.gather_kv(sid, layer, lengths[j - 1])
            att[i:j] = self._attend(q[i:j], K, Vc, start)
            i = j
        x = x + att.reshape(N, H * D) @ lp.wout + lp.bout
        h2 = _layernorm(x, lp.ln2_s, lp.ln2_b)
        x = x + _gelu(h2 @ lp.w1 + lp.b1) @ lp.w2 + lp.b2
        return x

    def prefill(self, seq_id: str, tokens: Sequence[int], start: int,
                cache: PagedKVCache, return_all: bool = False) -> np.ndarray:
        """Process ``tokens`` at positions start..start+T-1, writing K/V into
        the cache (pages must be reserved).  Returns the last position's
        logits [V] (or all [T, V] with ``return_all``)."""
        toks = np.asarray(tokens, np.int64)
        T = len(toks)
        pos = np.arange(start, start + T)
        x = self.wte[toks] + self.wpe[pos]
        writes = [(seq_id, start + t) for t in range(T)]
        lengths = [start + t + 1 for t in range(T)]
        # gather() reads committed length; this chunk's own K/V must be
        # visible to its queries, so commit the new length up front — the
        # pages are already reserved and write() precedes every gather.
        cache.commit(seq_id, start + T)
        for layer, lp in enumerate(self.layers):
            x = self._block(lp, x, layer, writes, cache, lengths)
        x = _layernorm(x, self.lnf_s, self.lnf_b)
        logits = x @ self.lm_head
        return logits if return_all else logits[-1]

    # ------------------------------------------------- speculative hooks
    def propose_tokens(self, items: Sequence[Tuple[str, int, int]],
                       cache: PagedKVCache,
                       max_draft: int = 0) -> List[List[int]]:
        """Speculative-decoding hook: propose up to ``max_draft`` draft
        tokens per sequence (``items`` as in :meth:`decode`).  The base
        runner has no draft model and proposes nothing; a future draft
        runner overrides this without any scheduler changes."""
        return [[] for _ in items]

    def verify_tokens(self, items: Sequence[Tuple[str, int, int]],
                      drafts: Sequence[List[int]],
                      cache: PagedKVCache) -> np.ndarray:
        """Verify drafted tokens against the target model.  The default
        single-token implementation ignores ``drafts`` and runs one plain
        decode step, so the engine's decode path can route through
        propose/verify unconditionally."""
        return self.decode(items, cache)

    def decode(self, items: Sequence[Tuple[str, int, int]],
               cache: PagedKVCache) -> np.ndarray:
        """One continuous-batching decode step.  ``items`` is a list of
        (seq_id, token_id, position); every sequence advances one token.
        Returns logits [B, V].  The linear layers run batched across the
        whole step; attention gathers each sequence's own pages."""
        toks = np.asarray([t for _, t, _ in items], np.int64)
        pos = np.asarray([p for _, _, p in items], np.int64)
        x = self.wte[toks] + self.wpe[pos]
        writes = [(sid, p) for sid, _, p in items]
        lengths = [p + 1 for _, _, p in items]
        for sid, _, p in items:
            cache.commit(sid, p + 1)
        for layer, lp in enumerate(self.layers):
            x = self._block(lp, x, layer, writes, cache, lengths)
        x = _layernorm(x, self.lnf_s, self.lnf_b)
        return x @ self.lm_head
