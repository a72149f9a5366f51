"""Kernel correctness: flash attention vs reference, ring attention vs unsharded,
GAE scans vs numpy loops.  Runs on the virtual 8-device CPU mesh (pallas kernels
in interpreter mode off-TPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention
from ray_tpu.ops.attention import (
    NEG_INF,
    flash_attention,
    mha_reference,
    ring_attention,
    ring_attention_sharded,
)
from ray_tpu.ops.gae import discounted_returns, gae_advantages


def _qkv(b=2, h=2, s=256, d=32, seed=0, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k1, (b, h, s, d), dtype)
    k = jax.random.normal(k2, (b, h, s, d), dtype)
    v = jax.random.normal(k3, (b, h, s, d), dtype)
    return q, k, v


def _eqns(jaxpr, kernel=None):
    """Every equation under ``jaxpr``, each with the ``pallas_call`` equation
    whose kernel it lies in (None outside any)."""
    for eqn in jaxpr.eqns:
        yield eqn, kernel
        if eqn.primitive.name == "pallas_call":
            yield from _eqns(eqn.params["jaxpr"], eqn)
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _eqns(sub, kernel)


class TestFlashAttention:
    def test_matches_reference_causal(self):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)

    def test_matches_reference_noncausal(self):
        q, k, v = _qkv(s=128)
        out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
        ref = mha_reference(q, k, v, causal=False)
        np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)

    def test_gradients_match_reference(self):
        q, k, v = _qkv(b=1, h=2, s=128, d=16)

        def f_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, block_q=64, block_k=64) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v) ** 2)

        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=5e-3, rtol=5e-3)

    def test_unaligned_seq_forward(self):
        # round-1 advisor bug: s_k not a multiple of block_k silently
        # double-counted re-read keys (s=200 with default 128 blocks).
        q, k, v = _qkv(s=200)
        out = flash_attention(q, k, v, causal=True)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)

    def test_unaligned_seq_noncausal(self):
        q, k, v = _qkv(s=200)
        out = flash_attention(q, k, v, causal=False)
        ref = mha_reference(q, k, v, causal=False)
        np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)

    def test_short_seq_gradients(self):
        # round-1 advisor bug: backward crashed for any s < default block_k.
        q, k, v = _qkv(b=1, h=2, s=64, d=16)

        def f_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v) ** 2)

        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=5e-3, rtol=5e-3)

    def test_unaligned_seq_gradients(self):
        q, k, v = _qkv(b=1, h=1, s=200, d=16)

        def f_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v) ** 2)

        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=5e-3, rtol=5e-3)

    # id -> (causal, d, dtype, s_q, s_k, q_offset, k_offset).  The backward
    # picks its tile edge from the length (``_block``): 1024 and 2048 run
    # in tiles of 1024 (one on the diagonal; four, one of them bare and one
    # skipped), 640 in 5 x 5 tiles of 128, 1100 in 5 x 5 of 256 with a padded
    # last block.  Unequal tiles or an offset that is no tile multiple take
    # the masked body in place of the diagonal's chunks.
    BWD_CASES = {
        "causal-d64-f32": (True, 64, jnp.float32, 1024, 1024, 0, 0),
        "causal-d128-bf16": (True, 128, jnp.bfloat16, 1024, 1024, 0, 0),
        "causal-d64-bf16-four-tiles": (True, 64, jnp.bfloat16, 2048, 2048, 0, 0),
        "causal-d64-bf16-small-tiles": (True, 64, jnp.bfloat16, 640, 640, 0, 0),
        "causal-d128-f32-not-a-block-multiple": (True, 128, jnp.float32, 1100, 1100, 0, 0),
        "full-d128-f32": (False, 128, jnp.float32, 256, 256, 0, 0),
        "full-d64-bf16-small-tiles": (False, 64, jnp.bfloat16, 640, 640, 0, 0),
        "full-d64-bf16-not-a-block-multiple": (False, 64, jnp.bfloat16, 1100, 1100, 0, 0),
        "causal-shorter-than-a-block": (True, 64, jnp.float32, 200, 200, 0, 0),
        "full-shorter-than-a-block": (False, 128, jnp.bfloat16, 72, 72, 0, 0),
        "causal-sq-ne-sk-q-offset": (True, 64, jnp.float32, 256, 768, 512, 0),
        "causal-sq-ne-sk-both-offsets": (True, 128, jnp.float32, 640, 1100, 560, 100),
        "causal-empty-softmax-rows": (True, 64, jnp.float32, 256, 768, 100, 300),
        # dQ's whole-sequence accumulator summed over several k blocks: four
        # tiles of 1024 at width 128; 5 x 5 unequal tiles (256 x 512), both
        # lengths padded, under an offset; 3 x 5 tiles of 256 with the
        # diagonal in chunks, the padded last k block past every query
        "causal-d128-bf16-four-tiles": (True, 128, jnp.bfloat16, 2048, 2048, 0, 0),
        "causal-sq-ne-sk-many-k-blocks-padded": (True, 64, jnp.bfloat16, 1100, 2200, 1100, 0),
        "causal-sq-ne-sk-offset-diagonal-chunks": (True, 64, jnp.float32, 768, 1100, 256, 0),
    }

    @pytest.mark.parametrize("case", list(BWD_CASES))
    def test_backward_kernel_matches_reference_gradients(self, case):
        causal, d, dtype, s_q, s_k, q_off, k_off = self.BWD_CASES[case]
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        q = jax.random.normal(ks[0], (1, 2, s_q, d), dtype)
        k = jax.random.normal(ks[1], (1, 2, s_k, d), dtype)
        v = jax.random.normal(ks[2], (1, 2, s_k, d), dtype)
        w = jax.random.normal(ks[3], (1, 2, s_q, d), jnp.float32)
        # a query before every key has an empty softmax: the kernel gives it
        # no output and no gradient, where the reference averages the values
        live = (jnp.arange(s_q) + q_off >= k_off) | (not causal)
        w_live = w * live[:, None]

        def loss(fn, w):
            return lambda q, k, v: jnp.sum(
                fn(q, k, v, causal=causal, q_offset=q_off, k_offset=k_off)
                .astype(jnp.float32) * w)

        got = jax.grad(loss(flash_attention, w), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(mha_reference, w_live), argnums=(0, 1, 2))(
            *(x.astype(jnp.float32) for x in (q, k, v)))
        tol = 5e-5 if dtype == jnp.float32 else 4e-2
        for a, b in zip(got, want):
            assert a.dtype == dtype
            np.testing.assert_allclose(a.astype(jnp.float32), b, atol=tol, rtol=tol)
        assert not bool(jnp.any(got[0][:, :, ~live]))

    # id -> (causal, d, dtype, s_q, s_k, q_offset, k_offset, block_q, block_k);
    # None: the rule's own choice (``_block``).  One k tile is the plain
    # softmax, several carry the running state; square tiles that the diagonal
    # meets corner to corner go chunk by chunk, the others (unequal tiles, an
    # offset that is no tile multiple) take the whole-tile mask.
    FWD_CASES = {
        "bf16-d64-rule-one-tile": (True, 64, jnp.bfloat16, 1024, 1024, 0, 0, None, None),
        "f32-d128-rule-four-tiles": (True, 128, jnp.float32, 2048, 2048, 0, 0, None, None),
        "bf16-d128-4x4-tiles": (True, 128, jnp.bfloat16, 512, 512, 0, 0, 128, 128),
        "f32-d64-unequal-tiles": (True, 64, jnp.float32, 512, 512, 0, 0, 256, 128),
        "f32-d64-not-a-tile-multiple": (True, 64, jnp.float32, 600, 600, 0, 0, 256, 256),
        "bf16-d128-full-not-a-tile-multiple": (False, 128, jnp.bfloat16, 600, 600, 0, 0, 256, 256),
        "f32-d64-full-rule-padded": (False, 64, jnp.float32, 1100, 1100, 0, 0, None, None),
        "bf16-d64-shorter-than-a-tile": (True, 64, jnp.bfloat16, 72, 72, 0, 0, None, None),
        "bf16-d128-full-sq-ne-sk": (False, 128, jnp.bfloat16, 256, 1024, 0, 0, 128, 256),
        "f32-d64-q-offset-a-tile-multiple": (True, 64, jnp.float32, 256, 768, 512, 0, 128, 128),
        "f32-d128-q-offset-no-tile-multiple": (True, 128, jnp.float32, 384, 1100, 560, 100, 128, 128),
        "f32-d64-empty-softmax-rows": (True, 64, jnp.float32, 256, 768, 100, 300, 128, 128),
        "bf16-d64-empty-softmax-rows-rule": (True, 64, jnp.bfloat16, 256, 768, 100, 300, None, None),
        "f32-d64-whole-q-tiles-before-every-key": (True, 64, jnp.float32, 384, 384, 0, 256, 128, 128),
    }

    @pytest.mark.parametrize("case", list(FWD_CASES))
    def test_forward_kernel_matches_reference_and_logsumexp(self, case):
        causal, d, dtype, s_q, s_k, q_off, k_off, bq, bk = self.FWD_CASES[case]
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(ks[0], (1, 2, s_q, d), dtype)
        k = jax.random.normal(ks[1], (1, 2, s_k, d), dtype)
        v = jax.random.normal(ks[2], (1, 2, s_k, d), dtype)
        out, lse = attention._flash_forward(
            q, k, v, causal, d ** -0.5, q_off, k_off, bq, bk, True)
        assert out.dtype == dtype and out.shape == q.shape
        assert lse.shape == (2, 1, s_q) and lse.dtype == jnp.float32

        q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
        want = mha_reference(q32, k32, v32, causal=causal, q_offset=q_off,
                             k_offset=k_off)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q32, k32,
                            precision="highest") * d ** -0.5
        # a query before every key has an empty softmax: output 0 and
        # lse NEG_INF, where the reference averages the values
        live = (jnp.arange(s_q) + q_off >= k_off) | (not causal)
        if causal:
            seen = (jnp.arange(s_q)[:, None] + q_off
                    >= jnp.arange(s_k)[None, :] + k_off)
            scores = jnp.where(seen, scores, -jnp.inf)
        want_lse = jax.nn.logsumexp(scores[:, :, live], axis=-1)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(out.astype(jnp.float32)[:, :, live],
                                   want[:, :, live], atol=tol, rtol=tol)
        np.testing.assert_allclose(lse.reshape(1, 2, s_q)[:, :, live], want_lse,
                                   atol=tol, rtol=tol)
        assert not bool(jnp.any(out[:, :, ~live]))
        assert bool(jnp.all(lse.reshape(1, 2, s_q)[:, :, ~live] == NEG_INF))
        if "empty" in case or "before-every-key" in case:
            assert int(jnp.sum(~live)) > 0

    def test_forward_is_one_kernel_over_a_kv_grid_with_bf16_dots(self):
        """The mechanism engages: the forward of the op is one Pallas call
        whose grid has a KV axis, and with bf16 inputs every dot in it takes
        bf16 operands (no q / k / v tile is upcast on its way to the MXU) and
        accumulates in float32."""
        q, k, v = _qkv(b=1, h=2, s=2048, d=128, dtype=jnp.bfloat16)
        jaxpr = jax.make_jaxpr(lambda q, k, v: flash_attention(q, k, v))(q, k, v)
        eqns = list(_eqns(jaxpr.jaxpr))
        calls = [e for e, _ in eqns if e.primitive.name == "pallas_call"]
        dots = [e for e, kernel in eqns
                if kernel is not None and e.primitive.name == "dot_general"]
        assert [c.params["name"] for c in calls] == ["flash_fwd"]
        # (batch, heads, q blocks, k blocks)
        assert tuple(calls[0].params["grid_mapping"].grid) == (1, 2, 2, 2)
        assert len(dots) >= 2
        for eqn in dots:
            assert [x.aval.dtype for x in eqn.invars] == [jnp.bfloat16] * 2
            assert eqn.outvars[0].aval.dtype == jnp.float32
        # and nothing in VMEM scales with the sequence: every block and
        # scratch buffer is a tile
        kernel_avals = [x.aval for x in calls[0].params["jaxpr"].invars]
        assert max(max(a.shape) for a in kernel_avals) == 1024

    def test_gradient_runs_pallas_kernels_and_no_loop(self):
        """The mechanism engages: the backward of the op is Pallas calls, and
        no XLA scan / while is left beside them."""
        q, k, v = _qkv(b=1, h=1, s=256, d=64)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(flash_attention(q, k, v)),
            argnums=(0, 1, 2)))(q, k, v)
        # (the kernels' own loops stay inside them)
        outside = [e for e, kernel in _eqns(jaxpr.jaxpr) if kernel is None]
        kernels = [e.params["name"] for e in outside
                   if e.primitive.name == "pallas_call"]
        others = [e.primitive.name for e in outside]
        assert sorted(kernels) == ["flash_bwd", "flash_fwd"]
        assert not {"scan", "while"} & set(others), others

    def test_backward_is_one_kernel_of_five_dots_and_one_exp_a_tile(self):
        """The mechanism engages: the backward of the op is one Pallas call
        whose bare tile body recomputes P once (one ``exp``) and takes dV, dK
        and dQ from it in five matmuls — bf16 operands, float32 accumulation,
        dQ's as the contraction over dim 0 of dS^T and K — and whose dQ
        accumulator and output block span the sequence."""
        q, k, v = _qkv(b=1, h=2, s=2048, d=128, dtype=jnp.bfloat16)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=False)
                                    .astype(jnp.float32)),
            argnums=(0, 1, 2)))(q, k, v)
        eqns = list(_eqns(jaxpr.jaxpr))
        calls = [e for e, _ in eqns if e.primitive.name == "pallas_call"
                 and e.params["name"].startswith("flash_bwd")]
        assert [c.params["name"] for c in calls] == ["flash_bwd"]
        assert tuple(calls[0].params["grid_mapping"].grid) == (1, 2, 2, 2)
        # no mask, no padding: every tile takes the one bare body
        body = [e for e, kernel in eqns if kernel is calls[0]]
        dots = [e for e in body if e.primitive.name == "dot_general"]
        assert len(dots) == 5
        assert sum(e.primitive.name == "exp" for e in body) == 1
        for eqn in dots:
            assert [x.aval.dtype for x in eqn.invars] == [jnp.bfloat16] * 2
            assert eqn.outvars[0].aval.dtype == jnp.float32
        contracting = [e.params["dimension_numbers"][0] for e in dots]
        assert contracting.count(((0,), (0,))) == 1  # dQ += (dS^T)^T K
        kernel_avals = [x.aval for x in calls[0].params["jaxpr"].invars]
        assert sorted(a.shape for a in kernel_avals
                      if max(a.shape) == 2048) == [(2048, 128)] * 2

    def test_offsets_shift_mask(self):
        # with q_offset = S_k, every key is visible (no masking)
        q, k, v = _qkv(s=64)
        out = flash_attention(q, k, v, causal=True, q_offset=64, block_q=32, block_k=32)
        ref = mha_reference(q, k, v, causal=True, q_offset=64)
        np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)


class TestRingAttention:
    def _mesh(self, sp=4):
        from jax.sharding import Mesh

        devs = np.asarray(jax.devices()[:sp])
        return Mesh(devs, ("sp",))

    def test_matches_unsharded(self):
        q, k, v = _qkv(b=1, h=2, s=256, d=16)
        mesh = self._mesh(4)
        out = ring_attention_sharded(
            q, k, v, mesh=mesh, causal=True, batch_axes=(), head_axis="_none")
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)

    def test_grads_flow(self):
        q, k, v = _qkv(b=1, h=1, s=128, d=8)
        mesh = self._mesh(4)

        def f(q, k, v):
            return jnp.sum(ring_attention_sharded(
                q, k, v, mesh=mesh, causal=True, batch_axes=(),
                head_axis="_none") ** 2)

        def f_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

        # (jitted: op by op the ring's shard_map is a program a device a hop)
        g = jax.jit(jax.grad(f))(q, k, v)
        g_ref = jax.grad(f_ref)(q, k, v)
        np.testing.assert_allclose(g, g_ref, atol=5e-3, rtol=5e-3)


class TestGAE:
    def test_discounted_returns_vs_loop(self):
        T, B = 37, 3
        rng = np.random.default_rng(0)
        r = rng.normal(size=(T, B)).astype(np.float32)
        dones = (rng.random((T, B)) < 0.1).astype(np.float32)
        out = discounted_returns(jnp.asarray(r), jnp.asarray(dones), 0.9)
        expect = np.zeros_like(r)
        running = np.zeros(B, np.float32)
        for t in reversed(range(T)):
            running = r[t] + 0.9 * (1 - dones[t]) * running
            expect[t] = running
        np.testing.assert_allclose(out, expect, atol=1e-5, rtol=1e-5)

    def test_gae_vs_loop(self):
        T, B = 29, 2
        rng = np.random.default_rng(1)
        r = rng.normal(size=(T, B)).astype(np.float32)
        vals = rng.normal(size=(T, B)).astype(np.float32)
        dones = (rng.random((T, B)) < 0.15).astype(np.float32)
        boot = rng.normal(size=(B,)).astype(np.float32)
        gamma, lam = 0.99, 0.95
        adv, targets = gae_advantages(
            jnp.asarray(r), jnp.asarray(vals), jnp.asarray(dones), gamma, lam,
            jnp.asarray(boot))
        nv = np.concatenate([vals[1:], boot[None]], 0)
        deltas = r + gamma * (1 - dones) * nv - vals
        expect = np.zeros_like(r)
        running = np.zeros(B, np.float32)
        for t in reversed(range(T)):
            running = deltas[t] + gamma * lam * (1 - dones[t]) * running
            expect[t] = running
        np.testing.assert_allclose(adv, expect, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(targets, expect + vals, atol=1e-4, rtol=1e-4)
