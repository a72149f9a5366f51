"""``ops.attention.attention``, the one place that decides which attention
runs: every combination of implementation, mask, shared key part and value
width either equals ``mha_reference`` — forward and every gradient, at a
toy shape that crosses a tile edge, the flash kernels interpreted — or is
refused in the entry's own words or the implementation's.  What is expected
is written out here, apart from the table the entry reads."""

import functools
import itertools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_tpu.ops.attention import (attention, block_diffusion_mask,
                                   mha_reference)

SEQ = 264       # three 128-wide tiles, the last one part padding
BLOCK, WINDOW = 4, 100
IMPLS = ("reference", "flash", "ring", "flash over sp")
MASKS = ("causal", "window", "block", "none", "window alone")


def _refused(impl, mask, shared, narrow):
    """(error, what its text names) of a combination nothing takes."""
    ring = impl in ("ring", "flash over sp")
    if mask == "window alone":
        return (NotImplementedError if ring else ValueError), "window"
    if ring and mask == "window":
        return NotImplementedError, "'ring'.* window"
    if ring and mask == "block":
        return NotImplementedError, "'ring'.* block mask"
    if ring and shared:
        return NotImplementedError, "'ring'.* key part .*latent attention"
    if (ring or (impl == "flash" and mask == "block")) and (shared or narrow):
        return NotImplementedError, "one width"
    return None


def _asked(mask):
    return dict(causal=mask in ("causal", "window", "block"),
                window=WINDOW * mask.startswith("window"),
                diffusion_block=BLOCK * (mask == "block"))


def _both(f, operands, g):
    """``f``'s output and every operand's gradient (jitted: the ring's
    gradient takes 12 s op by op)."""
    argnums = tuple(range(len(operands)))
    return jax.jit(lambda *a: (f(*a), jax.grad(
        lambda *a: jnp.sum(f(*a) * g), argnums)(*a)))(*operands)


@functools.lru_cache(maxsize=None)
def _case(mask, shared, narrow):
    """The operands of a combination, the cotangent and the plain reference:
    shared by the implementations."""
    seq = 2 * SEQ if mask == "block" else SEQ     # (two copies of SEQ)
    d, d_shared, d_v = 16, 8 * shared, 8 if narrow else 16
    keys = jax.random.split(jax.random.PRNGKey(3), 5)

    def normal(key, heads, width):
        return jax.random.normal(key, (1, heads, seq, width), jnp.float32)

    operands = [normal(keys[0], 2, d), normal(keys[1], 2, d - d_shared),
                normal(keys[2], 2, d_v)]
    if shared:
        operands.append(normal(keys[3], 1, d_shared))
    g = normal(keys[4], 2, d_v)

    def plain(q, k, v, k_shared=None):
        if mask == "block":
            return mha_reference(q, k, v, k_shared=k_shared,
                                 mask=block_diffusion_mask(SEQ, BLOCK))
        return mha_reference(q, k, v, k_shared=k_shared,
                             causal=_asked(mask)["causal"],
                             window=_asked(mask)["window"])

    return operands, g, plain


@functools.lru_cache(maxsize=None)
def _want(mask, shared, narrow):
    operands, g, plain = _case(mask, shared, narrow)
    return _both(plain, operands, g)


@pytest.mark.parametrize("impl,mask,shared,narrow", list(itertools.product(
    IMPLS, MASKS, ("shared", "whole"), ("narrow", "equal"))))
def test_every_combination_is_taken_or_refused(impl, mask, shared, narrow):
    shared, narrow = shared == "shared", narrow == "narrow"
    ring = impl in ("ring", "flash over sp")
    operands, g, plain = _case(mask, shared, narrow)

    def entry(q, k, v, k_shared=None):
        # (B, S, H * Dv), whatever the operands' ranks: here as the heads
        out = attention(q, k, v, k_shared=k_shared, **_asked(mask),
                        impl="flash" if impl == "flash over sp" else impl)
        assert out.shape == (1, q.shape[2], 2 * v.shape[-1])
        return out.reshape(1, q.shape[2], 2, -1).transpose(0, 2, 1, 3)

    # "flash" where the ambient mesh shards the sequence is the ring
    mesh = Mesh(np.asarray(jax.devices()[:2 if ring else 1]), ("sp",))
    with jax.set_mesh(mesh):
        refused = _refused(impl, mask, shared, narrow)
        if refused:
            with pytest.raises(refused[0], match=refused[1]):
                entry(*operands)
            return
        if impl == "flash over sp":     # the choice; "ring" has the numbers
            traced = str(jax.make_jaxpr(entry)(*operands))
            assert "ppermute" in traced and "pallas_call" not in traced
            return
        if impl == "reference":     # the very program, so its gradient too
            def there_and_back(*operands):
                b, h, s, d = (out := plain(*operands)).shape
                return out.transpose(0, 2, 1, 3).reshape(b, s, h * d).reshape(
                    b, s, h, d).transpose(0, 2, 1, 3)
            assert str(jax.make_jaxpr(entry)(*operands)) \
                == str(jax.make_jaxpr(there_and_back)(*operands))
            return
        out, grads = _both(entry, operands, g)
    want, want_grads = _want(mask, shared, narrow)
    assert out.shape == operands[2].shape
    np.testing.assert_allclose(out, want, atol=2e-5)
    for name, a, b in zip(("q", "k", "v", "k_shared"), grads, want_grads):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-4, err_msg="d" + name)
