"""The flash kernels' two operand ranks (``ops/attention.py::_Tiles``): a
rank-3 operand ``(B, S, H * D)``, as a projection wrote it, is read — and the
output and the gradients are written — where it lies, a head (width in 128s)
or a pair of 64-wide heads to a 128-lane column block; a rank-4 operand is
``(B, H, S, D)`` as before.  Every case here runs the call on rank-3 operands
against the same call on the same values head-major (Pallas interpreter):
output and every gradient, and whether a transpose stands between the
operands and the kernels (where the width allows none should).  Then the
models: ``GPT2`` and a Llama block against ``attention_impl="reference"``,
their parameter trees as the parent's.  And grouped-query calls, k and v with
the heads their projections gave them (``_Tiles.rep``), against
``mha_reference`` on operands repeated to the query heads."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_tpu.ops import attention as attention_ops
from ray_tpu.ops.attention import (HeadColumns, block_diffusion_mask,
                                   flash_attention, mha_reference)

SEQ = 256


def _tokens(x):
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _heads(x, h):
    b, s, c = x.shape
    return x.reshape(b, s, h, c // h).transpose(0, 2, 1, 3)


# name -> (heads, head width, values' width, length, the call's keywords,
#          which of q, k, v are rank 3, whether the kernels take them as
#          they lie, dtype)
CASES = {
    "causal-d128": (2, 128, 128, SEQ, {}, "qkv", True, jnp.float32),
    "causal-d128-bf16": (2, 128, 128, SEQ, {}, "qkv", True, jnp.bfloat16),
    "causal-d64-pair": (4, 64, 64, SEQ, {}, "qkv", True, jnp.float32),
    "causal-d64-pair-bf16": (4, 64, 64, SEQ, {}, "qkv", True, jnp.bfloat16),
    "causal-d64-many-tiles": (2, 64, 64, 384, dict(block_q=128, block_k=128),
                              "qkv", True, jnp.float32),
    "window-d128": (2, 128, 128, 384, dict(window=100, block_q=128,
                                           block_k=128), "qkv", True,
                    jnp.float32),
    "window-d64-pair": (2, 64, 64, 384, dict(window=100), "qkv", True,
                        jnp.float32),
    "block-mask-d128": (2, 128, 128, 2 * SEQ,
                        dict(causal=False, diffusion_block=4), "qkv", True,
                        jnp.float32),
    # the pair form is not the block mask's: head-major, as before
    "block-mask-d64-falls-back": (2, 64, 64, 2 * SEQ,
                                  dict(causal=False, diffusion_block=4),
                                  "qkv", False, jnp.float32),
    "padded-d128": (2, 128, 128, 264, {}, "qkv", True, jnp.float32),
    "padded-d64-pair": (2, 64, 64, 264, {}, "qkv", True, jnp.float32),
    "values-narrower-d128-v64": (2, 128, 64, SEQ, {}, "qkv", True,
                                 jnp.float32),
    # what a rotated layer sends: q and k as heads, v from its projection
    "mixed-ranks-d128": (2, 128, 128, SEQ, {}, "v", True, jnp.float32),
    "mixed-ranks-d64-pair": (4, 64, 64, SEQ, {}, "v", True, jnp.float32),
    "odd-heads-d64-fall-back": (3, 64, 64, SEQ, {}, "qkv", False,
                                jnp.float32),
    "width-32-falls-back": (2, 32, 32, SEQ, {}, "qkv", False, jnp.float32),
}


def _operands(h, d, d_v, s, dtype, b=2):
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    return [jax.random.normal(key, (b, h, s, w), dtype)
            for key, w in zip(keys, (d, d, d_v, d_v))]


def _four_d_transposes(f, *operands):
    """The transposes of rank-4 arrays in ``f``'s program outside the
    kernels: what turning an operand head-major, or the result back, is."""
    jaxpr = jax.make_jaxpr(f)(*operands).jaxpr

    def count(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                continue
            if eqn.primitive.name == "transpose":
                n += eqn.invars[0].aval.ndim == 4
            n += sum(count(sub) for sub in jax.core.jaxprs_in_params(
                eqn.params))
        return n
    return count(jaxpr)


def _both(f, operands, g):
    out, vjp = jax.vjp(f, *operands)
    return out, vjp(g)


def _close(got, want, dtype, exact, what):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    if exact:
        np.testing.assert_array_equal(got, want, err_msg=what)
    elif dtype == jnp.bfloat16:     # the last place of a bf16 value
        np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=2e-2,
                                   err_msg=what)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-5,
                                   err_msg=what)


@pytest.mark.parametrize("case", list(CASES))
def test_rank_3_operands_against_the_rank_4_call(case):
    h, d, d_v, s, kw, rank3, engaged, dtype = CASES[case]
    q, k, v, g = _operands(h, d, d_v, s, dtype)

    def head_major(q, k, v):
        return flash_attention(q, k, v, **kw)

    def as_they_lie(q, k, v):
        q, k, v = (_tokens(x) if n in rank3 else x
                   for n, x in zip("qkv", (q, k, v)))
        return flash_attention(q, k, v, head_dim=d, tokens_out=True, **kw)

    want, want_grads = _both(head_major, (q, k, v), g)
    g = _tokens(g)
    got, got_grads = _both(as_they_lie, (q, k, v), g)
    assert got.shape == (2, s, h * d_v)
    # the forward runs the very body on the very tiles: the same bits, a
    # pair's two heads included; the backward's ``delta`` is summed in
    # another order (a matmul by ones where the output is rank 3)
    _close(_heads(got, h), want, dtype, True, "out")
    for name, a, b in zip("qkv", got_grads, want_grads):
        _close(a, b, dtype, False, "d" + name)

    # what stands between the operands and the kernels: the test's own
    # ``_tokens`` of each rank-3 operand (and its gradient's way back), and
    # nothing more where the kernels take the operands as they lie
    def program(q, k, v):
        return _both(as_they_lie, (q, k, v), g)
    own = 2 * len(rank3)
    turned = _four_d_transposes(program, q, k, v) - own
    assert (turned == 0) == engaged, turned


def test_columns_of_one_array_are_read_where_they_lie():
    """GPT-2's form: q, k and v are the three thirds of one projection's
    output, two 64-wide heads to a column block; the array's gradient is the
    three gradients side by side."""
    b, h, d = 2, 4, 64
    qkv = jax.random.normal(jax.random.PRNGKey(2), (b, SEQ, 3 * h * d))
    g = jax.random.normal(jax.random.PRNGKey(3), (b, SEQ, h * d))

    def thirds(qkv):
        return flash_attention(
            *(HeadColumns(qkv, h, d, first=i * h * d) for i in range(3)),
            tokens_out=True)

    def split(qkv):
        q, k, v = (_heads(x, h) for x in jnp.split(qkv, 3, axis=-1))
        return _tokens(flash_attention(q, k, v))

    (got, (got_grad,)), (want, (want_grad,)) = (
        _both(f, (qkv,), g) for f in (thirds, split))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(got_grad, want_grad, rtol=1e-5, atol=2e-5)
    assert _four_d_transposes(lambda x: _both(thirds, (x,), g), qkv) == 0


def test_two_widths_and_a_shared_key_part_from_one_projection():
    """Latent attention's form: a head's key part and values are the two
    halves of its 256 columns of one projection's output, the queries are
    192-wide heads (no lane tile: rank 4), the last 64 dimensions of every
    key one shared vector a position."""
    b, h, dn, dr, dv = 1, 2, 128, 64, 128
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(keys[0], (b, h, SEQ, dn + dr))
    kv = jax.random.normal(keys[1], (b, SEQ, h * (dn + dv)))
    kr = jax.random.normal(keys[2], (b, 1, SEQ, dr))
    g = jax.random.normal(keys[3], (b, SEQ, h * dv))

    def as_they_lie(q, kv, kr):
        return flash_attention(
            q, HeadColumns(kv, h, dn, first=0, stride=dn + dv),
            HeadColumns(kv, h, dv, first=dn, stride=dn + dv), k_shared=kr,
            tokens_out=True)

    def head_major(q, kv, kr):
        kv = _heads(kv, h)
        return _tokens(flash_attention(q, kv[..., :dn], kv[..., dn:],
                                       k_shared=kr))

    (got, got_grads), (want, want_grads) = (
        _both(f, (q, kv, kr), g) for f in (as_they_lie, head_major))
    np.testing.assert_array_equal(got, want)
    for name, a, b in zip(("q", "kv", "kr"), got_grads, want_grads):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=5e-5, err_msg=name)
    assert _four_d_transposes(
        lambda *a: _both(as_they_lie, a, g), q, kv, kr) == 0


def test_a_head_columns_view_must_lie_inside_its_array():
    x = jnp.zeros((1, 128, 256))
    with pytest.raises(ValueError, match="heads"):
        flash_attention(*(HeadColumns(x, 4, 64, first=64),) * 3)
    with pytest.raises(ValueError, match="head_dim"):
        flash_attention(x, x, x)


@pytest.mark.parametrize("width", [64, 128])
def test_under_a_tp_mesh_the_columns_are_cut_by_whole_heads(width):
    """dp=2 x tp=2 on four CPU devices: each device's call sees its half of
    the heads — at width 64 one pair — as columns of its own arrays, the
    thirds of one projection's output taken apart first (a device's share of
    those columns is no whole heads)."""
    b, h = 2, 4
    qkv = jax.random.normal(jax.random.PRNGKey(2), (b, SEQ, 3 * h * width))
    g = jax.random.normal(jax.random.PRNGKey(3), (b, SEQ, h * width))

    def thirds(qkv):
        return flash_attention(
            *(HeadColumns(qkv, h, width, first=i * h * width)
              for i in range(3)), tokens_out=True)

    want, (want_grad,) = _both(thirds, (qkv,), g)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    with jax.set_mesh(mesh):
        got, (got_grad,) = jax.jit(lambda x: _both(thirds, (x,), g))(qkv)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_grad, want_grad, rtol=1e-5, atol=2e-5)


# ------------------------------------------------- grouped-query attention
# name -> (query heads, key/value heads, head width, length, the call's
#          keywords, the reference's, which of q, k, v are rank 3, how the
#          group's dK and dV are summed: "vmem", "beside" the kernel, or
#          "repeated" — k and v copied to the query heads first)
_TILES = dict(block_q=128, block_k=128)
_BLOCK_MASK = (dict(causal=False, diffusion_block=4),
               dict(causal=False, mask=block_diffusion_mask(SEQ, 4)))
GROUPED = {
    # what a rotated layer sends: q and k as heads, v from its projection
    "causal-rep4": (4, 1, 128, 384, _TILES, {}, "v", "vmem"),
    "causal-rep8": (8, 1, 128, 384, _TILES, {}, "v", "vmem"),
    "causal-rep4-padded": (8, 2, 128, 264, {}, {}, "v", "vmem"),
    # NoPE: every operand as its projection wrote it
    "causal-rep4-rank-3": (8, 2, 128, 384, _TILES, {}, "qkv", "vmem"),
    "causal-rep8-rank-4": (8, 1, 128, SEQ, {}, {}, "", "vmem"),
    "window-rep4": (8, 2, 128, 384, dict(window=100, **_TILES),
                    dict(window=100), "v", "vmem"),
    "window-rep8": (8, 1, 128, 384, dict(window=100, **_TILES),
                    dict(window=100), "v", "vmem"),
    "window-rep4-rank-3": (4, 1, 128, 384, dict(window=100),
                           dict(window=100), "qkv", "vmem"),
    "block-mask-rep4": (8, 2, 128, 2 * SEQ, *_BLOCK_MASK, "v", "vmem"),
    "block-mask-rep8": (8, 1, 128, 2 * SEQ, *_BLOCK_MASK, "v", "vmem"),
    "block-mask-rep8-rank-3": (8, 1, 128, 2 * SEQ, *_BLOCK_MASK, "qkv",
                               "vmem"),
    # the group's dQ past VMEM: a gradient a query head, summed outside
    "causal-rep4-beside": (8, 2, 128, 384, _TILES, {}, "v", "beside"),
    "block-mask-rep4-beside": (4, 1, 128, 2 * SEQ, *_BLOCK_MASK, "qkv",
                               "beside"),
    # seven query heads a key/value head (SmallThinker: 28 over 4): the
    # whole-row layer as projected (NoPE), a window layer's q and k as heads
    # under a band that spans several tiles, and both where the group's dQ
    # does not fit VMEM
    "causal-rep7-rank-3": (7, 1, 128, 384, _TILES, {}, "qkv", "vmem"),
    "window-rep7": (14, 2, 128, 384, dict(window=200, **_TILES),
                    dict(window=200), "v", "vmem"),
    "causal-rep7-beside": (14, 2, 128, 384, _TILES, {}, "qkv", "beside"),
    "window-rep7-beside": (7, 1, 128, 384, dict(window=200, **_TILES),
                           dict(window=200), "v", "beside"),
    # any width as (B, H, S, D); the pair form of 64-wide heads has no
    # grouped form, and the copies of before stand in
    "causal-rep2-d32": (4, 2, 32, SEQ, {}, {}, "v", "vmem"),
    "causal-rep2-d64-pair": (4, 2, 64, SEQ, {}, {}, "v", "repeated"),
}


def _kernel_operands(f, *operands):
    """kernel name -> the shapes of its operands and results, in ``f``'s
    program."""
    shapes = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                shapes[eqn.params["name"]] = [
                    v.aval.shape for v in (*eqn.invars, *eqn.outvars)]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(f)(*operands).jaxpr)
    return shapes


@pytest.mark.parametrize("case", list(GROUPED))
def test_grouped_operands_against_the_reference_on_repeated_ones(
        case, monkeypatch):
    h, n_kv, d, s, kw, ref_kw, rank3, summed = GROUPED[case]
    if summed == "beside":
        monkeypatch.setattr(attention_ops, "_VMEM_BYTES", 1 << 20)
    b, rep = 2, h // n_kv
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    q, k, v, g = (jax.random.normal(key, (b, heads, s, d))
                  for key, heads in zip(keys, (h, n_kv, n_kv, h)))
    g = _tokens(g)

    def grouped(q, k, v):
        q, k, v = (_tokens(x) if n in rank3 else x
                   for n, x in zip("qkv", (q, k, v)))
        return flash_attention(q, k, v, head_dim=d, tokens_out=True, **kw)

    def repeated(q, k, v):
        k, v = (jnp.repeat(x, rep, axis=1) for x in (k, v))
        return _tokens(mha_reference(q, k, v, **ref_kw))

    (got, got_grads), (want, want_grads) = (
        _both(f, (q, k, v), g) for f in (grouped, repeated))
    _close(got, want, jnp.float32, False, "out")
    for name, x, y in zip("qkv", got_grads, want_grads):
        assert x.shape == y.shape
        _close(x, y, jnp.float32, False, "d" + name)

    # what the kernels are handed and what they write: k, v and their
    # gradients at the key/value heads, whatever the rank, unless the copies
    # stand in; a gradient a query head where the sum is beside the kernel
    kernels = _kernel_operands(lambda *a: _both(grouped, a, g), q, k, v)
    as_q = np.prod(kernels["flash_fwd"][0])
    smaller = [as_q // np.prod(shape) for shape in (
        kernels["flash_fwd"][1:3] + kernels["flash_bwd"][4:6]
        + kernels["flash_bwd"][-2:])]
    assert smaller == {
        "vmem": [rep] * 6, "beside": [rep] * 4 + [1] * 2,
        "repeated": [1] * 6}[summed], kernels


@pytest.mark.parametrize("first", [0, 16])
@pytest.mark.parametrize("window", [None, 100])
def test_a_group_of_narrow_scores_over_wider_values(first, window):
    """Differential attention's calls: of one projection's 8 query and 4
    key heads 16 wide every second one, from ``first``, scores the values as
    2 heads of 32, two query heads a key/value head.  The group's dQ has rows
    narrower than a lane tile, which the backward kernel indexes in its
    accumulator (``_Head``), interpreted as compiled."""
    b, h, n_kv, d, s = 2, 8, 4, 16, 384
    keys = jax.random.split(jax.random.PRNGKey(13), 2)
    qkv = jax.random.normal(keys[0], (b, s, (h + 2 * n_kv) * d))
    g = jax.random.normal(keys[1], (b, s, h // 2 * 2 * d))
    kw = {} if window is None else dict(window=window)

    def as_they_lie(qkv):
        kv = qkv[..., h * d:]
        return flash_attention(
            HeadColumns(qkv, h // 2, d, first=first, stride=2 * d),
            HeadColumns(kv, n_kv // 2, d, first=first, stride=2 * d),
            HeadColumns(kv, n_kv // 2, 2 * d, first=n_kv * d),
            tokens_out=True, block_q=128, block_k=128, **kw)

    def plain(qkv):
        q, k, v = jnp.split(qkv, [h * d, (h + n_kv) * d], axis=-1)
        q, k = (_heads(x, n)[:, first // d::2] for x, n in ((q, h), (k, n_kv)))
        k, v = (jnp.repeat(x, 2, axis=1) for x in (k, _heads(v, n_kv // 2)))
        return _tokens(mha_reference(q, k, v, **kw))

    (got, (got_grad,)), (want, (want_grad,)) = (
        _both(f, (qkv,), g) for f in (as_they_lie, plain))
    _close(got, want, jnp.float32, False, "out")
    _close(got_grad, want_grad, jnp.float32, False, "dqkv")
    # the group's dK and dV are summed in the kernel, at the key/value heads
    kernels = _kernel_operands(lambda x: _both(as_they_lie, (x,), g), qkv)
    assert kernels["flash_bwd"][-2:] == [(b, n_kv // 2, s, d),
                                         (b, n_kv // 2, s, 2 * d)], kernels


@pytest.mark.parametrize("n_kv", [2, 1])
def test_under_a_tp_mesh_a_device_takes_whole_key_value_heads(n_kv, caplog):
    """dp=2 x tp=2 on four CPU devices, four query heads: over two key/value
    heads each device's call is grouped, two heads over its one; over one,
    which no two devices can share by whole heads, the copies stand in, and
    the log says so."""
    b, h, d = 2, 4, 128
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    q, k, v, g = (jax.random.normal(key, (b, heads, SEQ, d))
                  for key, heads in zip(keys, (h, n_kv, n_kv, h)))
    g = _tokens(g)

    def grouped(q, k, v):
        return flash_attention(q, k, _tokens(v), tokens_out=True)

    want, want_grads = _both(grouped, (q, k, v), g)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    with jax.set_mesh(mesh), caplog.at_level("WARNING"):
        got, got_grads = jax.jit(lambda *a: _both(grouped, a, g))(q, k, v)
    assert ("copied to the query heads" in caplog.text) == (n_kv == 1)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    for x, y in zip(got_grads, want_grads):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=2e-5)


# ------------------------------------------------------------- the models
def _tree(params):
    return {"/".join(str(k.key) for k in path): leaf.shape for path, leaf
            in jax.tree_util.tree_flatten_with_path(params)[0]}


def _loss_and_grads(model, params, ids):
    from ray_tpu.models.gpt2 import lm_loss

    def loss(params):
        logits = model.apply({"params": params}, ids)
        return lm_loss(logits[:, :-1], ids[:, 1:])
    # (jitted: op by op a model is a few hundred programs)
    return jax.jit(jax.value_and_grad(loss))(params)


def _against_reference(model_of, cfg, ids):
    """Loss and gradients of ``cfg``'s model under the flash kernels against
    ``attention_impl="reference"``, from the same parameters."""
    flash, plain = (model_of(dataclasses.replace(
        cfg, attention_impl=impl, dtype=jnp.float32, remat=False))
        for impl in ("flash", "reference"))
    params = jax.jit(flash.init)(jax.random.PRNGKey(0), ids)["params"]
    (got, got_grads), (want, want_grads) = (
        _loss_and_grads(m, params, ids) for m in (flash, plain))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(got_grads)[0],
            jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5,
                                   err_msg=str(path))
    return params


def test_gpt2_reads_its_projection_as_it_lies():
    """Four heads of 64 (two pairs): loss and every gradient against the
    reference attention; the parameter tree is the parent's; no rank-4
    transpose is left in the attention layer's program."""
    from ray_tpu.models.gpt2 import Attention, GPT2Config, GPT2LMModel

    cfg = GPT2Config(vocab_size=512, n_positions=128, n_embd=256, n_layer=2,
                     n_head=4)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 512)
    params = _against_reference(GPT2LMModel, cfg, ids)
    tree = _tree(params)
    assert {k: v for k, v in tree.items() if k.startswith("h_0/attn")} == {
        "h_0/attn/qkv_proj/kernel": (256, 768),
        "h_0/attn/qkv_proj/bias": (768,),
        "h_0/attn/out_proj/kernel": (256, 256),
        "h_0/attn/out_proj/bias": (256,)}
    assert len(tree) == 2 * 12 + 5, sorted(tree)
    layer = Attention(dataclasses.replace(cfg, dtype=jnp.float32))
    x = jnp.zeros((2, 128, 256))
    layer_params = layer.init(jax.random.PRNGKey(0), x)
    assert _four_d_transposes(jax.grad(
        lambda p, x: jnp.sum(layer.apply(p, x))), layer_params, x) == 0


LLAMA_TREES = {
    # every head its own key/value head: v goes as its projection wrote it
    "mha": (dict(n_head=2, n_kv_head=2), 2),
    # grouped: k and v go as they are, one head where q has two
    "gqa": (dict(n_head=2, n_kv_head=1), 1),
    "gqa-rep4-nope": (dict(n_head=4, n_kv_head=1, head_dim=128, rope=False),
                      1),
    # 64-wide heads, grouped, under the per-head norm: the pair form, over
    # k and v copied to the query heads (``flash_attention``'s fallback)
    "gqa-d64-head-norm": (dict(n_head=4, n_kv_head=2, head_dim=64,
                               qk_norm="head"), 2),
    # no rotation: q straight from its projection too
    "nope-mha": (dict(n_head=2, n_kv_head=2, rope=False), 2),
}


@pytest.mark.parametrize("kind", list(LLAMA_TREES))
def test_a_llama_block_hands_over_what_its_projections_wrote(kind):
    from ray_tpu.models.llama import LlamaConfig, LlamaLMModel

    fields, kv_heads = LLAMA_TREES[kind]
    cfg = LlamaConfig(vocab_size=512, n_positions=128, d_model=256, n_layer=1,
                      d_ff=256, **fields)
    d = fields.get("head_dim", 128)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 512)
    tree = _tree(_against_reference(LlamaLMModel, cfg, ids))
    want = {"h_0/attn/wq/kernel": (256, fields["n_head"] * d),
            "h_0/attn/wk/kernel": (256, kv_heads * d),
            "h_0/attn/wv/kernel": (256, kv_heads * d),
            "h_0/attn/wo/kernel": (fields["n_head"] * d, 256)}
    if fields.get("qk_norm"):
        want.update({"h_0/attn/q_norm/scale": (d,),
                     "h_0/attn/k_norm/scale": (d,)})
    assert {k: v for k, v in tree.items() if k.startswith("h_0/attn/")} == want
