"""Granite 4.0-H through the shared Llama block, at toy widths on the CPU:
``ops/ssd.py``'s chunked scan against the recurrence position by position,
``models/mamba.py``'s convolution against an explicit loop, and the whole
hybrid model (``mamba`` and ``attention`` layers mixed, NoPE, the three
multipliers, the tied head) against the plain reference of
``perfbench/harness/families/granite_hybrid.py``, with seeded weights moved
off their initial values; the steps under a mesh and the step that learns are
``tests/test_mamba_mesh.py``'s.  On the chip the same reference runs at
published widths against the bf16 program (``perfbench/harness/agreement.py``).

Tolerances.  Float32 against float32 at matmul precision 'highest' differ by
summation order alone: 2e-4 on logits of size 1, 1e-5 on the loss, 1e-4 on
the gradient norm (``tests/test_olmoe.py``'s, for the same reason).  The
scan alone is held tighter, 1e-4 relative to its largest value, and its
gradients to 1e-3 of theirs.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toys
from perfbench.harness.families import granite_hybrid
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.models.mamba import causal_conv
from ray_tpu.models.pretrain import init_params, make_optimizer, train_step
from ray_tpu.ops import ssd

# 64 wide; 8 Mamba heads of 16 with a state of 16, chunks of 16; 4 query
# heads over 2 key/value heads; layers mamba, mamba, attention
TOY = toys.toy("toy-granite")


def _recurrence(x, dt, rate, b, c):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T; y_t = h_t C_t, one
    position at a time."""
    batch, _, heads, p = x.shape
    groups, n = b.shape[-2:]
    b, c = (jnp.repeat(t, heads // groups, axis=2) for t in (b, c))

    def step(h, at):
        x_t, dt_t, b_t, c_t = at
        h = jnp.exp(dt_t * rate)[..., None, None] * h \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((batch, heads, p, n)),
                        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def _scan_inputs(seq, groups, dtype=jnp.float32, heads=4, p=8, n=16):
    k = jax.random.split(jax.random.PRNGKey(seq), 5)
    return (jax.random.normal(k[0], (2, seq, heads, p), dtype),
            jax.nn.softplus(jax.random.normal(k[1], (2, seq, heads)) - 1.0),
            -jnp.exp(jax.random.normal(k[2], (heads,))),
            jax.random.normal(k[3], (2, seq, groups, n), dtype),
            jax.random.normal(k[4], (2, seq, groups, n), dtype))


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("seq", [32, 37, 5])
def test_a_chunked_scan_equals_the_recurrence(seq, groups):
    """Values and gradients in float32, at a length that is a multiple of the
    chunk (8), at one that is not, and at one shorter than a chunk: the
    padding is inside ``ssd_scan``."""
    args = _scan_inputs(seq, groups)
    with jax.default_matmul_precision("highest"):
        # (jitted: the interpreter would run the kernels' bodies op by op)
        got = jax.jit(lambda *a: ssd.ssd_scan(*a, chunk=8))(*args)
        want = jax.jit(_recurrence)(*args)
        assert got.shape == want.shape == (2, seq, 4, 8)
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=1e-4 * float(jnp.max(jnp.abs(want))))

        def grads(fn):
            return jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                                    argnums=(0, 1, 2, 3, 4)))(*args)

        for name, g, w in zip("x dt A B C".split(),
                              grads(lambda *a: ssd.ssd_scan(*a, chunk=8)),
                              grads(_recurrence)):
            np.testing.assert_allclose(
                g, w, rtol=0, atol=1e-3 * float(jnp.max(jnp.abs(w))),
                err_msg=name)


@pytest.mark.parametrize("case", ["published_tile", "long_carry"])
def test_a2_the_kernels_at_the_published_tile_and_over_many_chunks(case):
    """``published_tile``: the shape the chip runs a grid step at — chunks of
    256, heads 64 wide two to a lane tile, a state of 128, four heads in one
    group — in bf16 over two whole chunks and a ragged third, against the
    float32 recurrence on the same bf16 inputs: values to the rounding of the
    bf16 matmul operands (2% of the largest, as (b)), the five gradients to
    3% of theirs.  ``long_carry``: float32, six chunks of 8 and a ragged
    seventh at decays strong enough (``exp(dt A)`` down to 1e-3 a position)
    that a state carried wrongly from chunk to chunk, or its cotangent on the
    way back, cannot hide behind the chunk's own part: (a)'s tolerances."""
    if case == "published_tile":
        seq, chunk, dtype, value_tol, grad_tol = 600, 256, jnp.bfloat16, \
            0.02, 0.03
        k = jax.random.split(jax.random.PRNGKey(1), 5)
        args = (jax.random.normal(k[0], (1, seq, 4, 64), dtype),
                jnp.exp(jax.random.uniform(k[1], (1, seq, 4), jnp.float32,
                                           np.log(1e-3), np.log(0.1))),
                -jax.random.uniform(k[2], (4,), jnp.float32, 1.0, 16.0),
                jax.random.normal(k[3], (1, seq, 1, 128), dtype),
                jax.random.normal(k[4], (1, seq, 1, 128), dtype))
    else:
        seq, chunk, dtype, value_tol, grad_tol = 53, 8, jnp.float32, \
            1e-4, 1e-3
        x, dt, rate, b, c = _scan_inputs(seq, 2)
        args = (x, dt, rate * 8.0, b, c)

    def grads(fn):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32))),
            argnums=(0, 1, 2, 3, 4)))(*args)

    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: ssd.ssd_scan(*a, chunk=chunk))(*args)
        assert got.dtype == dtype and got.shape == args[0].shape
        exact = lambda x, dt, rate, b, c: _recurrence(     # noqa: E731
            x.astype(jnp.float32), dt, rate, b.astype(jnp.float32),
            c.astype(jnp.float32))
        want = jax.jit(exact)(*args)
        np.testing.assert_allclose(
            got.astype(jnp.float32), want, rtol=0,
            atol=value_tol * float(jnp.max(jnp.abs(want))))
        for name, g, w in zip("x dt A B C".split(),
                              grads(lambda *a: ssd.ssd_scan(*a, chunk=chunk)),
                              grads(exact)):
            np.testing.assert_allclose(
                g.astype(jnp.float32), w.astype(jnp.float32), rtol=0,
                atol=grad_tol * float(jnp.max(jnp.abs(w))), err_msg=name)


def test_b_the_scan_decays_in_float32_inside_a_bf16_layer(monkeypatch):
    """The guarantee the configuration states: with bf16 activations the
    running sums of ``dt * A`` and the decays are float32, so the scan equals
    the float32 recurrence on the same bf16 inputs to the rounding of its
    bf16 matmul operands (2**-8 each, a few of them: 2% of the largest value
    holds it); running sums kept in bf16 over a chunk of 256 are another
    model, and fail the same test by a wide margin."""
    x, dt, rate, b, c = _scan_inputs(512, 1, jnp.bfloat16)
    rate = rate * 4.0       # decays as strong as A in [1, 16] gives
    with jax.default_matmul_precision("highest"):
        want = _recurrence(x.astype(jnp.float32), dt, rate,
                           b.astype(jnp.float32), c.astype(jnp.float32))
    top = float(jnp.max(jnp.abs(want)))

    def worst():
        got = ssd.ssd_scan(x, dt, rate, b, c, chunk=256)
        assert got.dtype == jnp.bfloat16
        return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) / top

    assert worst() < 0.02
    cumsum = jnp.cumsum
    monkeypatch.setattr(
        ssd.jnp, "cumsum", lambda a, axis: cumsum(
            a.astype(jnp.bfloat16), axis=axis).astype(jnp.float32))
    assert worst() > 0.1


def test_c_the_convolution_equals_a_loop_and_reads_no_later_position():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k[0], (2, 11, 6))
    kernel, bias = jax.random.normal(k[1], (4, 6)), jax.random.normal(k[2], (6,))
    got = np.asarray(causal_conv(x, kernel, bias))
    want = np.zeros((2, 11, 6), np.float32)
    for t in range(11):
        for tap in range(4):
            src = t - 3 + tap       # the last tap reads position t itself
            if src >= 0:
                want[:, t] += np.asarray(kernel[tap] * x[:, src])
        want[:, t] += np.asarray(bias)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the prefix property the chip's agreement check rests on
    later = x.at[:, 7:].set(99.0)
    np.testing.assert_array_equal(
        np.asarray(causal_conv(later, kernel, bias))[:, :7], got[:, :7])


# The program in float32 with XLA attention, so that what is left to differ
# from the reference is the mathematics.  41 positions are two whole chunks of
# 16 and a padded one.
_F32 = dict(attention_impl="reference")


@pytest.mark.parametrize("positions,chips", [(41, 1), (32, 0)])
def test_d_program_equals_the_reference_in_float32(positions, chips):
    """The three layers of the one-chip cut (mamba, mamba, attention) at a
    length the scan has to pad, and (``chips`` 0: no cut) the whole toy list,
    six layers of both kinds, at two whole chunks."""
    got = toys.program("toy-granite", positions, chips=chips, **_F32)
    want = toys.reference("toy-granite", positions, chips=chips, **_F32)
    np.testing.assert_allclose(got.logits, want.logits, rtol=2e-4, atol=2e-4)
    assert float(got.loss) == pytest.approx(float(want.loss), rel=1e-5)
    assert float(got.gradnorm) == pytest.approx(float(want.gradnorm),
                                                rel=1e-4)


@pytest.mark.parametrize("wrong,margin", [
    ({"attention_multiplier": 0.25}, 20),   # 1/sqrt(16) where 1/16 is stated
    ({"residual_multiplier": 1.0}, 20),
    ({"position_embedding_type": "rope"}, 20),
    ({"gate_after_norm": True}, 20),
    ({"decay_dtype": "bfloat16"}, 5),
    ({"embedding_multiplier": 1.0}, 20),
    ({"logits_scaling": 1.0}, 20),
], ids=lambda w: next(iter(w)) if isinstance(w, dict) else "")
def test_e_the_tolerance_sees_a_wrong_model(wrong, margin):
    """The five wrong models of the chip's controls
    (``perfbench/tests/granite_on_chip.py``) and the two other multipliers:
    each at least twenty times the tolerance of (d) away — but for the
    running sums in bf16, which over the toy's chunks of 16 positions lose
    little (five times the tolerance here); what holds them is (b), at the
    published chunk of 256.  The program's logits are (d)'s, made once."""
    wrong = {k: (jnp.dtype(v) if k == "decay_dtype" else v)
             for k, v in wrong.items()}
    got = toys.program("toy-granite", 41, **_F32)
    want = toys.reference("toy-granite", 41, backward=False, wrong=wrong,
                          **_F32)
    assert float(np.max(np.abs(got.logits - want.logits))) > margin * 2e-4


def test_f_the_tied_table_gets_both_gradients():
    """The table's gradient is the sum of the embedding gather's and the
    head matmul's: the same loss with two tables where the model has one —
    the gather reads the first, the head multiplies by the second — gives
    the two parts, both non-zero, and their sum is the program's gradient."""
    from ray_tpu.models.gpt2 import lm_loss

    model, params = toys.weights("toy-granite", **_F32)
    batch = toys.rows("toy-granite", 2, 41)
    assert "lm_head" not in params
    table = params["wte"]["embedding"]

    def loss_of(gather_table, head_table):
        _, sown = model.apply(
            {"params": dict(params, wte={"embedding": gather_table})},
            batch["input_ids"],
            capture_intermediates=lambda m, _: m.name == "norm_f")
        x = sown["intermediates"]["norm_f"]["__call__"][0]
        return lm_loss(jnp.einsum("bsd,vd->bsv", x / TOY["logits_scaling"],
                                  head_table), batch["targets"])

    whole = toys.program("toy-granite", 41, **_F32).grads["wte"]["embedding"]
    with jax.default_matmul_precision("highest"):
        by_gather, by_head = jax.jit(jax.grad(loss_of, argnums=(0, 1)))(
            table, table)
    assert float(jnp.max(jnp.abs(by_gather))) > 1e-4
    assert float(jnp.max(jnp.abs(by_head))) > 1e-4
    np.testing.assert_allclose(whole, by_gather + by_head, rtol=1e-4,
                               atol=1e-7)


def test_g_layer_types_drive_the_kinds():
    """A layer is what its entry says: the parameter tree has ``mamba`` or
    ``attn`` in each block, no ``lm_head`` (the head is the table), and a
    list that does not name every layer, or names an unknown kind, is
    refused."""
    _, params = toys.weights("toy-granite", 0, **_F32)
    kinds = TOY["layer_types"]
    assert len(kinds) == 6 and set(kinds) == {"mamba", "attention"}
    for i, kind in enumerate(kinds):
        block = params[f"h_{i}"]
        assert set(block) == {"attn_norm", "mlp_norm", "mlp",
                              "mamba" if kind == "mamba" else "attn"}, i
    assert set(params["h_0"]["mamba"]) == {
        "in_proj", "out_proj", "conv_kernel", "conv_bias", "dt_bias", "A_log",
        "D", "norm_scale"}
    assert set(params) == {"wte", "norm_f"} | {f"h_{i}" for i in range(6)}
    cfg = granite_hybrid.model_config(TOY, 1)
    for wrong in (("mamba",), ("mamba", "attention", "linear")):
        with pytest.raises(ValueError, match="layer"):
            # (refused while it is traced: nothing is initialised)
            jax.eval_shape(lambda: init_params(dataclasses.replace(
                cfg, layer_types=wrong))[1])


def _lowered_step(model, params):
    """The train step lowered, with every operation's name path in it;
    ``params`` may be shapes alone."""
    tx = make_optimizer()
    batch = {k: jax.ShapeDtypeStruct((2, 16), jnp.int32)
             for k in ("input_ids", "targets")}
    return jax.jit(lambda s, b: train_step(model, tx, s, b)).lower(
        (params, jax.eval_shape(tx.init, params)), batch).as_text(
            debug_info=True)


@functools.lru_cache(maxsize=None)
def _hybrid_text():
    return _lowered_step(*toys.weights("toy-granite", **_F32))


@pytest.mark.parametrize("family", ["llama", "olmoe", "gpt2"])
def test_h_a_model_without_mamba_is_the_program_it_was(family):
    """``layer_types`` empty and the new fields at their defaults: the step
    has nothing of the mixer, the multipliers or the tied head in it, and the
    parameter tree is the one it was.  (The lowered text of these toy steps,
    flash and XLA attention, remat on and off, equals the parent commit's
    byte for byte: checked by hand in PR 29, as PR 25 did.)"""
    from ray_tpu.models.gpt2 import GPT2Config, GPT2LMModel
    from ray_tpu.models.llama import LlamaLMModel

    if family == "llama":
        cfg = dataclasses.replace(LlamaConfig.tiny(),
                                  attention_impl="reference")
    elif family == "olmoe":
        cfg = toys.config("toy-olmoe", dtype=None, attention_impl="reference")
    else:
        cfg = GPT2Config(vocab_size=512, n_positions=64, n_embd=64, n_layer=2,
                         n_head=4, attention_impl="reference")
    model = (GPT2LMModel if family == "gpt2" else LlamaLMModel)(cfg)
    # (the tree's shapes are all the step's text reads of the parameters)
    params = jax.eval_shape(lambda: init_params(cfg)[1])
    assert "lm_head" in params
    assert all("mamba" not in params[k] for k in params if k.startswith("h_"))
    text = _lowered_step(model, params)
    for absent in ("/mamba/", "/ssd/", "/gated_norm/", "/conv/"):
        assert absent not in text, absent
    for present in ("/lm_head/", "/attn/") + (
            ("/rope/",) if family != "gpt2" else ()):
        assert present in text, present
    # the same text of the hybrid model does name them: the check can see
    hybrid = _hybrid_text()
    for present in ("/mamba/", "/ssd/", "/gated_norm/", "/conv/", "/lm_head/",
                    "/attn/"):
        assert present in hybrid, present
    assert "/rope/" not in hybrid
