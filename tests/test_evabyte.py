"""EvaByte through the shared Llama block, at toy widths on the CPU, with
seeded weights moved off their initial values: (a) the flash kernels under the
EVA mask — a window's own causal tiles and the summaries of the windows before
in one online softmax — interpreted, against ``mha_reference`` with the same
mask written out, forward and every gradient, the summaries' among them, and a
row of one window as the causal call; (b) the pooling (``ops/pooling.py``'s
``pool_chunks``) against the plain form, and what may reach what: no later
position, and no summary of a query's own window; (c) the program against the
plain reference of ``perfbench/harness/families/evabyte.py`` — the pooling as
a softmax over a ``(chunks, 16, 128)`` view, one dense mask over ``[summaries
; positions]``, a head at a time — logits of every head, loss, every gradient
leaf, one ``ShardedPretrainer`` step, and every wrong model of the on-chip
controls outside the float32 limits; (d) the objective: head ``r`` against the
token ``r + 1`` ahead, the mask shifted with the targets, one head
``lm_loss`` to the bit; (e) the new parameters' partition rules and one
device's losses on a virtual mesh.  (f), every toy's step as it was lowered,
is ``tests/test_pinned_steps.py`` and ``tests/test_sdar_programs.py`` (i),
``tests/test_sdar_parts.py`` (n).  The toy
(``perfbench/tests/toy/toy-evabyte.json``): 256 wide, two layers, two heads
of 128, windows of 128 and chunks of 16, four heads over 96 ids.  On the chip
the same reference runs at published widths against the bf16 program
(``perfbench/harness/mtp_agreement.py``).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toys
from perfbench.harness.families import evabyte
from perfbench.harness.tokens import ZipfStream
from ray_tpu.models.gpt2 import lm_loss, shifted_heads_loss
from ray_tpu.models.llama import LlamaConfig, LlamaLMModel
from ray_tpu.models.pretrain import init_params
from ray_tpu.ops.attention import (attention, eva_mask, flash_attention,
                                   mha_reference)
from ray_tpu.ops.pooling import pool_chunks

TOY = toys.toy("toy-evabyte")


# ----------------------------------------------------------- (a) the kernels
def _operands(batch, heads, seq, width, chunk, n_pooled=None, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed + seq), 6)
    n = -(-seq // chunk) if n_pooled is None else n_pooled
    q, k, v, g = (jax.random.normal(key, (batch, heads, seq, width),
                                    jnp.float32) for key in keys[:4])
    kp, vp = (jax.random.normal(key, (batch, heads, n, width), jnp.float32)
              for key in keys[4:])
    return (q, k, v, kp, vp), g


def _tokens(x):
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


@pytest.mark.parametrize("batch,heads,seq,width,window,chunk,rank3,block", [
    (1, 2, 128, 128, 128, 16, True, None),    # one window: no summary is seen
    (1, 2, 384, 128, 128, 16, False, None),   # three windows
    (2, 2, 300, 128, 128, 8, True, None),     # a last window and chunk partial
    (1, 1, 1024, 128, 256, 16, True, 128),    # two tiles a window
    (1, 2, 640, 64, 256, 2, True, None),      # heads 64 wide; a window's 128
                                              # summaries one whole tile
    (1, 2, 400, 32, 384, 16, False, None),    # a window of three lane tiles
], ids=["one-window", "three", "partial-b2", "two-tiles", "w64-full-tile",
        "w384"])
def test_a_kernels_equal_the_reference_under_the_same_mask(
        batch, heads, seq, width, window, chunk, rank3, block):
    """Forward and the gradients of q, k, v and of the summaries' keys and
    values; operands of rank 4 and, where the kernels address them as they
    lie, of rank 3."""
    args, g = _operands(batch, heads, seq, width, chunk)

    def plain(q, k, v, kp, vp):
        return mha_reference(q, k, v, eva=(window, chunk), pooled=(kp, vp))

    def kernels(q, k, v, kp, vp):
        if not rank3:
            return flash_attention(
                q, k, v, eva_window=window, eva_chunk=chunk, k_pooled=kp,
                v_pooled=vp, block_q=block, block_k=block)
        out = flash_attention(
            *map(_tokens, (q, k, v)), eva_window=window, eva_chunk=chunk,
            k_pooled=_tokens(kp), v_pooled=_tokens(vp), head_dim=width,
            block_q=block, block_k=block)
        return out.reshape(batch, seq, heads, width).transpose(0, 2, 1, 3)

    np.testing.assert_allclose(kernels(*args), plain(*args), atol=2e-5)
    got, want = (jax.grad(lambda *a: jnp.sum(f(*a) * g), range(5))(*args)
                 for f in (kernels, plain))
    for name, a, w in zip(("q", "k", "v", "k_pooled", "v_pooled"), got, want):
        assert a.shape == w.shape
        np.testing.assert_allclose(a, w, atol=5e-5, err_msg="d" + name)
    # the summaries that no query sees take no gradient: those of the last
    # window, and a partial chunk's
    seen = (seq - 1) // window * (window // chunk)
    assert not np.any(got[3][:, :, seen:]) and not np.any(got[4][:, :, seen:])
    assert seen == 0 or np.any(got[3][:, :, :seen])


def test_a_the_mask_written_out():
    """``eva_mask`` at a size to read: windows of 4, chunks of 2, 10
    positions."""
    mask = np.asarray(eva_mask(10, 4, 2, 5))
    assert mask.shape == (10, 15)
    for i in range(10):
        w = i // 4
        assert list(np.flatnonzero(mask[i, :5])) == list(range(2 * w))
        assert list(np.flatnonzero(mask[i, 5:])) == list(range(4 * w, i + 1))


def test_a_a_row_of_one_window_is_the_causal_call():
    """At most one window: ``attention`` makes the causal call, the summaries
    unread — the same lowered text — whatever the implementation; and past
    one window ``ring`` refuses the mask by name."""
    (q, k, v, kp, vp), _ = _operands(1, 2, 128, 128, 16)

    def lowered(impl, **eva):
        return jax.jit(lambda q, k, v, kp, vp: attention(
            q, k, v, impl=impl, **(dict(k_pooled=kp, v_pooled=vp, **eva)
                                   if eva else {}))).lower(
                q, k, v, kp, vp).as_text()

    for impl in ("flash", "reference"):
        assert lowered(impl, eva_window=128, eva_chunk=16) == lowered(impl)
        assert lowered(impl, eva_window=256, eva_chunk=16) == lowered(impl)
    assert lowered("reference", eva_window=64, eva_chunk=16) \
        != lowered("reference")
    with pytest.raises(NotImplementedError, match="'ring'.* has no EVA mask"):
        attention(q, k, v, impl="ring", eva_window=64, eva_chunk=16,
                  k_pooled=kp, v_pooled=vp)
    with pytest.raises(NotImplementedError, match="whole lanes"):
        flash_attention(q, k, v, eva_window=64, eva_chunk=16, k_pooled=kp,
                        v_pooled=vp)


# ------------------------------------------------------- the cell's stream
@pytest.mark.parametrize("hold", [0.5, 0.75, 0.9])
def test_the_held_stream_is_zipf_ids_held_for_runs(hold):
    """``HeldZipfStream``: ``ZipfStream``'s ids and its marginal
    distribution, the id ``r + 1`` ahead the id at hand
    with probability ``hold ** (r + 1)`` (beside a fresh draw's chance of the
    same id), ``targets`` the row rolled left by one, and the same seed the
    same rows."""
    from perfbench.harness.held_tokens import HeldZipfStream

    vocab, seq = 320, 16384
    rows = HeldZipfStream(vocab, 2 ** 31 + 47, hold).rows(8, seq)
    ids = rows["input_ids"]
    assert ids.dtype == np.int32 and ids.min() >= 0 and ids.max() < vocab
    np.testing.assert_array_equal(rows["targets"], np.roll(ids, -1, axis=1))
    np.testing.assert_array_equal(
        HeldZipfStream(vocab, 2 ** 31 + 47, hold).rows(8, seq)["input_ids"],
        ids)
    # every id is one that ``ZipfStream`` drew, at its own or an earlier place
    drawn = ZipfStream(vocab, 2 ** 31 + 47).rows(8, seq)["input_ids"]
    fresh = np.concatenate([np.ones((8, 1), bool), ids[:, 1:] != ids[:, :-1]],
                           axis=1)
    np.testing.assert_array_equal(ids[fresh], drawn[fresh])
    zipf = 1.0 / np.arange(1, vocab + 1)
    zipf /= zipf.sum()
    share = np.bincount(ids.ravel(), minlength=vocab) / ids.size
    # (runs make the sample's effective size 1 - hold of its positions)
    assert abs(share[:8] - zipf[:8]).max() < 0.02
    same = float(np.sum(zipf ** 2))
    for ahead in (1, 2, 8):
        kept = hold ** ahead
        assert (ids[:, ahead:] == ids[:, :-ahead]).mean() == pytest.approx(
            kept + (1 - kept) * same, abs=0.01)


# ----------------------------------------------------------- (b) the pooling
@pytest.mark.parametrize("v_rank,kernels,s,chunk", [
    (3, False, 96, 16), (4, False, 96, 16), (4, True, 96, 16),
    (3, True, 96, 16),          # the Pallas pass: less than one tile
    (3, True, 2048 + 512, 16),  # a whole tile of 128 chunks and a part
    (3, True, 320, 2),          # a tile of 256 positions
], ids=["xla-tokens", "xla-heads", "heads-fall-back", "kernels",
        "kernels-tiles", "kernels-chunk2"])
def test_b_the_pooling_equals_the_plain_form(v_rank, kernels, s, chunk):
    """Both summaries of every chunk and head against ``families/evabyte.py``'s
    softmax over a ``(chunks, 16, 128)`` view, and every gradient, ``phi``'s
    and ``mu``'s among them: as XLA fuses ``pool_reference`` and
    through the kernels of ``ops/pooling.py``, interpreted, with their
    written-out backward."""
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    b, h, d = 2, 2, 128
    k, v = (jax.random.normal(key, (b, h, s, d), jnp.float32)
            for key in keys[:2])
    phi, mu = (jax.random.normal(key, (h, d), jnp.float32)
               for key in keys[2:4])
    g = jax.random.normal(keys[4], (2, b, h, s // chunk, d), jnp.float32)

    def program(k, v, phi, mu):
        kt, vt = pool_chunks(k, _tokens(v) if v_rank == 3 else v, phi, mu,
                             chunk, d ** -0.5,
                             impl="flash" if kernels else "reference")
        if v_rank == 3:
            vt = vt.reshape(b, s // chunk, h, d).transpose(0, 2, 1, 3)
        return jnp.stack([kt, vt])

    def plain(k, v, phi, mu):
        one = functools.partial(evabyte.pool, chunk=chunk, scale=d ** -0.5)
        over_heads = jax.vmap(one, in_axes=(0, 0, 0, 0))
        return jnp.stack(jax.vmap(over_heads, in_axes=(0, 0, None, None))(
            k, v, phi, mu))

    np.testing.assert_allclose(program(k, v, phi, mu), plain(k, v, phi, mu),
                               atol=2e-5)
    got, want = (jax.grad(lambda *a: jnp.sum(f(*a) * g), range(4))(
        k, v, phi, mu) for f in (program, plain))
    for name, a, w in zip(("k", "v", "phi", "mu"), got, want):
        np.testing.assert_allclose(a, w, atol=5e-5, err_msg="d" + name)


# The program runs in float32, so that what is left to differ from the
# reference is the mathematics; ``attention_impl`` "flash" is the Pallas
# kernels interpreted, with their own backward rule.  Every leaf is moved off
# its initial value: no norm's ``g`` is zero.
@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_b_what_reaches_what(impl):
    """Position ``t`` does not move when a later input does.  A chunk's
    summary reaches no query of its own window — an input in the last chunk
    of window 0 moves its own window's later positions through the exact keys
    alone, so dropping the summaries' path (a window as long as the row)
    changes nothing there — and does reach the next window's."""
    model, params = toys.weights(TOY, attention_impl=impl)
    ids = toys.rows(TOY, 2, 320)["input_ids"]
    apply = jax.jit(lambda ids: model.apply({"params": params}, ids))
    out = apply(ids)
    assert out.dtype == jnp.float32 and out.shape == (2, 320, 4 * 96)
    later = apply(ids.at[:, 200:].set((ids[:, 200:] + 1) % 96))
    np.testing.assert_array_equal(out[:, :200], later[:, :200])
    assert float(jnp.max(jnp.abs(out - later)[:, 200:])) > 1e-3
    # window 0 of the EVA stack is the causal stack's window 0 ...
    causal = LlamaLMModel(toys.config(TOY, attention_impl=impl,
                                      eva_window=0))
    plain = jax.jit(lambda ids: causal.apply(
        {"params": {k: ({n: w for n, w in v.items() if n != "attn"}
                        | {"attn": {n: w for n, w in v["attn"].items()
                                    if n not in ("phi", "mu")}})
                    if k.startswith("h_") else v
                    for k, v in params.items()}}, ids))(ids)
    np.testing.assert_allclose(out[:, :128], plain[:, :128], rtol=2e-4,
                               atol=2e-4)
    # ... and past it the two differ: summaries stand for window 0
    assert float(jnp.max(jnp.abs(out - plain)[:, 128:])) > 1e-3
    # moving phi moves nothing inside window 0 and everything after it
    moved = dict(params, h_0=dict(params["h_0"], attn=dict(
        params["h_0"]["attn"], phi=params["h_0"]["attn"]["phi"] + 1.0)))
    other = jax.jit(lambda p: model.apply({"params": p}, ids))(moved)
    np.testing.assert_array_equal(out[:, :128], other[:, :128])
    assert float(jnp.min(jnp.max(jnp.abs(out - other)[:, 128:],
                                 axis=(0, 2)))) > 1e-6


# ------------------------------------------ (c) the stack and its reference
@pytest.mark.parametrize("impl,positions", [
    ("reference", 320), ("flash", 320), ("flash", 300), ("flash", 96)],
    ids=["reference", "flash", "flash-300", "flash-one-window"])
def test_c_program_equals_the_reference_in_float32(impl, positions):
    """Logits of all four heads, the loss over them and the gradient norm to
    float32 rounding; 300 positions end inside a window and a chunk, 96 are
    less than one window."""
    got = toys.program(TOY, positions, attention_impl=impl)
    want = toys.reference(TOY, positions, attention_impl=impl)
    assert got.logits.shape == (2, positions, 4 * 96)
    np.testing.assert_allclose(got.logits, want.logits, rtol=2e-4, atol=2e-4)
    assert float(got.loss) == pytest.approx(float(want.loss), rel=1e-5)
    assert float(got.gradnorm) == pytest.approx(float(want.gradnorm),
                                                rel=1e-4)


def test_c_every_gradient_equals_the_references():
    """Leaf by leaf, not only the norm: attention's four projections with
    ``phi`` and ``mu``, the norms' ``g``, the SwiGLU, the head's 4 x 96
    columns."""
    got = toys.program(TOY, 320, attention_impl="flash").grads
    want = toys.reference(TOY, 320, leaves=True,
                          attention_impl="flash").grads
    assert set(got["h_1"]["attn"]) == {"wq", "wk", "wv", "wo", "phi", "mu"}
    assert got["lm_head"]["kernel"].shape == (256, 4 * 96)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-5,
                                   err_msg=jax.tree_util.keystr(path))
    for layer in ("h_0", "h_1"):
        assert np.any(got[layer]["attn"]["phi"])
        assert np.any(got[layer]["attn"]["mu"])


def test_c_the_trainers_step_takes_the_references_loss_down():
    """Through ``ShardedPretrainer``, the path the benchmark times: the first
    step's loss is the reference's on the same batch and weights (every
    ``g``, and so every norm's scale, at its start: 1), and the loss
    falls."""
    assert not np.any(toys.weights(TOY, by=0)[1]["h_0"]["attn_norm"]["scale"])
    want, losses, stats, *_ = toys.one_device(TOY, 2, 320, 10, lr=0.1)
    assert losses[0] == pytest.approx(want, rel=1e-4)
    assert losses[-1] < losses[0] - 0.5
    assert stats == {}


@pytest.mark.parametrize("wrong", evabyte.WRONG + (evabyte.PRECISION_BELOW,))
def test_c_the_tolerance_sees_each_wrong_model(wrong):
    """Each wrong model of the on-chip controls moves the toy's logits by far
    more than (c)'s tolerance — ``mu`` left out, mean pooling, the pooling's
    scale left out, a window's own summaries seen, chunk-by-chunk visibility,
    half the window, twice the chunk, the norms without their unit offset,
    RoPE left off, the residual rounded to bf16 — or, where only the
    objective is wrong (the later heads scoring the next token), the loss;
    and so does the reference itself with float8 activations."""
    got = toys.program(TOY, 320, attention_impl="reference")
    want = toys.reference(TOY, 320, wrong=wrong, attention_impl="reference")
    if wrong == "heads_next_byte":
        assert abs(float(want.loss) - float(got.loss)) \
            > 100 * 1e-5 * float(want.loss)
    else:
        assert float(jnp.max(jnp.abs(got.logits - want.logits))) \
            > 100 * 2e-4


# ------------------------------------------------------- (d) the objective
def test_d_head_r_scores_the_token_r_plus_one_ahead():
    """Against the sum written out; the mask is shifted with the targets, so
    a prefix mask gives the prefix's own loss; one head is ``lm_loss`` to the
    bit."""
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    b, s, heads, vocab = 2, 24, 4, 10
    # (128 columns, as the padded head gives them: the last 88 are padding)
    logits = jax.random.normal(keys[0], (b, s, 128), jnp.float32)
    ids = np.asarray(jax.random.randint(keys[1], (b, s), 0, vocab))
    targets = jnp.asarray(np.roll(ids, -1, axis=1))
    logp = np.asarray(jax.nn.log_softmax(
        logits[..., :heads * vocab].reshape(b, s, heads, vocab), axis=-1))

    def written_out(counted):
        terms = [-logp[row, t, r, int(targets[row, t + r])]
                 for row in range(b) for r in range(heads)
                 for t in range(s - r) if counted(row, t + r)]
        return np.mean(terms), len(terms)

    want, n = written_out(lambda row, at: True)
    assert n == b * sum(s - r for r in range(heads))
    assert float(shifted_heads_loss(logits, targets, None, heads, vocab)) \
        == pytest.approx(want, rel=1e-6)
    # a mask of the first 16 positions: head r counts t with t + r < 16, the
    # loss of the 16-position prefix on its own
    mask = jnp.asarray(np.arange(s) < 16, jnp.float32)[None].repeat(b, 0)
    want, n = written_out(lambda row, at: at < 16)
    assert n == b * sum(16 - r for r in range(heads))
    got = shifted_heads_loss(logits, targets, mask, heads, vocab)
    assert float(got) == pytest.approx(want, rel=1e-6)
    assert float(got) == pytest.approx(float(shifted_heads_loss(
        logits[:, :16], targets[:, :16], None, heads, vocab)), rel=1e-6)
    # weights that are no 0 / 1 mask weigh a term by the position it scores
    odd = mask.at[:, 3].set(0.0).at[0, 5].set(0.0)
    want, _ = written_out(lambda row, at: bool(odd[row, at]))
    assert float(shifted_heads_loss(logits, targets, odd, heads, vocab)) \
        == pytest.approx(want, rel=1e-6)
    # one head: today's loss, bit for bit, with a mask and without
    for m in (None, mask):
        assert np.asarray(shifted_heads_loss(logits, targets, m, 1, 128)
                          ).tobytes() == np.asarray(
                              lm_loss(logits, targets, m)).tobytes()


def test_d_the_new_fields_default_to_the_program_as_it_was():
    cfg = LlamaConfig()
    assert (cfg.eva_window, cfg.eva_chunk, cfg.norm_unit_offset,
            cfg.residual_dtype, cfg.logits_dtype, cfg.n_pred_heads) == (
                0, 0, False, None, None, 1)
    with pytest.raises(ValueError, match="4 prediction heads are an untied "
                       "head under the next-token objective"):
        jax.eval_shape(lambda: init_params(dataclasses.replace(
            LlamaConfig.tiny(), n_pred_heads=4, tie_embeddings=True))[1])


# ------------------------------------------------- (e) on a virtual mesh
@pytest.mark.parametrize("mesh", [{"dp": 1, "fsdp": 4}, {"dp": 2, "tp": 2}])
def test_e_a_sharded_mesh_gives_the_single_device_loss(mesh):
    """``phi`` and ``mu`` are cut by head under ``tp``, the head's columns as
    every head's are; the step under them — the kernels in a ``shard_map``,
    each device its own heads' summaries — gives one device's losses."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.parallel.sharding import (llama_partition_rules,
                                           match_partition_rules)

    specs = match_partition_rules(llama_partition_rules(),
                                  toys.weights(TOY)[1])
    attn = specs["h_0"]["attn"]
    assert attn["phi"] == attn["mu"] == P("tp", None)
    assert attn["wq"]["kernel"] == P("fsdp", "tp")
    assert specs["h_0"]["attn_norm"]["scale"] == P()
    assert specs["lm_head"]["kernel"] == P("fsdp", "tp")

    one = toys.one_device(TOY, 4, 320, 2, want=False)   # for both meshes
    many = ShardedPretrainer(toys.config(TOY), MeshConfig(**mesh),
                             devices=jax.devices()[:4])
    for want in one.losses:     # the second step sees the first's gradients
        assert float(many.step(one.rows)) == pytest.approx(want, rel=1e-5)
