"""The parts SDAR's block-diffusion training brought to the program, each
against something plain, at toy sizes on the CPU: the flash kernels' block
mask against the written-out boolean mask; a routed layer that holds a part of
its experts against ``perfbench/harness/families/sdar_moe.py::routed_part``
(the shares add up to the whole layer; nothing is dropped at either extreme
of the routing); the noising's statistics.  ``head_dim`` and the per-head q/k
norm, and every model the benchmark had before, unchanged, are
``tests/test_sdar_programs.py``'s.  The whole model against the whole
reference is ``tests/test_sdar.py``."""

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toys
from perfbench.harness.families import sdar_moe
from ray_tpu.models import moe
from ray_tpu.models.moe import RoutedConfig, RoutedSwiGLU, capacity_ladder
from ray_tpu.models.pretrain import noise_blocks
from ray_tpu.ops import attention
from ray_tpu.ops.attention import (block_diffusion_mask, flash_attention,
                                   mha_reference)

# 64 wide, 4 / 2 heads of 32 (not 64 / 4), 8 experts of 32 of which 4 are
# held, top-2 renormalised, 512 of 2048 vocabulary rows, blocks of 4
TOY = toys.toy("toy-sdar")


@pytest.mark.parametrize("length,block", [(160, 4), (160, 32), (1152, 32),
                                          (384, 4), (384, 128), (1152, 128),
                                          (160, 1), (1152, 1)])
def test_c_the_kernels_block_mask_is_the_written_out_mask(length, block):
    """The interpreted flash kernels under ``diffusion_block`` against plain
    attention under the boolean ``block_diffusion_mask``, forward and
    backward: at 160 a copy is one padded 256-tile, at 1152 four 256-tiles
    and a padded fifth with the diagonal taken chunk by chunk, at 384 three
    whole 128-tiles.  The noised blocks' own squares are one more step of
    the kernels' own softmax, a 128-chunk of positions against itself: a
    block of 128 fills a whole chunk (and at 384 a whole tile, whose clean
    diagonal tile is then dead), a block of 1 is a noised token that sees
    itself alone of its copy.  The first noised block sees no clean key at
    all: its rows are plain attention inside the block, and their logsumexp
    is finite."""
    mask = block_diffusion_mask(length, block)
    assert int(mask.sum()) == length * length + length * block
    assert bool(jnp.all(mask == sdar_moe.block_mask(length, block)))
    q, k, v = (jax.random.normal(key, (1, 2, 2 * length, 32), jnp.float32)
               for key in jax.random.split(jax.random.PRNGKey(length), 3))

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=False, diffusion_block=block)

    def plain(q, k, v):
        return mha_reference(q, k, v, mask=mask)

    out = kernel(q, k, v)
    np.testing.assert_allclose(out, plain(q, k, v), atol=5e-6)
    first = slice(0, block)
    np.testing.assert_allclose(
        out[:, :, first], mha_reference(
            q[:, :, first], k[:, :, first], v[:, :, first], causal=False),
        atol=5e-6)
    _, lse = attention._flash_forward(q, k, v, False, 32 ** -0.5, 0, 0, None,
                                      None, True, block)
    assert lse.shape == (2, 1, 2 * length)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        precision="highest") * 32 ** -0.5
    np.testing.assert_allclose(
        lse[:, 0], jax.nn.logsumexp(jnp.where(mask, scores, -jnp.inf),
                                    axis=-1)[0], atol=2e-5)
    g = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)
    for got, want in zip(jax.vjp(kernel, q, k, v)[1](g),
                         jax.vjp(plain, q, k, v)[1](g)):
        np.testing.assert_allclose(got, want, atol=3e-5)


# sha256 of str(jax.make_jaxpr(...)) of the causal call and of its vjp at
# (1, 2, 1152, 64) bf16 (256-tiles, the last one padded, the diagonal chunk
# by chunk), and of the unmasked call at (1, 2, 200, 64) float32 (padding
# keys), kernel bodies included (under a JAX that prints a jaxpr otherwise,
# take them again there).  PR 42's: the grid is (batch, heads, tiles, tiles)
# and the operands are addressed by batch and head, where it was (batch *
# heads, tiles, tiles) at the commit these stood for before (7ea4048); the
# bodies of a rank-4 call are what they were
_CAUSAL_AS_IT_WAS = {
    ("causal", "fwd"):
        "a7f6506ccfe8ddccbeddc13d306ca9587480ed4084edfc045a6ffb1f2a7bca1f",
    ("causal", "vjp"):
        "522e26a9d8f7d46b498b60780f1592ab0aae5137df802edf0b48f12946bfaabd",
    ("full", "fwd"):
        "9b67f0097cd93e0a93492621680edc864200dd16549b2577d9fac60c97afc4c6",
    ("full", "vjp"):
        "41f21073c429b9bf002ab85ab77d39ac581e6eb79979dc29fcb94f36e752b265",
}


def _eqns(jaxpr, inside_kernel=False):
    """(primitive name, whether inside a pallas_call) of every equation,
    sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, inside_kernel
        inner = inside_kernel or eqn.primitive.name == "pallas_call"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, inner)


@pytest.mark.parametrize("which", ["fwd", "vjp"])
def test_n_the_block_mask_attention_is_the_kernel_alone(which, request):
    """``flash_attention(..., diffusion_block=4)`` is one ``pallas_call`` and
    its vjp (the forward's rule and the backward's) two, and beside them only
    what lays the operands out and, in the backward, ``delta`` and the guard
    of the logsumexp: no softmax arithmetic, no matmul and no copy of the
    output (the noised blocks' own squares were a plain term merged by
    logsumexp until PR 34).  And the calls without the block mask trace to
    the pinned jaxpr, character for character."""
    def traced(f, x):
        if which == "fwd":
            return jax.make_jaxpr(f)(x, x, x)
        return jax.make_jaxpr(
            lambda q, k, v, g: jax.vjp(f, q, k, v)[1](g))(x, x, x, x)

    x = jax.ShapeDtypeStruct((2, 4, 2 * 384, 32), jnp.bfloat16)
    names = list(_eqns(traced(lambda q, k, v: flash_attention(
        q, k, v, causal=False, diffusion_block=4), x).jaxpr))
    assert [n for n, _ in names].count("pallas_call") \
        == {"fwd": 1, "vjp": 2}[which]
    # (``name``: the forward rule's output and logsumexp under the names a
    # block's checkpoint keeps them by, ``ops.attention.FLASH_RESIDUALS``)
    layout = {"custom_vjp_call", "jit", "pallas_call", "reshape", "pad",
              "slice", "convert_element_type", "name"}
    delta_and_guard = {"mul", "reduce_sum", "gt", "select_n",
                       "broadcast_in_dim"}
    assert {n for n, inside in names if not inside} \
        <= layout | (delta_and_guard if which == "vjp" else set())
    # (since PR 38 the forward rules name their output and logsumexp; with
    # the names taken off, the vjp is the parent's too)
    request.getfixturevalue("flash_names_off")
    for kind, x in (("causal", jax.ShapeDtypeStruct((1, 2, 1152, 64),
                                                    jnp.bfloat16)),
                    ("full", jax.ShapeDtypeStruct((1, 2, 200, 64),
                                                  jnp.float32))):
        text = str(traced(lambda q, k, v: flash_attention(
            q, k, v, causal=kind == "causal"), x))
        assert hashlib.sha256(text.encode()).hexdigest() \
            == _CAUSAL_AS_IT_WAS[kind, which], (kind, which)


def _layer(held, n_experts=8, k=2):
    return RoutedSwiGLU(RoutedConfig(
        n_experts=n_experts, top_k=k, d_model=64, d_ff=32,
        norm_topk_prob=True, dtype=jnp.float32, experts_held=held))


@functools.lru_cache(maxsize=None)
def _whole_layer_params():
    """(inputs, a whole layer's parameters), made once: nobody writes into
    them (under jit the forward that ``init`` traces is dead code)."""
    layer = _layer(None)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 44, 64), jnp.float32)
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)["params"]
    return x, dict(params, router={"kernel": 2.0 * jax.random.normal(
        jax.random.PRNGKey(3), (64, 8))})


def _share(params, first, count):
    return dict(params, **{name: params[name][first:first + count]
                           for name in ("gate_proj", "up_proj", "down_proj")})


def test_d_the_shares_of_a_routed_layer_add_up_to_the_whole_layer():
    """The tie the model-configs guide asks for: four chips that hold two of
    the eight experts each, every one routing over all eight and
    renormalising over its tokens' whole top-2, give parts that sum to what
    the uncut reference (and the uncut program) gives for the whole layer;
    and their held rows sum to every assignment."""
    x, params = _whole_layer_params()
    config = dict(TOY, num_experts=8)
    def plain(first):       # (jitted: op by op a layer is a hundred programs)
        return jax.jit(lambda p, x: sdar_moe.routed_part(
            x, p, config, first)[0])

    with jax.default_matmul_precision("highest"):
        whole = plain(0)(params, x)
        np.testing.assert_allclose(jax.jit(lambda p, x: _layer(None).apply(
            {"params": p}, x))(params, x), whole, atol=2e-5)
        parts, rows = [], 0.0
        for first in range(0, 8, 2):
            share = _share(params, first, 2)
            part, sown = jax.jit(lambda p, x: _layer((first, 2)).apply(
                {"params": p}, x, mutable=["intermediates"]))(share, x)
            np.testing.assert_allclose(part, plain(first)(share, x),
                                       atol=2e-5)
            parts.append(part)
            rows += float(sown["intermediates"]["moe_rows_held"][0])
    np.testing.assert_allclose(sum(parts), whole, atol=5e-5)
    assert rows == 2 * 44 * 2
    assert float(jnp.max(jnp.abs(parts[0]))) > 1e-3     # a part is not nothing


@pytest.mark.parametrize("favoured,rows_held", [(0, 2 * 44 * 2), (4, 0)])
def test_e_no_token_is_dropped_at_either_extreme(favoured, rows_held):
    """A router that sends every token to experts ``favoured`` and
    ``favoured + 1``: with experts 0-3 held that is every assignment (the
    buffer's worst case, full) or none of them (empty; the layer's part is
    exactly zero).  Both equal the reference, and both are one program:
    the jaxpr does not depend on the routing."""
    x, params = _whole_layer_params()
    router = jnp.zeros((64, 8)).at[:, favoured:favoured + 2].set(1.0)
    x = jnp.abs(x)      # so that the favoured logits are the largest
    share = _share(dict(params, router={"kernel": router}), 0, 4)
    layer, config = _layer((0, 4)), dict(TOY, num_experts=8)

    def part(p, x):
        return layer.apply({"params": p}, x, mutable=["intermediates"])

    with jax.default_matmul_precision("highest"):
        got, sown = jax.jit(part)(share, x)
        want = jax.jit(lambda p: sdar_moe.routed_part(x, p, config, 0)[0])(
            share)
        grads = jax.jit(jax.grad(lambda p: jnp.sum(jnp.sin(
            part(p, x)[0]))))(share)
        want_grads = jax.jit(jax.grad(lambda p: jnp.sum(jnp.sin(
            sdar_moe.routed_part(x, p, config, 0)[0]))))(share)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(sown["intermediates"]["moe_rows_held"][0]) == rows_held
    if not rows_held:
        assert float(jnp.max(jnp.abs(got))) == 0.0
    for name in ("gate_proj", "up_proj", "down_proj"):
        np.testing.assert_allclose(grads[name], want_grads[name], atol=1e-4)
        assert bool(jnp.all(jnp.isfinite(grads[name])))
    other = _share(params, 0, 4)
    assert str(jax.make_jaxpr(part)(share, x)) == \
        str(jax.make_jaxpr(part)(other, x))


def _routed_to_held(n):
    """Inputs and a share of the toy layer (experts 0-1 of 8 held, top-2 over
    2 x 44 tokens: 176 assignments) whose router sends exactly ``n``
    assignments to held experts: channel 2 flags the ``n // 2`` tokens that
    go to experts 0 and 1, channel 1 the ``n % 2`` that go to 0 and 4,
    channel 0 the rest, which go to 4 and 5."""
    x, params = _whole_layer_params()
    both, one = n // 2, n % 2
    kind = jnp.where(jnp.arange(88) < both, 2,
                     jnp.where(jnp.arange(88) < both + one, 1, 0))
    x = jnp.abs(x).at[..., :3].set(
        8.0 * jax.nn.one_hot(kind, 3).reshape(2, 44, 3))
    router = jnp.zeros((64, 8)).at[0, 4:6].set(1.0).at[1, (0, 4)].set(
        1.0).at[2, 0:2].set(1.0)
    return x, _share(dict(params, router={"kernel": router}), 0, 2)


# a share of 44 where the toy's balance is 88: four rungs, so that the
# loop's second and later trips are walked too
_LADDER = capacity_ladder(176, 2, 8)


def test_j_the_ladder_is_the_configurations_own():
    """The balance share and its multiples up to the whole buffer: 16,384 to
    131,072 in eight rungs at the cell's shapes; small shares are rounded up
    to whole row tiles of the grouped matmul, and the last rung holds every
    assignment whatever the rounding."""
    assert capacity_ladder(16384 * 8, 16, 128) == tuple(
        16384 * i for i in range(1, 9))
    assert _LADDER == (48, 96, 144, 192)
    assert capacity_ladder(8 * 8, 16, 128) == (8,) * 0 + tuple(
        range(8, 65, 8))
    assert capacity_ladder(4, 1, 128) == (8,)
    for rows, held, experts in ((176, 4, 8), (100, 3, 7), (4096, 16, 128)):
        ladder = capacity_ladder(rows, held, experts)
        assert ladder[-1] >= rows > ladder[-1] - ladder[0]


@pytest.mark.parametrize("n", [0, 1, _LADDER[0] - 1, _LADDER[0],
                               _LADDER[0] + 1, _LADDER[1], 176])
def test_k_every_edge_of_the_ladder_is_the_reference(n):
    """Exactly ``n`` assignments to the held experts, around every capacity:
    the part and the gradients of ``x`` and of the three matrices equal the
    reference's, the counter is ``n``, the layer runs at the smallest rung
    that holds ``n`` (no piece at all for no row) — and it is one program for
    every ``n``."""
    x, share = _routed_to_held(n)
    layer, config = _layer((0, 2)), dict(TOY, num_experts=8)

    def part(p, x):
        return layer.apply({"params": p}, x, mutable=["intermediates"])

    def loss(of):
        return lambda p, x: jnp.sum(jnp.sin(of(p, x)))

    with jax.default_matmul_precision("highest"):
        got, sown = jax.jit(part)(share, x)
        want = jax.jit(lambda p, x: sdar_moe.routed_part(
            x, p, config, 0)[0])(share, x)
        grads = jax.jit(jax.grad(loss(lambda p, x: part(p, x)[0]), (0, 1)))(
            share, x)
        want_grads = jax.jit(jax.grad(loss(lambda p, x: sdar_moe.routed_part(
            x, p, config, 0)[0]), (0, 1)))(share, x)
    np.testing.assert_allclose(got, want, atol=2e-5)
    sown = sown["intermediates"]
    assert float(sown["moe_rows_held"][0]) == n
    assert float(sown["moe_buffer_rows"][0]) == min(
        c for c in (0,) + _LADDER if c >= n)
    for name in ("gate_proj", "up_proj", "down_proj", "router"):
        # (the flagged channels make some sums hundreds large: rtol)
        np.testing.assert_allclose(
            *(jax.tree.leaves(g[0][name]) for g in (grads, want_grads)),
            atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(grads[1], want_grads[1], atol=1e-4, rtol=1e-5)
    assert str(jax.make_jaxpr(part)(share, x)) == \
        str(jax.make_jaxpr(part)(*_routed_to_held(7)[::-1]))


def _walk(jaxpr, found, in_loop=False):
    """Every equation of ``jaxpr`` and of the jaxprs inside it (not inside a
    kernel): ``found`` gets, for each ``while`` with a trip count found on
    the device, the Mosaic calls of its body (``loops``), the ``cond``s
    (``switches``), and the primitives that give what ``_buffer_wide`` names
    (``wide``), wherever they stand."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if any(_buffer_wide(v.aval) for v in eqn.outvars):
            found["wide"].append(name)
        if name == "pallas_call":
            if in_loop:
                found["loops"][-1] += 1
            continue
        if name == "cond":
            found["switches"].append(len(eqn.params["branches"]))
        if name == "while":
            found["loops"].append(0)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _walk(getattr(sub, "jaxpr", sub), found, in_loop or name == "while")


def _buffer_wide(aval, rows=176, k=2, widths=(64, 32)):
    """A float array with a row for every one of the ``T * k`` assignments,
    as wide as the model or as an expert."""
    shape = getattr(aval, "shape", ())
    return (shape[:1] == (rows,) or shape[:2] == (rows // k, k)) \
        and shape[-1] in widths and len(shape) > 1 \
        and jnp.issubdtype(aval.dtype, jnp.floating)


def test_l_no_row_is_moved_at_the_worst_cases_size():
    """The mechanism, not its speed: in the held layer's ``value_and_grad``
    no array as wide as the model or an expert has a row for every
    assignment, anywhere; whatever is that wide is inside a loop whose trip
    count the device finds, at one piece's rows: the forward's loop with the
    three grouped matmuls and the kernel that adds their rows into the
    tokens, and the backward's with the three recomputed, their six
    transposes and the same kernel for the tokens' gradients — and no third:
    where the checkpoint recomputes the forward nothing reads the rule's
    result, and its loop is not there.  A layer that holds every expert has
    no loop and does pass over all ``T * k`` rows."""
    x, share = _routed_to_held(7)
    layer = _layer((0, 2))

    def loss(p, x):
        return jnp.sum(jnp.sin(jax.checkpoint(
            lambda p, x: layer.apply({"params": p}, x))(p, x)))

    found = {"wide": [], "switches": [], "loops": []}
    _walk(jax.make_jaxpr(jax.value_and_grad(loss, (0, 1)))(share, x).jaxpr,
          found)
    assert found["wide"] == [] and found["switches"] == [], found
    assert found["loops"] == [3 + 1, 9 + 1], found
    whole = {"wide": [], "switches": [], "loops": []}
    _walk(jax.make_jaxpr(jax.value_and_grad(lambda p, x: jnp.sum(
        _layer(None).apply({"params": p}, x))))(
            _whole_layer_params()[1], x).jaxpr, whole)
    assert whole["loops"] == [] and whole["wide"]    # the walk does see


# ---------------------- (o) a piece's rows added into their tokens (PR 36)
# 512 tokens x 4 slots over 3 held experts, pieces of 384 rows: four tiles of
# 128 tokens and three blocks of 128 rows, so that a run of rows crosses
# both.  A case is the (token, slot) assignments that go to a held expert.
_ONTO = {
    "a_no_live_row": [],
    "b_one_row": [(301, 2)],
    "c_every_slot_of_a_token": [(200, s) for s in range(4)] + [(3, 1),
                                                               (450, 0)],
    "d_a_tokens_rows_in_two_pieces": [(t, s) for t in range(128)
                                      for s in range(4)],
    "e_rows_end_inside_a_tile": [(37 * i % 512, i % 4) for i in range(150)],
    "e_a_piece_exactly_full": [(5 * t, s) for t in range(96)
                               for s in range(4)],
}


@pytest.mark.parametrize("gated", [True, False], ids=["gates", "plain"])
@pytest.mark.parametrize("case", sorted(_ONTO))
def test_o_a_pieces_rows_added_into_their_tokens_are_the_scatter_add(
        case, gated):
    """``_onto_tokens`` — the rows brought into token order, then the
    (interpreted) kernel over tiles of tokens, ``acc`` updated in place —
    against ``acc.at[tokens].add(rows * gates)``, piece after piece as the
    loop runs them: bf16 rows as the grouped matmul writes them, what lies
    past a piece's live rows never written (NaN here), the forward's gates
    and the backward's plain sum."""
    n_tokens, k, n_held, c, d = 512, 4, 3, 384, 128
    flat = np.full((n_tokens * k,), n_held, np.int32)
    for token, slot in _ONTO[case]:
        flat[token * k + slot] = (token + slot) % n_held
    route = moe._route(jnp.asarray(flat), n_held, n_tokens * k)
    n_pieces = int(moe._n_pieces(route, c))
    assert n_pieces == -(-len(_ONTO[case]) // c)
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    got = want = jax.random.normal(keys[0], (n_tokens, d), jnp.float32)
    gates = jax.random.uniform(keys[1], (n_tokens * k,), jnp.float32)
    seen = 0
    for i in range(max(n_pieces, 1)):   # (no row: the one, empty piece)
        piece = moe._piece(route, i, c)
        live = np.asarray(piece.live)
        rows = jnp.where(live[:, None], jax.random.normal(
            jax.random.fold_in(keys[2], i), (c, d), jnp.float32),
            jnp.nan).astype(jnp.bfloat16)
        got = moe._onto_tokens(got, rows, gates if gated else None, piece, k)
        want = want.at[piece.slots // k].add(jnp.where(
            live[:, None], rows.astype(jnp.float32)
            * (gates[piece.slots][:, None] if gated else 1.0), 0))
        seen += int(live.sum())
    assert seen == len(_ONTO[case])
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
    if not _ONTO[case]:
        assert bool(jnp.all(got == want))    # nothing added: not a bit moved
    if case.startswith("d"):    # the last token's rows: experts 1, 2, 0, 1
        assert len({int(r) // c
                    for r in np.asarray(route.row[127 * k:128 * k])}) > 1


def test_p_a_held_layers_gradients_are_the_whole_layers_with_absent_gates_zero():
    """``jax.grad`` of ``held_experts`` (the loop over pieces, the kernel
    that adds rows into tokens in both directions) against ``routed_experts``
    over all the experts with the absent ones' gate weights set to zero: the
    tokens', the gate weights' and the held matrices' gradients; and in the
    held layer's forward and backward no scatter-add of rows as wide as the
    model is left, inside the loops or outside them."""
    n_experts, n_held, k, d, f = 8, 3, 2, 64, 32
    keys = jax.random.split(jax.random.PRNGKey(36), 6)
    x = jax.random.normal(keys[0], (2, 44, d), jnp.float32)
    idx = jax.lax.top_k(jax.random.normal(keys[1], (2, 44, n_experts)), k)[1]
    weights = jax.random.uniform(keys[2], (2, 44, k), jnp.float32)
    mats = tuple(0.2 * jax.random.normal(key, (n_experts,) + shape)
                 for key, shape in zip(keys[3:], ((d, f), (d, f), (f, d))))
    cfg = RoutedConfig(n_experts=n_experts, top_k=k, d_model=d, d_ff=f,
                       dtype=jnp.float32)
    held_cfg = dataclasses.replace(cfg, experts_held=(0, n_held))

    def held(x, weights, mine):
        return jnp.sum(jnp.sin(moe.held_experts(
            x, weights, idx, mine, held_cfg)[0]))

    def whole(x, weights, mine):
        return jnp.sum(jnp.sin(moe.routed_experts(
            x, jnp.where(idx < n_held, weights, 0.0), idx,
            tuple(jnp.concatenate([m, rest[n_held:]]) for m, rest
                  in zip(mine, mats)), cfg)))

    mine = tuple(m[:n_held] for m in mats)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(held, (0, 1, 2)))(x, weights, mine)
        want = jax.jit(jax.grad(whole, (0, 1, 2)))(x, weights, mine)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=1e-5)
    assert float(jnp.max(jnp.abs(got[0]))) > 1e-3

    def scatter_adds(jaxpr, out):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                continue
            if eqn.primitive.name in ("scatter-add", "scatter_add"):
                out.append(eqn.outvars[0].aval.shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                scatter_adds(getattr(sub, "jaxpr", sub), out)
        return out

    shapes = scatter_adds(jax.make_jaxpr(jax.value_and_grad(
        held, (0, 1, 2)))(x, weights, mine).jaxpr, [])
    # (the gates' gradient is one, over scalars; the grouped matmuls' own
    # bookkeeping has more)
    assert (2 * 44 * k,) in shapes and all(len(s) == 1 for s in shapes), shapes
    assert scatter_adds(jax.make_jaxpr(lambda acc, rows, at: acc.at[at].add(
        rows))(x[0], x[0], jnp.arange(44)).jaxpr, []) == [(44, d)]


def test_f_the_noising_masks_a_share_t_of_each_block_and_weighs_by_1_over_t():
    """Blocks of 32 over 64 rows of 4096: every block's weights are 0 or one
    value 1/t with t in (t_min, 1]; over the blocks the masked share follows
    t (a block of 32 at level t masks 32 t on average); masked positions hold
    the mask id and the others their token; the same key gives the same
    noise, another key another."""
    ids = jax.random.randint(jax.random.PRNGKey(0), (64, 4096), 0, 500)
    key = jax.random.PRNGKey(11)
    x_t, masked, weights = noise_blocks(key, ids, 32, 511, 0.001)
    assert bool(jnp.all(jnp.where(masked, x_t == 511, x_t == ids)))
    w = np.asarray(weights).reshape(64, 128, 32)
    m = np.asarray(masked).reshape(64, 128, 32)
    level = 1.0 / w.max(axis=-1, where=m, initial=1e-9)    # a block's t
    some = m.any(axis=-1)
    assert np.all(w[m] >= 1.0) and np.all(w[~m] == 0.0)
    assert np.all(np.where(m, w, w.max(-1, keepdims=True))
                  == w.max(-1, keepdims=True))      # one weight a block
    t = level[some]
    assert 0.001 < t.min() and t.max() <= 1.0
    assert abs(t.mean() - 0.5) < 0.02               # uniform over (t_min, 1]
    share = m.mean(axis=-1)[some]
    assert abs(np.mean(share - t)) < 0.005          # masked share = t
    assert np.corrcoef(share, t)[0, 1] > 0.95
    # E[sum of a block's weights] is its length: the loss is a mean nll
    assert abs(w.sum(-1).mean() / 32 - 1.0) < 0.05
    again = noise_blocks(key, ids, 32, 511, 0.001)
    assert all(bool(jnp.all(a == b)) for a, b in zip(again,
                                                     (x_t, masked, weights)))
    other = noise_blocks(jax.random.fold_in(key, 1), ids, 32, 511, 0.001)
    assert float(jnp.mean(other[1] != masked)) > 0.2
