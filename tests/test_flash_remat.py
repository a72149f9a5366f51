"""What ``remat_policy="full"`` keeps: a flash kernel's output and logsumexp,
named by the forward rules of ``ops/attention.py`` and saved by the blocks'
``jax.checkpoint`` policy (``models/gpt2.py::remat_block``), so that a
rematerialised block does not run its attention forward a second time.
(a) the op under a checkpoint, the four uses of it; (b) the model stacks:
which residuals a loss keeps, on one device and under ``fsdp``; (c) a Kimi
Delta Attention layer's chunk inverses, named by ``ops/kda.py``: the solve
runs once a layer, and the second forward reads what the first one's made.
The Pallas kernels are interpreted, on the CPU."""

import collections
import contextlib
import dataclasses
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax._src.ad_checkpoint import saved_residuals  # jax.ad_checkpoint has
# only print_saved_residuals, which prints this list

from ray_tpu.models.gpt2 import GPT2Config
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.models.pretrain import _model_family, init_params, loss_fn
from ray_tpu.ops.attention import FLASH_RESIDUALS, flash_attention
from ray_tpu.parallel.mesh import MeshConfig, build_mesh
from test_chip_compile import (  # the kernels' calls in a jaxpr
    _flash_fwd_calls, _kernel_calls)

KEEP = jax.checkpoint_policies.save_only_these_names(*FLASH_RESIDUALS)


# ------------------------------------------------------------ (a) the op
def _normal(seed, heads, seq, d):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, heads, seq, d),
                             jnp.float32)


_USES = {
    "causal": (dict(block_q=128, block_k=128),
               [(3, 256, 32), (3, 256, 32), (3, 256, 32)]),
    "window": (dict(window=100, block_q=128, block_k=128),
               [(3, 300, 32), (3, 300, 32), (3, 300, 32)]),
    "diffusion_block": (dict(causal=False, diffusion_block=4),
                        [(3, 2 * 192, 32)] * 3),
    # scores 24 + 8 wide over values 16 wide, the last 8 of a key shared
    "k_shared": (dict(block_q=128, block_k=128),
                 [(3, 256, 32), (3, 256, 24), (3, 256, 16), (1, 256, 8)]),
}


@pytest.mark.parametrize("use", sorted(_USES))
def test_a_a_checkpoint_with_the_names_runs_the_forward_once(use):
    """The op's gradients taken three ways: plainly, under ``jax.checkpoint``
    alone, and under the blocks' policy.  All three agree bit for bit; the
    gradient's jaxpr holds the forward kernel twice under a bare checkpoint
    (the parent's "full") and once under the policy, as without any
    checkpoint: the names do it, and nothing else about the checkpoint."""
    kwargs, shapes = _USES[use]
    operands = [_normal(i, *shape) for i, shape in enumerate(shapes)]
    g = _normal(9, 3, shapes[0][1], shapes[2][2])

    def block(q, k, v, k_shared=None):
        return flash_attention(q, k, v, k_shared=k_shared, **kwargs)

    def grads_of(f):
        grad = jax.grad(lambda *a: jnp.sum(f(*a) * g),
                        argnums=tuple(range(len(operands))))
        return jax.jit(grad)(*operands), _flash_fwd_calls(
            jax.make_jaxpr(grad)(*operands).jaxpr)

    plain, n_plain = grads_of(block)
    bare, n_bare = grads_of(jax.checkpoint(block))
    kept, n_kept = grads_of(jax.checkpoint(block, policy=KEEP))
    assert (n_plain, n_bare, n_kept) == (1, 2, 1)
    for name, a, b, c in zip(("q", "k", "v", "k_shared"), plain, bare, kept):
        assert float(jnp.max(jnp.abs(a))) > 0, name
        np.testing.assert_array_equal(a, b, err_msg="d" + name)
        np.testing.assert_array_equal(a, c, err_msg="d" + name)


def test_a_the_names_are_on_what_the_backward_reads():
    """The named arrays are the rule's own residuals, after the forward's
    slicing and reshaping: a checkpoint with the policy keeps the argument,
    ``out`` and ``lse`` at the caller's length, and nothing at the padded
    one; a bare checkpoint keeps the argument alone."""
    q = _normal(0, 2, 200, 32)      # 200: padded to a tile and sliced back

    def f(q):
        return jnp.sum(flash_attention(q, q, q))

    def kept(f):
        return sorted((aval.shape, "".join(re.findall(r"named '(\w+)'", why)))
                      for aval, why in saved_residuals(f, q))

    # (``out`` is also ``f``'s own value, so JAX hands it over through a
    # ``reduce_precision`` and the list reads that, not the name)
    assert kept(jax.checkpoint(f, policy=KEEP)) == [
        ((2, 2, 200, 32), ""), ((2, 2, 200, 32), ""),
        ((4, 1, 200), "flash_lse")]
    assert kept(jax.checkpoint(f)) == [((2, 2, 200, 32), "")]


# ------------------------------------------------------ (b) the stacks
def _llama(**fields):
    return dataclasses.replace(LlamaConfig.tiny(), n_layer=2, **fields)


def _hybrid(**fields):
    """A Mamba layer and then an attention layer."""
    return _llama(layer_types=("mamba", "attention"), mamba_n_heads=4,
                  mamba_d_head=16, mamba_d_state=16, mamba_chunk=32, **fields)


_STACKS = {
    "llama": (_llama, 2),
    "llama-reference": (lambda **f: _llama(**{"attention_impl": "reference",
                                              **f}), 0),
    "mamba-then-attention": (_hybrid, 1),
    "gpt2": (lambda **f: GPT2Config(vocab_size=512, n_positions=128,
                                    n_embd=64, n_layer=2, n_head=4, **f), 2),
}
_BATCH, _SEQ = 4, 128


@functools.lru_cache(maxsize=None)
def _params(stack):
    """A stack's parameters, which neither remat nor the attention's kind
    moves: made once, under jit (the forward ``init`` traces is dead code)."""
    return jax.jit(lambda: init_params(_STACKS[stack][0]())[1])()


def _kept(stack, mesh=None, gradients=True, **fields):
    """(loss, gradients, what the loss keeps for its backward: a count of
    each (shape, dtype), and the names ``saved_residuals`` read); without
    ``gradients`` nothing is run and the first two are None."""
    cfg = _STACKS[stack][0](**fields)
    model, params = _model_family(cfg)[0](cfg), _params(stack)
    batch = {k: jnp.asarray(v) for k, v in zip(
        ("input_ids", "targets"),
        np.random.default_rng(0).integers(0, 512, (2, _BATCH, _SEQ)))}

    def loss(params):
        return loss_fn(model, params, batch)

    def run():
        # (op by op: compiled as one program the bf16 roundings of a block
        # and of its recomputation fall differently, and the bits below)
        value, grads = jax.value_and_grad(loss)(params) if gradients \
            else (None, None)
        kept, names = collections.Counter(), collections.Counter()
        for aval, why in saved_residuals(loss, params):
            kept[aval.shape, str(aval.dtype)] += 1
            names.update(re.findall(r"named '(\w+)'", why))
        return value, grads, kept, names

    if mesh is None:
        return run()
    with jax.set_mesh(mesh):
        return run()


def _check(stack, mesh=None):
    build, attention_layers = _STACKS[stack]
    loss, grads, kept, names = _kept(stack, mesh, remat=True)
    want_loss, want_grads, *_ = _kept(stack, mesh, remat=False)
    # (the same arithmetic: the same kernels on the same operands)
    assert float(loss) == float(want_loss)
    jax.tree_util.tree_map(np.testing.assert_array_equal, grads, want_grads)
    # the same stack with XLA attention keeps its blocks' inputs and what
    # lies outside the blocks; the kernels add their two arrays a layer to
    # that and nothing else.  (Shapes are the whole batch's: under a mesh a
    # residual crosses the ``shard_map`` as the devices' rows together.)
    *_, plain, no_names = _kept(stack, mesh, gradients=False, remat=True,
                                attention_impl="reference")
    cfg = build()
    d_head = (cfg.n_embd if stack == "gpt2" else cfg.d_model) // cfg.n_head
    out = ((_BATCH, cfg.n_head, _SEQ, d_head), "bfloat16")
    lse = ((_BATCH * cfg.n_head, 1, _SEQ), "float32")
    assert kept - plain == {k: attention_layers for k in (out, lse)
                            if attention_layers}
    assert not plain - kept and not no_names
    # ``out`` is used again inside its block (the output projection), so JAX
    # hands it over through a ``reduce_precision`` of its own width and the
    # list reads that and not its name; inside a ``shard_map`` it reads
    # neither name
    assert names.pop("flash_lse", 0) == (0 if mesh else attention_layers)
    assert names.pop("flash_out", 0) in (0, attention_layers) and not names


@pytest.mark.parametrize("stack", sorted(_STACKS))
def test_b_a_rematerialised_stack_keeps_one_out_and_one_lse_a_layer(stack):
    """Two layers under ``remat=True``: the loss keeps, beside what it keeps
    anyway, exactly one ``flash_out`` and one ``flash_lse`` an attention
    layer — nothing for a Mamba layer, nothing under ``attention_impl=
    "reference"`` — and the loss and every gradient equal ``remat=False``'s
    bit for bit."""
    _check(stack)


def test_b_the_same_under_fsdp_where_the_op_is_inside_a_shard_map():
    """Four CPU devices, ``fsdp=4``: the names are inside ``flash_attention``'s
    ``shard_map`` and the policy finds them there."""
    mesh = build_mesh(MeshConfig(fsdp=4), devices=jax.devices()[:4])
    _check("llama", mesh)


@pytest.mark.parametrize("stack", ["llama", "gpt2"])
def test_b_the_gradient_of_a_stack_runs_each_layers_forward_once(stack):
    """The count the chip's ``flash_fwd_calls_per_step`` reads: ``n_layer``
    forward kernels in the gradient of the loss, with remat as without."""
    build, layers = _STACKS[stack]
    for remat in (True, False):
        cfg = build(remat=remat)
        model, params = _model_family(cfg)[0](cfg), _params(stack)
        batch = {k: jnp.zeros((2, _SEQ), jnp.int32)
                 for k in ("input_ids", "targets")}
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda p: loss_fn(model, p, batch)))(params)
        assert _flash_fwd_calls(jaxpr.jaxpr) == layers, remat


# -------------------------------------------- (c) the scan's chunk inverses
def _kda(**fields):
    """Two Kimi Delta Attention layers: four heads of 16, chunks of 32.
    In float32: in bf16 the CPU's fusions round a recomputed block's
    convolutions and norms otherwise than the forward's, under any policy."""
    return _llama(layer_types=("kda", "kda"), kda_n_heads=4, kda_head_dim=16,
                  kda_chunk=32, dtype=jnp.float32, **fields)


@functools.lru_cache(maxsize=None)
def _kda_params():
    # (once for all, and under jit: the layers ``init`` traces are dead code)
    return jax.jit(lambda: init_params(_kda())[1])()


def _kda_grad(cfg):
    """(the loss of two ``kda`` blocks, the parameters)."""
    model, params = _model_family(cfg)[0](cfg), _kda_params()
    batch = {k: jnp.asarray(v) for k, v in zip(
        ("input_ids", "targets"),
        np.random.default_rng(0).integers(0, 512, (2, _BATCH, _SEQ // 2)))}
    return (lambda p: loss_fn(model, p, batch)), params


def _kda_calls(mesh=None, **fields):
    """The scan's kernels in the gradient of two layers' loss, by name."""
    loss, params = _kda_grad(_kda(**fields))
    with jax.set_mesh(mesh) if mesh else contextlib.nullcontext():
        calls = _kernel_calls(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    return tuple(calls[k] for k in ("kda_solve", "kda_fwd", "kda_bwd"))


@pytest.mark.parametrize("mesh", [None, {"fsdp": 4}])
def test_c_a_rematerialised_kda_layer_solves_once_and_runs_its_forward_twice(
        mesh):
    """The gradient of two ``kda`` blocks holds, a layer: one ``kda_solve``,
    ``kda_fwd`` and ``kda_bwd`` without remat; one ``kda_solve``, two
    ``kda_fwd`` and one ``kda_bwd`` under ``remat_block(..., "full")`` — under
    the name the recomputation's solve is dead code —; two ``kda_solve``
    under ``"dots"``, whose policy has no names.  The same on four CPU
    devices, ``fsdp=4``, where the name is inside ``kda_scan``'s
    ``shard_map`` and the policy finds it there."""
    if mesh:
        mesh = build_mesh(MeshConfig(**mesh), devices=jax.devices()[:4])
    assert _kda_calls(mesh, remat=False) == (2, 2, 2)
    assert _kda_calls(mesh, remat=True) == (2, 4, 2)
    assert _kda_calls(mesh, remat=True, remat_policy="dots") == (4, 4, 2)


def test_c_the_kept_inverse_gives_the_gradients_of_a_stack_without_remat():
    """Bit for bit: the second forward and the backward read the matrix the
    first forward's solve made."""
    (loss, params), (plain, _) = (_kda_grad(_kda(remat=r))
                                  for r in (True, False))
    jax.tree_util.tree_map(np.testing.assert_array_equal,
                           jax.jit(jax.grad(loss))(params),
                           jax.jit(jax.grad(plain))(params))


def test_c_a_loss_keeps_one_inverse_a_layer_beside_the_blocks_inputs():
    """What two rematerialised ``kda`` blocks keep that the same blocks under
    a policy without names do not: each layer's chunk inverses, (batch, head
    blocks, seq, heads * chunk) float32.  (The inverse is used again inside
    its block, by ``kda_fwd``, so JAX hands it over through a
    ``reduce_precision`` and the list reads that and not the name, as for
    ``flash_out``.)"""
    def kept(**fields):
        cfg = _kda(remat=True, **fields)
        inverse = (_BATCH, 1, _SEQ // 2, cfg.kda_n_heads * cfg.kda_chunk)
        return sum(aval.shape == inverse and aval.dtype == jnp.float32
                   for aval, _ in saved_residuals(*_kda_grad(cfg)))

    assert (kept(), kept(remat_policy="dots")) == (2, 0)
