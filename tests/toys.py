"""How a test gets a toy model: the configurations under
``perfbench/tests/toy/`` (read, never edited), the program built from one and
the family's plain reference, each made once for the arguments it is asked
with and kept for the tests that ask again.  ``tests/README.md`` says which
kind of test shares which value.

A toy is named by its file (``"toy-laguna"``) or, where a test wants the same
layers cut another way, given as the file's dict with keys replaced
(``dict(toys.toy("toy-laguna"), num_experts=16)``); the caches count a dict
by its content.  Everything runs in float32 at matmul precision "highest"
unless ``dtype`` says otherwise, so that what is left between the program and
its reference is the order of the sums.
"""

import dataclasses
import functools
import inspect
import json
import math
import os
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from perfbench.harness import families
from perfbench.harness import reference as plain
from perfbench.harness.tokens import ZipfStream
from ray_tpu.models.pretrain import (_model_family, init_params, loss_fn,
                                     noise_blocks, objective_fn)

TOY_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench", "tests", "toy")


class Run(NamedTuple):
    """What a program, or its reference, makes of a batch."""
    logits: Any             # (rows, positions, the columns that score a token)
    loss: Any
    gradnorm: Any = None    # the gradient's global L2 norm
    grads: Any = None       # ... and its leaves, where they were asked for
    held: Any = None        # held experts' assignments, where the family counts


class Trained(NamedTuple):
    """Steps of ``ShardedPretrainer`` on one device."""
    want: Optional[float]   # the reference's loss at the first step's weights
    losses: list            # every step's loss
    stats: Dict[str, Any]   # the last step's MoE statistics
    rows: Dict[str, Any]    # the batch every step saw
    trainer: Any = None     # the trainer after them, for a test that steps on


def _frozen(value):
    return json.dumps(value, sort_keys=True, default=repr)


def _cached(fn):
    """``functools.lru_cache`` for arguments that may be dicts, and that count
    the same however they are handed over (a default left out or spelt)."""
    made = {}
    signature = inspect.signature(fn)

    @functools.wraps(fn)
    def cached(*args, **kwargs):
        bound = signature.bind(*args, **kwargs)
        bound.apply_defaults()
        key = _frozen(bound.arguments)
        if key not in made:
            made[key] = fn(*args, **kwargs)
        return made[key]

    return cached


@functools.lru_cache(maxsize=None)
def _read(name: str):
    with open(os.path.join(TOY_DIR, name + ".json")) as f:
        return json.load(f)


def toy(name) -> Dict[str, Any]:
    """The configuration file of a toy by its name, as a dict of its own; a
    dict is its own configuration."""
    return dict(name) if isinstance(name, dict) else dict(_read(name))


def config(name, chips=1, *, dtype=jnp.float32, **replace):
    """The program's dataclass for a toy as the benchmark fills it, in
    float32 (``dtype=None``: as published), with ``replace`` on top."""
    cfg = toy(name)
    if dtype is not None:
        replace["dtype"] = dtype
    filled = families.of(cfg).model_config(cfg, chips)
    return dataclasses.replace(filled, **replace) if replace else filled


def _shapes(replace):
    """``replace`` without what no leaf's shape or start depends on, and the
    plain reference does not read: the attention's kind and remat."""
    return {k: v for k, v in replace.items()
            if k not in ("attention_impl", "remat")}


@_cached
def _initial(name, chips, replace):
    # under jit the forward that ``init`` traces is dead code, not a hundred
    # programs run one by one; the values are the eager ones, bit for bit
    cfg = config(name, chips, **replace)
    return jax.jit(lambda: init_params(cfg)[1])()


def weights(name, chips=1, *, seed=1, by=0.1, **replace):
    """(model, parameters at ``PRNGKey(0)`` moved off their start by ``by``
    times a normal draw a leaf from ``seed``'s keys); ``by=0`` leaves them
    where ``init_params`` put them."""
    cfg = config(name, chips, **replace)
    return _model_family(cfg)[0](cfg), _moved(name, chips, seed, by,
                                              _shapes(replace))


def moved(params, seed=1, by=0.1):
    """``params`` with ``by`` times a normal draw added to every leaf, each
    from its own key of ``seed``'s: no norm's scale is 1 and no bias 0."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 1000))
    return jax.tree_util.tree_map(
        lambda a: a + by * jax.random.normal(next(keys), a.shape, a.dtype),
        params)


@_cached
def _moved(name, chips, seed, by, replace):
    params = _initial(name, chips, replace)
    return moved(params, seed, by) if by else params


@_cached
def rows(name, batch=2, positions=64, seed=5, chips=1):
    """A ``ZipfStream`` batch over the toy's vocabulary as device arrays; for
    a block-diffusion toy with ``x_t`` and ``weights`` under the noise of
    ``PRNGKey(3)``."""
    cfg = config(name, chips)
    out = {k: jnp.asarray(v) for k, v in ZipfStream(
        cfg.vocab_size, seed=seed).rows(batch, positions).items()}
    if getattr(cfg, "objective", "next_token") == "block_diffusion":
        x_t, _, weigh = noise_blocks(
            jax.random.PRNGKey(3), out["input_ids"], cfg.diffusion_block,
            cfg.mask_token_id, cfg.diffusion_t_min)
        out.update(x_t=x_t, weights=weigh)
    return out


def _columns(cfg):
    return getattr(cfg, "n_pred_heads", 1) * cfg.vocab_size


class _Tap:
    """A model whose last ``apply`` is kept: ``loss_fn`` applies it once, and
    the logits that made the loss are read back, not made a second time."""

    def __init__(self, model):
        self.model, self.config, self.out = model, model.config, None

    def apply(self, *args, **kwargs):
        self.out = self.model.apply(*args, **kwargs)
        return self.out


@_cached
def program(name, positions=64, *, batch=2, backward=True, chips=1, seed=1,
            by=0.1, rows_seed=5, **replace):
    """The program on ``rows(name, batch, positions, rows_seed)`` with
    ``weights(name, chips, seed=seed, by=by, **replace)``, jitted once:
    logits, loss and (``backward``) the gradient's leaves and norm."""
    model, params = weights(name, chips, seed=seed, by=by, **replace)
    data = rows(name, batch, positions, rows_seed, chips)
    diffusion = "x_t" in data

    def run(params, data):
        def loss_of(p):
            tap = _Tap(model)
            if diffusion:
                loss, stats = objective_fn(tap, p, data)[1]
                held = stats["moe_rows_held"]
            else:
                loss, held = loss_fn(tap, p, data), None
            logits = tap.out[0] if isinstance(tap.out, tuple) else tap.out
            return loss, (logits[..., :_columns(model.config)], held)

        if not backward:
            loss, (logits, held) = loss_of(params)
            return Run(logits, loss, held=held)
        (loss, (logits, held)), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params)
        return Run(logits, loss, plain.global_norm(grads), grads, held)

    with jax.default_matmul_precision("highest"):
        return jax.jit(run)(params, data)


def _cross_entropy(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()


def _forward_takes_wrong(family) -> bool:
    """Whether the family has a ``_forward(params, ids, config, wrong)``."""
    forward = getattr(family, "_forward", None)
    return forward is not None and list(
        inspect.signature(forward).parameters)[3:4] == ["wrong"]


def _plain_run(cfg, chips, columns, backward, leaves, wrong):
    """(params, batch) -> ``Run`` of the family's plain reference.  ``wrong``
    is one of the family's own names for a wrong model, or a dict: the keys
    that the family's ``logits`` takes as keywords go to it, the others
    replace the configuration's."""
    family = families.of(cfg)
    keywords = {}
    if isinstance(wrong, dict):
        takes = inspect.signature(family.logits).parameters
        keywords = {k: v for k, v in wrong.items() if k in takes}
        cfg = dict(cfg, **{k: v for k, v in wrong.items() if k not in takes})
        wrong = None
    named = {} if wrong is None else {"wrong": wrong}

    def whole(params, data):
        """The family's own logits, loss, gradient norm (and held rows)."""
        ids = data["input_ids"]
        if "x_t" in data:
            out = family.logits_loss_gradnorm(
                params, ids, data["x_t"], data["weights"], cfg, ids.size,
                chips, **named)
        elif hasattr(family, "logits_loss_gradnorm"):
            out = family.logits_loss_gradnorm(params, ids, data["targets"],
                                              cfg, **named)
        else:
            assert wrong is None and not keywords
            out = plain.logits_loss_gradnorm(params, ids, data["targets"],
                                             cfg)
        return Run(out[0], out[1], out[2],
                   held=out[3] if len(out) > 3 else None)

    def forward(params, data):
        """The forward alone; the loss is the mean cross entropy."""
        ids = data["input_ids"]
        if wrong is not None:
            out = family._forward(params, ids, cfg, wrong)
            logits = out[0] if isinstance(out, tuple) else out
        else:
            logits = family.logits(params, ids, cfg, **keywords)
        return logits[..., :columns]

    def loss_of(params, data):
        logits = forward(params, data)
        if hasattr(family, "heads_loss"):
            return family.heads_loss(logits, data["targets"], cfg), logits
        return _cross_entropy(logits, data["targets"]), logits

    def run(params, data):
        if leaves:
            (loss, logits), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params, data)
            return Run(logits, loss, plain.global_norm(grads), grads)
        if backward:
            return whole(params, data)
        if "x_t" in data or not (wrong is None
                                 or _forward_takes_wrong(family)):
            # no forward of the family's takes this: its whole function,
            # whose backward jit drops with the norm that is not returned
            return whole(params, data)._replace(gradnorm=None)
        if wrong is not None:       # (what it does to the loss is not here)
            return Run(forward(params, data), None)
        loss, logits = loss_of(params, data)
        return Run(logits, loss)

    return run


def reference(name, positions=64, *, batch=2, backward=True, leaves=False,
              wrong=None, chips=1, seed=1, by=0.1, rows_seed=5, **replace):
    """The same of the family's plain reference on the same rows and weights,
    jitted: with ``backward`` through the family's ``logits_loss_gradnorm``
    (the gradient's norm), with ``leaves`` by differentiating the cross
    entropy of its ``logits`` (every leaf), with neither the forward alone.
    The right model is kept; a ``wrong`` one is made for the asking."""
    args = (toy(name), positions, batch, backward, leaves, chips, seed, by,
            rows_seed, _shapes(replace))
    if wrong is None:
        return _right(*args)
    return _reference(*args, wrong)


def _reference(name, positions, batch, backward, leaves, chips, seed, by,
               rows_seed, replace, wrong=None):
    model, params = weights(name, chips, seed=seed, by=by, **replace)
    data = rows(name, batch, positions, rows_seed, chips)
    run = _plain_run(name, chips, _columns(model.config), backward, leaves,
                     wrong)
    with jax.default_matmul_precision("highest"):
        return jax.jit(run)(params, data)


_right = _cached(_reference)


@_cached
def one_device(name, batch, positions, steps, *, lr=3e-4, seed=5, chips=1,
               want=True, **replace):
    """``steps`` steps of ``ShardedPretrainer`` on one device over one
    ``ZipfStream`` batch, once for the tests that read them: the trainer-step
    test and the cases of the mesh test that compare with one device."""
    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig

    cfg = config(name, chips, **replace)
    trainer = ShardedPretrainer(cfg, MeshConfig(), devices=jax.devices()[:1],
                                lr=lr)
    data = ZipfStream(cfg.vocab_size, seed=seed).rows(batch, positions)
    plain_loss = None
    if want:
        run = _plain_run(toy(name), chips, _columns(cfg), False, False, None)
        with jax.default_matmul_precision("highest"):
            plain_loss = float(jax.jit(run)(trainer.state[0], {
                k: jnp.asarray(v) for k, v in data.items()}).loss)
    losses = [float(trainer.step(data)) for _ in range(steps)]
    return Trained(plain_loss, losses, dict(trainer.moe_stats), data, trainer)


def step_text(name, mesh=None) -> str:
    """The toy's train step as the benchmark builds it (as published: bf16,
    flash attention), on one device or on ``mesh`` (its axes' sizes), traced
    for two rows of 64 and lowered, kernel bodies included: what
    ``tests/test_pinned_steps.py`` takes the sha256 of."""
    from ray_tpu.models.pretrain import make_optimizer, sharded_train_step
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh

    axes = dict(mesh or {})
    mesh = build_mesh(MeshConfig(**axes),
                      devices=jax.devices()[:math.prod(axes.values())])
    s = sharded_train_step(config(name, dtype=None), mesh, make_optimizer())
    batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32, sharding=sh)
             for k, sh in s.batch_sharding.items()}
    with jax.set_mesh(mesh):
        return s.step.trace(s.state, batch).lower().as_text()
