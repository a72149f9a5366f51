"""``tokens.ZipfStream``: what ``--seed`` makes, and what it leaves alone
where a traffic file gives ``window_ids_seed`` (PR 67: a cell whose work
follows from the ids gets the same ids for every seed)."""

import glob
import json
import os

import numpy as np
import pytest

from perfbench.harness import manifest
from perfbench.harness.tokens import ZipfStream

SEEDS = (5, 2 ** 31 + 9, 2167100011)
KINDS_THAT_READ_THE_KEY = ("bd_train_loop", "train_loop")


def _steps(stream, rows=4, seq=32, n=6):
    batches = stream.batches(rows, seq)
    return [next(batches) for _ in range(n)]


@pytest.mark.parametrize("seed", SEEDS)
def test_without_the_key_every_id_is_the_seeds(seed):
    """The stream of every cell whose traffic file has no such key: the
    check's rows and then a fresh batch a step, all from one generator."""
    stream, rng = ZipfStream(1000, seed), np.random.default_rng(seed)
    weights = 1.0 / np.arange(1, 1001, dtype=np.float64)
    cdf = np.cumsum(weights / weights.sum())
    cdf[-1] = 1.0
    for got in [stream.rows(1, 32)] + _steps(stream):
        want = np.searchsorted(cdf, rng.random(got["input_ids"].shape),
                               side="right")
        assert np.array_equal(got["input_ids"], want)
        assert np.array_equal(got["targets"], np.roll(want, -1, axis=1))


def test_a_stream_of_its_own_kind_keeps_its_own_rows_in_its_steps():
    """``HeldZipfStream`` makes its runs in ``rows``: a step of it is held
    too, and is what ``rows`` gives from the same generator."""
    from perfbench.harness.held_tokens import HeldZipfStream

    a, b = (HeldZipfStream(1000, SEEDS[2], hold=0.75) for _ in "ab")
    for step in _steps(a, rows=2, seq=4096, n=2):
        ids = step["input_ids"]
        assert np.array_equal(ids, b.rows(2, 4096)["input_ids"])
        assert np.mean(ids[:, 1:] == ids[:, :-1]) > 0.7


def test_with_the_key_every_seed_gets_the_same_steps():
    runs = {}
    for seed in SEEDS:
        stream = ZipfStream(1000, seed, window_ids_seed=67)
        runs[seed] = (stream.rows(1, 32), _steps(stream))
    first_check, first = runs[SEEDS[0]]
    for seed in SEEDS[1:]:
        check, steps = runs[seed]
        # the reference check's sequence is the seed's own
        assert not np.array_equal(check["input_ids"],
                                  first_check["input_ids"])
        # a step holds the same rows in the same order whatever the seed
        for a, b in zip(steps, first):
            assert np.array_equal(a["input_ids"], b["input_ids"])
            assert np.array_equal(a["targets"],
                                  np.roll(a["input_ids"], -1, axis=1))
    # a step's rows differ from the last step's: the stream goes on
    assert not np.array_equal(first[0]["input_ids"], first[1]["input_ids"])
    # and they are not the rows of the stream whose seed is that number
    assert not np.array_equal(first[0]["input_ids"],
                              _steps(ZipfStream(1000, 5))[0]["input_ids"])


def test_what_the_check_draws_does_not_move_the_steps():
    """The reference check takes a row a replica from the seed before the
    first step: under the key the steps are the same after one row or four."""
    a, b = (ZipfStream(1000, 5, window_ids_seed=67) for _ in "ab")
    a.rows(1, 32), b.rows(4, 32)
    for x, y in zip(_steps(a), _steps(b)):
        assert np.array_equal(x["input_ids"], y["input_ids"])


@pytest.mark.parametrize("key", (None, 67))
def test_the_same_seed_gives_the_same_inputs(key):
    a, b = (ZipfStream(1000, SEEDS[1], window_ids_seed=key) for _ in "ab")
    assert np.array_equal(a.rows(2, 16)["input_ids"],
                          b.rows(2, 16)["input_ids"])
    for x, y in zip(_steps(a), _steps(b)):
        assert np.array_equal(x["input_ids"], y["input_ids"])


def test_the_ids_follow_the_zipf_law_under_the_key():
    ids = np.concatenate([b["input_ids"].ravel() for b in _steps(
        ZipfStream(1000, 3, window_ids_seed=67), rows=8, seq=4096, n=4)])
    share = np.mean(ids == 0)
    assert share == pytest.approx(1.0 / np.sum(1.0 / np.arange(1, 1001)),
                                  rel=0.05)


def test_the_cells_that_give_the_key():
    """The key stands in the traffic files of the cells whose kind reads it
    and nowhere else: a file that gave it to a kind that does not read it
    would say something of its cell that is not so."""
    with_key = set()
    for path in glob.glob(os.path.join(manifest.BENCH_DIR, "traffic",
                                       "*.json")):
        with open(path) as f:
            traffic = json.load(f)
        if "window_ids_seed" in traffic:
            assert traffic["kind"] in KINDS_THAT_READ_THE_KEY, path
            assert isinstance(traffic["window_ids_seed"], int)
            assert traffic["window_ids_why"]
            with_key.add(os.path.basename(path))
    assert with_key == {"bd-s4k-b2-gen.json", "s16k-b1-gen.json"}


@pytest.mark.parametrize("kind", KINDS_THAT_READ_THE_KEY)
def test_the_kind_hands_the_key_to_its_stream(kind):
    """PR 71, after the check's refusal over ``smallthinker-s16k-1chip``:
    ``train_loop`` reads the key as ``bd_train_loop`` has since PR 67."""
    import importlib
    import inspect

    loop = importlib.import_module(f"perfbench.harness.kinds.{kind}").loop
    assert 'window_ids_seed=traffic.get("window_ids_seed")' \
        in inspect.getsource(loop)
