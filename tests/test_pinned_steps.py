"""Every toy's train step, lowered, is the program it was: sha256 of
``toys.step_text(name)`` — the step as the benchmark builds it (bf16, flash
attention, remat as the toy says), kernel bodies included, with
``flash_names_off`` — for every ``perfbench/tests/toy/toy-*.json`` that names
a model family.  A refactor of ``models/``, ``ops/`` or ``parallel/`` that
means to move no program is judged here before the chip is asked.  When a
hash moves on purpose: run the case, take the new hash from the assertion's
message, pin it, and say in ``CHANGES.md`` which toys moved and why (the
older hashes are in ``git log -p`` of this file).
"""

import glob
import hashlib
import os

import pytest

import toys

PINNED = {
    "toy-evabyte": "d18f329ed9775b3f3d4e4e7c933bc7c33972aed0d4cb5882bea59bcbbcadc56e",
    "toy-gpt2": "ba11271144fed4ca835af77562ca30fbb9989cac73bf82a195c1a4e4d5921693",
    "toy-granite": "4055866a1def3c74086a083c0f621efc1c2bee6c785cd361a06910e393a85613",
    "toy-kimi-linear": "5c0d97570c411cf0f592f071ab7dd6ff64b04db88fe014a145253f9c80ce579d",
    "toy-kimi-vl": "5a017906206e0d13c1f718b10c38f6a80c6893aafaa08d252665c2a7a745d148",
    "toy-laguna": "df60f397d9bdb65309344c8928dad00d38661abd054cc1ee31eeb5407ae51203",
    "toy-lfm2": "ad07a2bf3eae0808f1e15c23cd22a6c700ac2fbf8f402d9cf0bcc4b9ea0af5d6",
    "toy-llama": "3f093a50754c69664f39a2c72264ae3a12b3e0d67a722d3f326ae8d1ef0fb00e",
    "toy-nemotron-h": "81aec893b1adcc63a48c4d6117e42096e0c376ace04fd8ee2acaf406dd9e6ea4",
    "toy-olmoe": "41054e8f510af2a7d7be4f320326eb4237e4c1efc006d16bd81fbd3d9bea858f",
    "toy-phi4-flash": "79a8ea30f0ff39e594a039012999dd6e27df988b0ecb88a1d11bf6f31d7440d9",
    "toy-qwen3-next": "aa54b2aec115ec70d4155b9700fdd5c24d6c40bb142cffd1014f1d398259b780",
    "toy-sdar": "ff8e09d3e6a65ea9935059aba9f4c46ea2127d572f13434078f2b64b86576cb4",
    "toy-smallthinker": "93f22a8b8dd122f0ad365fda5333bd32f284eef760cd37790e1be2d4af71dd0e",
    "toy-xing4": "f9b88bdfcfc9f29b33f7db2d577c04e62fe12c4981e0dd873ec6e2f00cbc7054",
}


def test_every_toy_that_names_a_family_is_pinned():
    names = {os.path.basename(path)[:-len(".json")]
             for path in glob.glob(os.path.join(toys.TOY_DIR, "toy-*.json"))}
    assert {n for n in names if "family" in toys.toy(n)} == set(PINNED)


@pytest.mark.parametrize("name", sorted(PINNED))
def test_the_lowered_step_is_the_pinned_one(name, flash_names_off):
    text = toys.step_text(name)
    assert hashlib.sha256(text.encode()).hexdigest() == PINNED[name]
