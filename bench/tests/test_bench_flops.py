"""Model FLOPs per token against the hand counts of both configurations."""

import json

import pytest

from bench import flops
from bench.tests.tiny import BENCH


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name, params, gflop", [
    ("smollm-135m", 134_515_008, 1.232),
    ("olmo-1b-8l", 640_155_648, 4.244),
])
def test_flops_per_token_at_2048(name, params, gflop):
    cfg = config(name)
    assert flops.param_count(cfg) == params
    assert round(flops.train_flops_per_token(cfg, 2048) / 1e9, 3) == gflop


def test_vocabulary_is_counted_as_laid_out():
    assert flops.padded_vocab({"vocab_size": 50304}) == 50432
    assert flops.padded_vocab({"vocab_size": 49152}) == 49152


def test_param_count_matches_the_program_model():
    import jax

    from bench import program

    for name in ("smollm-135m", "olmo-1b-8l"):
        cfg = config(name)
        shapes = jax.eval_shape(program.model(cfg).init, jax.random.key(0))
        assert sum(x.size for x in jax.tree.leaves(shapes)) == flops.param_count(cfg)
