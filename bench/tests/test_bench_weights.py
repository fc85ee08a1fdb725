"""The program, given the benchmark's weights through the configuration's
weight map, computes the configured model: granite's multipliers and tied
head folded into the weights, checked in float32 at a reduced size on
the program's plain (non-kernel) path against the reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import common, program

CONFIGS = common.BENCH / "configs"


def _reduced(tied=False):
    conf = {**common.load_json(CONFIGS / "granite-moe-1b-a400m.json"),
            "_file": str(CONFIGS / "granite-moe-1b-a400m.json")}
    conf.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
                num_key_value_heads=2, intermediate_size=32,
                num_local_experts=8, num_experts_per_tok=2, vocab_size=256,
                torch_dtype="float32", tie_word_embeddings=tied)
    conf["program"] = {**conf["program"],
                       "set": {**conf["program"]["set"],
                               "use_pallas_kernels": False}}
    ref = common.load_module(CONFIGS / conf["reference"], "ref_granite")
    return conf, ref


def _logits(conf, ref, to_program):
    from repro.models import transformer as tf
    cfg = program.program_config(conf)
    w = ref.init_weights(conf, jax.random.PRNGKey(3), jnp.float32)
    params = to_program(conf, w)
    program.check_tree(params, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 24), 0,
                                conf["vocab_size"])
    got, _, _ = tf.forward(params, tokens, cfg)
    want = ref.logits(conf, w, tokens[0])
    return np.asarray(got[0], np.float32), np.asarray(want)


@pytest.mark.parametrize("tied", [False, True])
def test_folded_weights_make_the_program_compute_the_reference(tied):
    conf, ref = _reduced(tied)
    got, want = _logits(conf, ref, program.weights_map(conf))
    assert conf["embedding_multiplier"] != 1
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert err < 1e-4, err


def test_without_the_fold_the_program_computes_another_model():
    conf, ref = _reduced()
    got, want = _logits(conf, ref, lambda c, w: w)
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert err > 0.1, err
