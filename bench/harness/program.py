"""The system under test, as a configuration file asks for it: the
program's own configuration with the file's sizes and settings, the map
from the benchmark's weights to the program's parameters, and a check
that they fill the program's parameter tree."""
from __future__ import annotations

import re
from pathlib import Path

import jax

from harness import common


def program_config(conf: dict):
    """``repro`` ArchConfig for ``conf``: the registry's entry, every
    mapped field taken from the file, then the file's settings."""
    from repro.configs.registry import get_config
    prog = conf["program"]
    fields = {k: conf[v] for k, v in prog["fields"].items()}
    return get_config(prog["arch"]).with_(**fields, **prog.get("set", {}))


def weights_map(conf: dict):
    """``f(conf, weights) -> params``, from the configuration's
    ``program.weights`` file: the benchmark's weights laid out, and
    where need be rescaled, for the program to compute the configured
    model."""
    path = Path(conf["_file"]).parent / conf["program"]["weights"]
    return common.load_module(
        path, "bench_weights_" + re.sub(r"\W", "_", conf["name"])).weights


def seed_key(seed: int):
    """A PRNG key from any whole number, beyond 32 bits too."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                              seed // 2 ** 32)


def check_tree(weights, cfg) -> None:
    """The benchmark's weights have the program's tree, shapes and
    types, or the run stops here."""
    from repro.models import transformer as tf
    want = jax.eval_shape(lambda k: tf.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: weights)
    if jax.tree.structure(want) != jax.tree.structure(got):
        raise SystemExit("bench: the weight generator's tree is not the "
                         f"program's:\n{jax.tree.structure(got)}\nvs\n"
                         f"{jax.tree.structure(want)}")
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree.leaves(got)):
        if (w.shape, w.dtype) != (g.shape, g.dtype):
            raise SystemExit(f"bench: weight {jax.tree_util.keystr(path)} "
                             f"is {g.shape} {g.dtype}, the program wants "
                             f"{w.shape} {w.dtype}")
