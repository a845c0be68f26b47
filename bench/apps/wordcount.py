"""WordCount: word ids drawn uniformly from a fixed vocabulary, as Hadoop's
RandomTextWriter (HiBench WordCount's data generator) draws its words;
count per word.

The generator and the reference are the benchmark's own, so the
yardstick does not move when the program does.
"""

from __future__ import annotations


def validate(config: dict, job: dict) -> None:
    if config["tokens"] < job["num_mappers"]:
        raise ValueError("fewer tokens than map tasks")


def make_app(config: dict):
    """The program's WordCount over this configuration's vocabulary."""
    from repro.mapreduce import wordcount

    return wordcount(config["key_space"])


def generate(config: dict, key):
    """``tokens`` int32 word ids, each uniform over ``key_space`` words, made
    on the device in one call."""
    import jax
    import jax.numpy as jnp

    return jax.random.randint(key, (config["tokens"],), 0,
                              config["key_space"], dtype=jnp.int32)


def pairs(xp, tokens, config: dict):
    """The (key, value) pairs the job aggregates: <word, 1>."""
    return tokens, xp.ones_like(tokens)
