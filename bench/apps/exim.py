"""Exim mainlog parsing: the sum of logged sizes per message id.

The log is a flat int32 stream of ``[txn, event, size]`` records.  Each
message logs ``lines_per_message`` consecutive lines (arrival, one
delivery per recipient, completion) under an id of its own, drawn from a
seeded permutation of ``key_space`` ids, so log order carries no key
order.  The message's size is logged on its arrival and delivery lines.
"""

from __future__ import annotations

RECORD = 3  # [txn, event, size]
ARRIVAL, DELIVERY, COMPLETION = 0, 1, 2


def validate(config: dict, job: dict) -> None:
    """Every map task's split has to start on a record (the program parses
    each split of ``tokens / M`` tokens as whole records), the log has to
    end on a whole message, and every message needs an id of its own."""
    per_split = RECORD * job["num_mappers"]
    if config["tokens"] % per_split:
        raise ValueError(
            f"tokens={config['tokens']} is not a multiple of {per_split}: "
            "some splits would start inside a record")
    per_message = RECORD * config["lines_per_message"]
    if config["tokens"] % per_message:
        raise ValueError(f"tokens={config['tokens']} is not a multiple of "
                         f"{per_message}: the log ends inside a message")
    if config["tokens"] // per_message > config["key_space"]:
        raise ValueError("more messages than ids in the key space")


def make_app(config: dict):
    """The program's Exim mainlog parser over this configuration's ids."""
    from repro.mapreduce import eximparse

    return eximparse(config["key_space"])


def generate(config: dict, key):
    """The log as ``tokens`` int32, made on the device in one call."""
    import jax
    import jax.numpy as jnp

    lines = config["lines_per_message"]
    n_rec = config["tokens"] // RECORD
    n_msg = n_rec // lines
    smin, smax = config["size_bytes"]
    k_ids, k_size = jax.random.split(key)
    msg = jnp.arange(n_rec, dtype=jnp.int32) // lines
    line = jnp.arange(n_rec, dtype=jnp.int32) % lines
    event = jnp.where(line == 0, ARRIVAL,
                      jnp.where(line == lines - 1, COMPLETION, DELIVERY))
    txn = jax.random.permutation(k_ids, config["key_space"])[:n_msg][msg]
    size = jax.random.randint(k_size, (n_msg,), smin, smax + 1)[msg]
    size = jnp.where(event == COMPLETION, 0, size)
    log = jnp.stack([txn, event, size], axis=1).astype(jnp.int32)
    return log.reshape(-1)


def pairs(xp, tokens, config: dict):
    """The (key, value) pairs the job aggregates: <txn, size> per record."""
    rec = tokens.reshape(-1, RECORD)
    return rec[:, 0], rec[:, 2]
