"""UserVisits aggregation: ``SELECT sourceIP, SUM(adRevenue) FROM
UserVisits GROUP BY sourceIP`` (Pavlo et al., SIGMOD 2009, Sec. 4.3; HiBench
``sql/aggregation``).

The table is a flat int32 stream of fixed-width rows.  Each column takes
the number of tokens the configuration's ``columns`` give it, in order:
``sourceIP`` an interned id uniform over ``key_space``, ``adRevenue`` int32
cents uniform over ``revenue_cents``, every other token seeded random
content.
"""

from __future__ import annotations


def layout(config: dict) -> tuple[int, int, int]:
    """The row width in tokens and the tokens of ``sourceIP`` and
    ``adRevenue`` within a row."""
    offsets, width = {}, 0
    for name, tokens in config["columns"]:
        offsets[name] = width
        width += tokens
    return width, offsets["sourceIP"], offsets["adRevenue"]


def validate(config: dict, job: dict) -> None:
    """The table is whole rows, and every column holds at least a token."""
    width, _, _ = layout(config)
    if any(tokens < 1 for _, tokens in config["columns"]):
        raise ValueError("a column of no tokens")
    if config["tokens"] != config["rows"] * width:
        raise ValueError(f"tokens={config['tokens']} is not rows="
                         f"{config['rows']} of {width} tokens")


def make_app(config: dict):
    """The program's UserVisits aggregation over this table's layout."""
    from repro.mapreduce import uservisits

    return uservisits(config["key_space"], *layout(config))


def generate(config: dict, key):
    """The table as ``tokens`` int32, made on the device in one call, flat
    so that no row is padded out to a vector register."""
    import jax
    import jax.numpy as jnp

    width, ip_col, revenue_col = layout(config)
    n = config["tokens"]
    lo, hi = config["revenue_cents"]
    k_ip, k_revenue, k_rest = jax.random.split(key, 3)
    col = jnp.arange(n, dtype=jnp.int32) % width
    ip = jax.random.randint(k_ip, (n,), 0, config["key_space"], jnp.int32)
    revenue = jax.random.randint(k_revenue, (n,), lo, hi + 1, jnp.int32)
    rest = jax.lax.bitcast_convert_type(
        jax.random.bits(k_rest, (n,), jnp.uint32), jnp.int32)
    return jnp.where(col == ip_col, ip,
                     jnp.where(col == revenue_col, revenue, rest))


def pairs(xp, tokens, config: dict):
    """The (key, value) pairs the query aggregates: <sourceIP, adRevenue>
    per row."""
    width, ip_col, revenue_col = layout(config)
    rows = tokens.reshape(-1, width)
    return rows[:, ip_col], rows[:, revenue_col]
