"""The paper's two benchmark applications, and the Pavlo et al.
aggregation, on the TPU MapReduce engine.

* **WordCount** — each map task takes a split of word-ids and emits
  ``<word, 1>``; reducers sum per word.  (Paper §V.A, refs [33-34].)
* **Exim Mainlog parsing** — Exim logs are sequences of per-message records;
  the Hadoop job groups log lines by transaction id.  Our token encoding of a
  mainlog is a flat stream of fixed-width records
  ``[txn_id, event_type, size]``: the app declares that width, so the plan
  splits the log on records and the map emits one ``<txn_id, size>`` pair per
  record; reducers sum the bytes per transaction.  (Paper §V.A, ref [35].)
* **UserVisits aggregation** — ``SELECT sourceIP, SUM(adRevenue) FROM
  UserVisits GROUP BY sourceIP`` (Pavlo et al., SIGMOD 2009, §4.3; HiBench
  ``sql/aggregation``) over a table of fixed-width int32 rows: the app
  declares its row width, so the plan splits the table on rows and the map
  emits one pair per row.

All three are pure `jnp` map functions with static output sizes, as the
engine requires.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.mapreduce.engine import MapReduceApp, PAD_KEY
from repro.mapreduce.phases import LANES

# ---------------------------------------------------------------------------
# Fixed-width records
# ---------------------------------------------------------------------------


def _column(tokens, width: int, col: int):
    """Column ``col`` of a split of ``width``-token rows, read from a
    lane-dense ``(rows * width / LANES, LANES)`` view (row ``i * k + j`` of
    ``k = LANES / width`` rows at lane ``j * width + col``) where the rows
    tile the lanes, else from the flat split by stride; a ``(rows, width)``
    view would pad each row out to a whole vector register."""
    if LANES % width == 0 and tokens.size % LANES == 0:
        return tokens.reshape(-1, LANES)[:, col::width].reshape(-1)
    return tokens.reshape(-1)[col::width]


def _record_pairs(width: int, key_col: int, value_col: int):
    """The map of one ``<record[key_col], record[value_col]>`` pair per
    record of ``width`` tokens: the plan hands it a split of S whole
    records and one validity flag per record, and it emits S pairs."""

    def map_fn(tokens, valid):
        key = _column(tokens, width, key_col)
        value = _column(tokens, width, value_col)
        keys = jnp.where(valid, key, PAD_KEY)
        values = jnp.where(valid, value, 0).astype(jnp.int32)
        return keys, values, valid

    return map_fn


# ---------------------------------------------------------------------------
# WordCount
# ---------------------------------------------------------------------------


def _wordcount_map(tokens, valid):
    """<line of words> -> <word, 1> pairs."""
    keys = jnp.where(valid, tokens, PAD_KEY)
    values = jnp.where(valid, 1, 0).astype(jnp.int32)
    return keys, values, valid


def wordcount(vocab_size: int = 4096) -> MapReduceApp:
    return MapReduceApp(
        name="wordcount",
        key_space=vocab_size,
        map_fn=_wordcount_map,
        reduce_op="sum",
    )


# ---------------------------------------------------------------------------
# Exim Mainlog parsing
# ---------------------------------------------------------------------------

RECORD_WIDTH = 3  # [txn_id, event_type, size_bytes]


def eximparse(n_transactions: int = 1024) -> MapReduceApp:
    """<record> -> <txn_id, size>, one pair per record; reducers sum the
    sizes per transaction id, the per-transaction grouping of the paper's
    Exim job.  The plan splits the log on records, as Hadoop's input
    splits are line-aligned, so no record straddles two map tasks."""
    return MapReduceApp(
        name="eximparse",
        key_space=n_transactions,
        map_fn=_record_pairs(RECORD_WIDTH, 0, 2),
        record_width=RECORD_WIDTH,
        reduce_op="sum",
    )


# ---------------------------------------------------------------------------
# UserVisits aggregation
# ---------------------------------------------------------------------------


def uservisits(key_space: int, record_width: int, ip_col: int,
               revenue_col: int) -> MapReduceApp:
    """``SUM(adRevenue) GROUP BY sourceIP`` over UserVisits rows of
    ``record_width`` int32 tokens: ``sourceIP`` interned to an id below
    ``key_space`` at token ``ip_col``, ``adRevenue`` in int32 cents at
    token ``revenue_col``."""
    for col in (ip_col, revenue_col):
        if not 0 <= col < record_width:
            raise ValueError(f"column {col} outside a row of {record_width}")
    return MapReduceApp(
        name="uservisits",
        key_space=key_space,
        map_fn=_record_pairs(record_width, ip_col, revenue_col),
        record_width=record_width,
        reduce_op="sum",
    )
