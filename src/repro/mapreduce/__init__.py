"""TPU-native MapReduce substrate: phase pipeline + pluggable backends +
the paper's two applications and the UserVisits aggregation.

Layering (see ARCHITECTURE.md):

    phases.py   — the single shared implementation of each phase
    backends.py — swappable shuffle/reduce strategies + registries
    plan.py     — ExecutionPlan: the ONE lowering into canonical wave
                  steppers; fused/traced/sharded/resumable are modes
    engine.py   — JobConfig/MapReduceApp + thin build_job mode selectors
    apps.py     — WordCount, Exim mainlog parsing, UserVisits aggregation
    datagen.py  — synthetic corpora
"""

from repro.mapreduce.engine import (
    JobConfig,
    MapReduceApp,
    PAD_KEY,
    build_job,
    build_job_sharded,
    collect_results,
)
from repro.mapreduce.plan import ExecutionPlan
from repro.mapreduce.backends import (
    REDUCE_BACKENDS,
    SHUFFLE_BACKENDS,
    ReduceBackend,
    ShuffleBackend,
    get_reduce_backend,
    get_shuffle_backend,
    register_reduce_backend,
    register_shuffle_backend,
)
from repro.mapreduce.apps import eximparse, uservisits, wordcount, \
    RECORD_WIDTH
from repro.mapreduce.datagen import exim_mainlog, wordcount_corpus

__all__ = [
    "ExecutionPlan",
    "JobConfig",
    "MapReduceApp",
    "PAD_KEY",
    "build_job",
    "build_job_sharded",
    "collect_results",
    "REDUCE_BACKENDS",
    "SHUFFLE_BACKENDS",
    "ReduceBackend",
    "ShuffleBackend",
    "get_reduce_backend",
    "get_shuffle_backend",
    "register_reduce_backend",
    "register_shuffle_backend",
    "eximparse",
    "uservisits",
    "wordcount",
    "RECORD_WIDTH",
    "exim_mainlog",
    "wordcount_corpus",
]
