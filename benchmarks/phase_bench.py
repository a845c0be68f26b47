"""Benchmark section ``phases``: per-phase telemetry + decomposed models.

The paper's Table 1 reports total-time prediction error; this section
decomposes it.  For WordCount and Exim parse on the Fig. 3 grid
(20 (M, R) settings in [5, 40]^2):

1. every setting runs through the telemetry path (``build_job(recorder=)``)
   and yields per-phase wall times + resource counters;
2. one regression per (phase, resource) is fitted on the paper's basis
   (``repro.telemetry.models``) next to the monolithic total-time model;
3. prediction error is reported per phase and for the *composed* predictor
   (sum of phase models) vs the monolithic one, on the training grid and
   on held-out settings — OLS is linear in its target, so composed can
   never lose on a shared basis, and the gap is verified numerically;
4. counter conservation (shuffle bytes in == out + dropped, phase times
   sum ~ total) is checked across all three reduce backends;
5. XLA's static flops/bytes estimates per phase (``telemetry.estimator``)
   are reported next to the measured times when the backend provides them.

CSV rows:
  phases,<app>,<M>,<R>,<phase>,<mean_time_s>,<share_pct>
  phases,<app>,_model,<phase>,train_mape_pct,
  phases,<app>,_composed,<grid|heldout>,composed_mape,monolithic_mape
  phases,<app>,_conservation,<backend>,ok,
  phases,<app>,_xla,<phase>,<flops>,<bytes>
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import heldout_configs, make_app, training_configs
from repro.core import fit
from repro.mapreduce import REDUCE_BACKENDS, JobConfig, build_job
from repro.telemetry import (
    PhaseRecorder,
    collect_traced,
    composed_vs_monolithic,
    estimates_available,
    fit_phase_models,
    stage_cost_estimates,
    targets_from_traces,
)
from repro.telemetry.models import TIME_RESOURCE

#: the conservation cross-check runs every reduce backend; the Pallas
#: kernel accepts partitions up to MAX_C = 2048 wide, which is what the
#: (8, 8) partitions at capacity factor 8 come to at this size.
CONSERVATION_TOKENS = 1 << 11


class TracedRunner:
    """Compile-cached traced runs: trace(config) for one application."""

    def __init__(self, app, corpus, *, warmup: int = 1, **cfg_kwargs):
        self.app = app
        self.corpus = corpus
        self.warmup = warmup
        self.cfg_kwargs = cfg_kwargs
        self.recorder = PhaseRecorder()
        self._cache: dict = {}

    def __call__(self, config):
        """Run once; return the JobTrace (collect phase included)."""
        M, R = int(round(config[0])), int(round(config[1]))
        key = (M, R)
        if key not in self._cache:
            job = build_job(
                self.app,
                JobConfig(num_mappers=M, num_reducers=R, **self.cfg_kwargs),
                len(self.corpus),
                recorder=self.recorder,
            )
            for _ in range(self.warmup):
                job(self.corpus)
                self.recorder.traces.pop()  # warmup (compile) not telemetry
            self._cache[key] = job
        job = self._cache[key]
        out_keys, out_vals, _ = job(self.corpus)
        trace = self.recorder.last
        collect_traced(trace, out_keys, out_vals)
        return trace


def profile_phases(runner, configs, repeats: int):
    """(params, traces_per_config): ``repeats`` traces per setting."""
    traces = [[runner(row) for _ in range(repeats)] for row in configs]
    return np.asarray(configs, dtype=np.float64), traces


def conservation_rows(app_name: str, app_factory, corpus) -> tuple[list, bool]:
    """Run one mid-grid config per reduce backend; verify conservation and
    counter equality (counters are semantics, never a backend axis)."""
    rows, ok = [], True
    reference = None
    for name in sorted(REDUCE_BACKENDS):
        runner = TracedRunner(
            app_factory, corpus, capacity_factor=8.0, reduce_backend=name
        )
        trace = runner((8, 8))
        violations = trace.check_conservation()
        # cpu_s / net_s are clock measurements — deterministic-equality
        # across backends applies to the semantic counters only.
        counters = {
            p.phase: {k: v for k, v in p.counters.items()
                      if k not in ("cpu_s", "net_s")}
            for p in trace.phases
        }
        if reference is None:
            reference = counters
        backend_ok = not violations and counters == reference
        ok = ok and backend_ok
        rows.append(
            f"phases,{app_name},_conservation,{name},"
            f"{'ok' if backend_ok else 'VIOLATION:' + ';'.join(violations)},"
        )
    return rows, ok


def main(tokens: int, repeats: int = 3) -> tuple[list[str], dict]:
    repeats = max(2, repeats)
    rows = ["phases,app,mappers,reducers,phase,mean_time_s,share_pct"]
    summary: dict = {"apps": {}}
    all_composed_le = True
    all_conservation = True
    for app_name in ("wordcount", "eximparse"):
        app, corpus = make_app(app_name, tokens)
        runner = TracedRunner(app, corpus, capacity_factor=8.0)
        train = training_configs()
        params, traces = profile_phases(runner, train, repeats)
        targets = targets_from_traces(traces)
        phase_names = traces[0][0].phase_names()
        phase_times = {
            p: targets[(p, TIME_RESOURCE)] for p in phase_names
        }
        totals = np.sum(list(phase_times.values()), axis=0)

        # Per-config rows: where does the time go at each setting?
        for i, (m, r) in enumerate(params):
            for p in phase_names:
                t = phase_times[p][i]
                rows.append(
                    f"phases,{app_name},{int(m)},{int(r)},{p},"
                    f"{t:.5f},{t / totals[i] * 100:.1f}"
                )

        # Decomposed models (paper basis) + the monolithic reference.
        phase_models = fit_phase_models(params, targets)
        monolithic = fit(params, totals)
        for p in phase_names:
            mape = phase_models.model(p).train_mape
            rows.append(f"phases,{app_name},_model,{p},{mape:.3f},")

        grid_cmp = composed_vs_monolithic(
            phase_models, monolithic, params, totals
        )
        rows.append(
            f"phases,{app_name},_composed,grid,"
            f"{grid_cmp['composed_mean_pct']:.4f},"
            f"{grid_cmp['monolithic_mean_pct']:.4f}"
        )
        # Held-out settings (paper's prediction phase), measured fresh.
        held = heldout_configs()
        h_params, h_traces = profile_phases(runner, held, repeats)
        h_targets = targets_from_traces(h_traces)
        h_totals = np.sum(
            [h_targets[(p, TIME_RESOURCE)] for p in phase_names], axis=0
        )
        held_cmp = composed_vs_monolithic(
            phase_models, monolithic, h_params, h_totals
        )
        rows.append(
            f"phases,{app_name},_composed,heldout,"
            f"{held_cmp['composed_mean_pct']:.4f},"
            f"{held_cmp['monolithic_mean_pct']:.4f}"
        )
        all_composed_le = all_composed_le and grid_cmp["composed_le_monolithic"]

        # Conservation across every reduce backend (small corpus: pallas).
        cons_app, cons_corpus = make_app(
            app_name, min(tokens, CONSERVATION_TOKENS)
        )
        cons_rows, cons_ok = conservation_rows(
            app_name, cons_app, cons_corpus
        )
        rows += cons_rows
        all_conservation = all_conservation and cons_ok

        # Static XLA cost estimates for a mid-grid setting.
        estimates = stage_cost_estimates(
            app, JobConfig(num_mappers=16, num_reducers=16,
                           capacity_factor=8.0), len(corpus)
        )
        for p, est in estimates.items():
            rows.append(
                f"phases,{app_name},_xla,{p},{est['flops']:.0f},"
                f"{est['bytes']:.0f}"
            )

        shuffle_bytes_model = phase_models.model("shuffle", "bytes_out")
        summary["apps"][app_name] = {
            "phase_time_share_pct": {
                p: float(phase_times[p].sum() / totals.sum() * 100)
                for p in phase_names
            },
            "per_phase_train_mape_pct": {
                p: phase_models.model(p).train_mape for p in phase_names
            },
            "composed_vs_monolithic_grid": grid_cmp,
            "composed_vs_monolithic_heldout": held_cmp,
            "shuffle_bytes_model_mape_pct": shuffle_bytes_model.train_mape,
            "conservation_ok": cons_ok,
            "xla_estimates": estimates,
            "xla_estimates_available": estimates_available(estimates),
        }

    summary["composed_le_monolithic_all_apps"] = all_composed_le
    summary["conservation_ok_all"] = all_conservation
    rows.append(
        f"phases,_summary,composed_le_monolithic={all_composed_le},"
        f"conservation_ok={all_conservation},,"
    )
    return rows, summary


if __name__ == "__main__":
    out, _ = main(1 << 14, 2)
    print("\n".join(out))
