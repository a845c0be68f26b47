"""Device ms of the combine phase per job: one run of the benchmark's own
``bench_combine`` program, ``ExecutionPlan.phase_fns()["combine"]`` jitted alone,
in the traced run."""


def read(r):
    return r.phase_ms("combine")
