"""Device ms of the shuffle phase per job: one run of the benchmark's own
``bench_shuffle`` program, ``ExecutionPlan.phase_fns()["shuffle"]`` jitted alone,
in the traced run."""


def read(r):
    return r.phase_ms("shuffle")
