"""Device ms of the reduce phase per job: one run of the benchmark's own
``bench_reduce`` program, ``ExecutionPlan.phase_fns()["reduce"]`` jitted alone,
in the traced run."""


def read(r):
    return r.phase_ms("reduce")
