"""Device ms of the map phase per job: one run of the benchmark's own
``bench_map`` program, ``ExecutionPlan.phase_fns()["map"]`` jitted alone,
in the traced run."""


def read(r):
    return r.phase_ms("map")
