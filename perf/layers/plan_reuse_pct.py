"""Share of sampling plans that built and sent nothing: the batch and
its `SamplingParams` had not changed since the plan before (counted;
`sampler.plan_reuse`)."""
from perf.rounds import ratio


def read(run):
    value = ratio(run, "aphrodite:sampler_plan_reuses_total",
                  "aphrodite:sampler_plans_total")
    return None if value is None else value * 1e2
