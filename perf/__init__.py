"""The on-chip benchmark of aphrodite-tpu: one served cell per run.

`python perf/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>`

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name that
`BENCHMARK.json` gives it: `configs/<config>.json`,
`traffic/<traffic>.json`, `generators/<generator>.py`,
`end_to_end/<metric>.py`, `layers/<metric>.py` (a metric split by the
end-to-end metric it moves, `<quantity>.<split>`, is read by
`<quantity>.py`). A later PR adds a cell by adding such files and one
`workloads` entry; it edits nothing that is here.
"""
