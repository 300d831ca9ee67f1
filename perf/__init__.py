"""The on-chip benchmark of aphrodite-tpu: one served cell per run.

`python perf/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>`

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name that
`BENCHMARK.json` gives it: `configs/<config>.json`,
`traffic/<traffic>.json`, `generators/<generator>.py`,
`end_to_end/<metric>.py`, `layers/<metric>.py` (a metric split by the
end-to-end metric it moves, `<quantity>.<split>`, is read by
`<quantity>.py`), `references/<reference>.py` (the configuration's
plain float32 forward pass, named by its `perf.reference`; it decides
`correct`, see `reference.py`, and states the tree of weights that
`weights.py` makes from the seed for the server and for itself) and `rooflines/<kernel>.py` (a kernel's
bytes and operations from shapes, for a `<kernel>_roofline` reader). A
later PR adds a cell by adding such files and entries; it edits
nothing that is here.
"""
