"""The one child that holds the chip: the program's own OpenAI server.

    python perf/serve_child.py <cell_config.json> <the server's arguments>

Runs `aphrodite_tpu.endpoints.openai.api_server` exactly as `python -m`
would, but for one thing: the weights are the benchmark's. The server
is started with `--load-format dummy`, and the function the program's
loader calls for that format (`aphrodite_tpu.modeling.loader
.initialize_dummy_params(model, seed=, mesh=)`, the one name of the
program this file depends on) is replaced by one that makes the tree
the configuration's reference states (`perf/references/<name>.py::tree`)
from the seed by the benchmark's own recipe (`perf/weights.py`), a
stage in one jitted call. A tree that differs from the program's own
in a name, a shape or a type ends the process: the reference would be
run over other weights than were served. When the server has drained
and returned, the peak device memory is printed, which only the
process holding the chip can read.
"""
import json
import os
import runpy
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _print_memory_peak() -> None:
    import jax
    peaks = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use",
                                   stats.get("bytes_in_use", 0))))
    print(f"perf: device memory peak_bytes_in_use={peaks}",
          file=sys.stderr, flush=True)


def serve_weights_of(config: dict) -> None:
    """Put the benchmark's weights in the place of the program's dummy
    ones, for a configuration that names a reference."""
    from aphrodite_tpu.modeling import loader
    from perf import cells, weights
    if not callable(getattr(loader, "initialize_dummy_params", None)):
        raise SystemExit("perf/serve_child.py: the program's loader has no "
                         "initialize_dummy_params to take the "
                         "benchmark's weights")
    ref = cells.load_module(os.path.join(
        cells.ROOT, "perf", "references",
        config["perf"]["reference"] + ".py"))

    def from_the_benchmark(model, seed=0, mesh=None):
        import jax
        tree = ref.tree(config)
        have = {b: {n: (tuple(a.shape), a.dtype.name)
                    for n, a in leaves.items()}
                for b, leaves in jax.eval_shape(model.init_params).items()}
        want = {b: {n: (tuple(spec[0]), spec[1])
                    for n, spec in leaves.items()}
                for b, leaves in tree.items()}
        if have != want:
            off = sorted(b for b in set(have) | set(want)
                         if have.get(b) != want.get(b))
            raise SystemExit(
                "perf/serve_child.py: the reference's tree is not the "
                f"program's; they differ in {off[:6]} ({len(off)} buckets)")
        params = weights.whole(tree, ref.stages(config), seed)
        if mesh is not None and mesh.size > 1:
            from jax.sharding import NamedSharding, PartitionSpec
            specs = model.param_specs()
            params = {b: {n: jax.device_put(a, NamedSharding(
                mesh, specs.get(b, {}).get(n, PartitionSpec())))
                for n, a in leaves.items()}
                for b, leaves in params.items()}
        print(f"perf: weights from the benchmark: {len(params)} buckets, "
              f"seed {seed}, reference {config['perf']['reference']}",
              file=sys.stderr, flush=True)
        return params

    loader.initialize_dummy_params = from_the_benchmark


if __name__ == "__main__":
    with open(sys.argv.pop(1)) as f:
        cell_config = json.load(f)
    try:
        if "reference" in cell_config["perf"]:
            serve_weights_of(cell_config)
        runpy.run_module("aphrodite_tpu.endpoints.openai.api_server",
                         run_name="__main__", alter_sys=True)
    finally:
        if "jax" in sys.modules:
            _print_memory_peak()
