"""The one child that holds the chip: the program's own OpenAI server.

Runs `aphrodite_tpu.endpoints.openai.api_server` exactly as `python -m`
would, and when it has drained and returned, prints the peak device
memory that only the process holding the chip can read.
"""
import runpy
import sys


def _print_memory_peak() -> None:
    import jax
    peaks = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use",
                                   stats.get("bytes_in_use", 0))))
    print(f"perf: device memory peak_bytes_in_use={peaks}",
          file=sys.stderr, flush=True)


if __name__ == "__main__":
    try:
        runpy.run_module("aphrodite_tpu.endpoints.openai.api_server",
                         run_name="__main__", alter_sys=True)
    finally:
        if "jax" in sys.modules:
            _print_memory_peak()
