"""(prompt + output tokens) a second x 2 x parameters, over the peak
of the configuration's matmul precision: an end-to-end utilisation of
the window, not a kernel's roofline share."""


def read(run):
    rates = [run.rate(f"aphrodite:{k}_tokens_total")
             for k in ("prompt", "generation")]
    if None in rates or not sum(rates) or run.peaks is None:
        return None
    cfg = run.cell.config["perf"]
    peak = run.peaks[cfg["matmul_peak"]] * run.cell.chips
    return sum(rates) * 2.0 * cfg["parameters"] / peak * 100.0
