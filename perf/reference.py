"""`correct`, as far as arithmetic goes: what the timed path produced
against the configuration's plain reference.

The harness keeps a few replies whose token ids it has (the canary's,
each served alone, and the journal caller's of the window, rows of the
full decode batch), and, once the server has exited and the chip is
free, starts `perf/reference_child.py`. The child runs the reference
that `perf.reference` names (`perf/references/<name>.py`) over prompt
and reply of each sequence, teacher-forced, and returns for every
generated position the reference's logit of the token the system
chose, its largest logit and the standard deviation of its logits
there. This module never imports JAX.

The numbers compared are in logit space, so that a flipped near-tie
costs what the tie was worth and no more: `gap` = (largest - chosen) /
standard deviation at a position; its mean over all positions, the
share of positions where it is over `gap_threshold`, and its largest.
Each has its limit in the configuration's `perf.reference_tolerances`.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time
from typing import List, Optional, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
NUMBERS = ("gap_mean", "gap_share", "gap_worst")
CHILD_TIMEOUT_S = 600.0


def pick(canary: Sequence, replies: Sequence, t0: float, t1: float,
         most: int) -> Tuple[List[dict], int]:
    """The sequences to compare and how many of them are the window's:
    the first canary reply that came whole (each is alone on the
    server, one prefill and one one-row decode program, so one of
    them covers what the three do), then up to `most` streamed
    replies that ended in the window: of those that also began in it
    (prefill and every decode step of theirs ran in it) the longest,
    then the earliest of the others; where they are fewer than `most`,
    those that began before it opened fill up, the earliest first."""
    have = sorted((r for r in replies
                   if r.ok and r.ids and r.prompt and r.ended <= t1),
                  key=lambda r: r.sent)
    inside = [r for r in have if r.sent >= t0]
    pool = inside + [r for r in have if r.sent < t0]
    longest = max(inside or pool, key=lambda r: len(r.ids), default=None)
    chosen = ([longest] + [r for r in pool if r is not longest])[:most] \
        if pool else []
    alone = [r for r in canary if r.ok and r.ids and r.prompt][:1]
    return ([dict(prompt=list(r.prompt), reply=list(r.ids))
             for r in alone + chosen], len(chosen))


def gap_stats(chosen: Sequence[float], best: Sequence[float],
              std: Sequence[float], threshold: float) -> dict:
    """The three numbers from the child's per-position readings."""
    gaps = [(b - c) / s if s > 0 else (0.0 if b <= c else float("inf"))
            for c, b, s in zip(chosen, best, std)]
    if not gaps:
        return dict(positions=0)
    worst = max(range(len(gaps)), key=gaps.__getitem__)
    return dict(positions=len(gaps), gap_mean=sum(gaps) / len(gaps),
                gap_share=sum(g > threshold for g in gaps) / len(gaps),
                gap_worst=gaps[worst], worst_at=worst)


def judge(stats: dict, tolerances: dict) -> Tuple[List[str], List[str]]:
    """One line for each number beside its limit, and the faults."""
    if not stats.get("positions"):
        return [], ["the reference compared no position"]
    lines, faults = [], []
    for name in NUMBERS:
        value, limit = stats[name], float(tolerances[name])
        lines.append(f"reference: {name} {value:.6g} (limit {limit:g})"
                     f"{'' if value <= limit else ' EXCEEDED'}")
        if not value <= limit:
            faults.append(f"reference: {name} {value:.6g} over its limit "
                          f"{limit:g} ({stats['positions']} positions)")
    return lines, faults


@dataclasses.dataclass
class Check:
    """A reference child that has been started. It imports and
    prepares while the server drains and then waits; `release()` tells
    it that the server has exited and the chip is free, `finish()`
    waits for its result. What lies between those two runs beside it
    (the trace's reduction, on the host, while the child reaches the
    chip)."""
    cell: object
    sequences: List[dict]
    window_replies: int
    began: float
    proc: Optional[subprocess.Popen] = None
    path_out: str = ""
    fault: Optional[str] = None
    #: after `finish()`: what each control asked for read,
    #: `{name: (numbers, lines, faults)}`
    controls: dict = dataclasses.field(default_factory=dict)

    def _numbers(self, side: dict) -> dict:
        limits = self.cell.config["perf"]["reference_tolerances"]
        return gap_stats(side["chosen"], side["best"], side["std"],
                         float(limits["gap_threshold"]))

    def release(self) -> None:
        """The server has exited: the child may take the chip. The
        seconds the step costs the run count from here. A second call
        does nothing."""
        if self.proc is not None and self.proc.stdin is not None:
            self.began = time.monotonic()
            try:
                self.proc.stdin.write("go\n")
                self.proc.stdin.close()
            except OSError:
                pass                    # it has died: finish() says so
            self.proc.stdin = None

    def abandon(self) -> None:
        """The run is given up: end the child and wait for it."""
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()

    def finish(self) -> Tuple[dict, List[str], List[str]]:
        """The `reference` object of the result line, one line for each
        number compared, and the faults."""
        self.release()
        if self.fault is None:
            try:
                text, _ = self.proc.communicate(timeout=CHILD_TIMEOUT_S)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                text, _ = self.proc.communicate()
                self.fault = "the reference child did not end in time"
            if self.fault is None and (self.proc.returncode != 0 or
                                       not os.path.exists(self.path_out)):
                self.fault = ("the reference child failed (exit code "
                              f"{self.proc.returncode}): {text[-1500:]}")
        if self.fault is not None:
            return dict(positions=0), [], [f"reference: {self.fault}"]
        with open(self.path_out) as f:
            out = json.load(f)
        tolerances = self.cell.config["perf"]["reference_tolerances"]
        for name in self.controls:
            numbers = self._numbers(out[name])
            numbers.pop("worst_at", None)
            self.controls[name] = (numbers,) + judge(numbers, tolerances)
        stats = self._numbers(out["served"])
        if stats.get("positions"):
            seq, pos = out["positions"][stats.pop("worst_at")]
            stats["worst_at"] = dict(sequence=seq, position=pos)
        stats.update(
            sequences=len(self.sequences),
            window_replies=self.window_replies,
            tokens=sum(len(s["prompt"]) + len(s["reply"])
                       for s in self.sequences),
            layer_share=out["layer_share"],
            seconds=time.monotonic() - self.began,
            child_start_s=out["start_s"], compute_s=out["compute_s"])
        return (stats,) + judge(stats, tolerances)


def start(cell, seed: int, sequences: List[dict], window_replies: int,
          rows: int, cpu: bool, controls: Sequence[str] = ()) -> Check:
    """Start the child on the sequences kept; it stays off the chip
    until `Check.release()`. `rows` is the most sequences a run of the
    cell can keep (the child's programs have one shape).
    For each name in `controls` the child also runs the reference with
    what `perf.controls[name]` lowers, and `Check.controls[name]` gets
    the numbers of the tokens which that puts first."""
    check = Check(cell=cell, sequences=sequences,
                  window_replies=window_replies, began=time.monotonic(),
                  controls=dict.fromkeys(controls))
    if not window_replies:
        check.fault = ("no streamed reply of the window to compare "
                       "with the reference")
        return check
    work = os.path.join(cell.root, "perf", ".work", cell.name)
    os.makedirs(work, exist_ok=True)
    path_in = os.path.join(work, "reference_in.json")
    check.path_out = os.path.join(work, "reference_out.json")
    with open(path_in, "w") as f:
        json.dump(dict(
            root=cell.root, config=cell.config,
            name=cell.config["perf"]["reference"], seed=seed,
            sequences=sequences, rows=rows, cpu=cpu,
            controls=list(controls),
            cache=os.path.join(cell.root, "perf", ".cache", "jax",
                               "reference", "cpu" if cpu else "tpu")), f)
    if os.path.exists(check.path_out):
        os.remove(check.path_out)
    # As for the server (`perf/server.py`): the cache is the
    # benchmark's own and may not evict. A machine may bring a size cap
    # in its environment, and under one JAX keeps an access-time file
    # beside each entry and fails every write where one is missing.
    env = dict(os.environ, PYTHONPATH=cell.root,
               JAX_COMPILATION_CACHE_MAX_SIZE="-1")
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    try:
        check.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "reference_child.py"),
             path_in, check.path_out], cwd=cell.root, env=env, text=True,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT)
    except OSError as e:
        check.fault = f"the reference child did not start: {e}"
    return check
