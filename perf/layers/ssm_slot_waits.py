"""Admissions put off while the window was open because no state slot
was free although the pages were: the growth of
`aphrodite:ssm_slot_waits_total` (one for each scheduling round in
which the prompt at the head of the queue waited so). With as many
callers as slots it reads 0 unless a slot comes free a round late. A
program without the counter gives None."""


def read(run):
    seen = run.gauge("aphrodite:ssm_slot_waits_total")
    return seen[-1] - seen[0] if seen else None
