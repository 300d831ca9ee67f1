"""Share of the KV pages the decode-attention kernel copied that were
live: pages below the rows' context lengths over pages copied, both
summed over the window's decode steps (counted on the host where the
model runner builds a step's work list, by the kernel's own rule:
`aphrodite:decode_attn_pages_live_total` over
`aphrodite:decode_attn_pages_fetched_total`). 100 says a row's last
work item copies its live pages only; a kernel that copies items whole
reads the dead share of a row's last item under it. A program without
the counters gives None."""
from perf.rounds import ratio


def read(run):
    value = ratio(run, "aphrodite:decode_attn_pages_live_total",
                  "aphrodite:decode_attn_pages_fetched_total")
    return None if value is None else value * 1e2
