"""Prefix tokens that prompt steps read back from latent pages and
up-projected to keys and values, over the prompt tokens written
(`aphrodite:mla_prefix_tokens_expanded_total`, a layer's, counted in
the step programs, over `aphrodite:prompt_tokens_total`): what
writing a prompt in chunks costs the up-projection of a model with
multi-head latent attention, whose cache holds the latent and not the
keys. A prompt written whole reads 0; an 8,192-token prompt in four
chunks of 2,048 reads 2,048 + 4,096 + 6,144 back: 1.5. A program
without the counter gives None."""
from perf.rounds import ratio


def read(run):
    return ratio(run, "aphrodite:mla_prefix_tokens_expanded_total",
                 "aphrodite:prompt_tokens_total")
