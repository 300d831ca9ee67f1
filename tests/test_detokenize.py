"""`detokenize_incrementally` a token at a time: the same tokens, text
and offsets as the form it replaced (kept below as the definition:
the whole token list joined at every call, the tokenizer's own list
route for one id), and a call reads the window alone."""
import numpy as np
import pytest
from transformers import AutoTokenizer

from aphrodite_tpu.transformers_utils.tokenizer import (
    detokenize_incrementally)


@pytest.fixture(scope="module")
def tokenizer(tiny_model_dir):
    return AutoTokenizer.from_pretrained(tiny_model_dir)


def _defined(tokenizer, ids, prev, prefix, read, skip):
    """The step as it was written before PR 41's second session."""
    if prev is None:
        new = tokenizer.convert_ids_to_tokens(ids, skip_special_tokens=skip)
        new = [t if t is not None else "" for t in new]
        tokens = new
        prefix = max(len(tokens) - 5, 0)
        read = len(tokens) if skip and ids[-1] in \
            tokenizer.all_special_ids else max(len(tokens) - 1, 0)
    else:
        new = tokenizer.convert_ids_to_tokens([ids[-1]],
                                              skip_special_tokens=skip)
        if new and new[0] is None:
            new = [""]
        tokens = prev + new
    before = tokenizer.convert_tokens_to_string(tokens[prefix:read])
    text = tokenizer.convert_tokens_to_string(tokens[prefix:])
    if len(text) > len(before) and not text.endswith("�"):
        return new, text[len(before):], read, len(tokens)
    return new, "", prefix, read


# ids under the vocabulary's 512 (bytes that are no UTF-8 alone among
# them), ids past it (the benchmark's: they have no token), the three
# special ids thick among them
@pytest.mark.parametrize("skip", [False, True])
@pytest.mark.parametrize("low,high", [(3, 512), (0, 6), (0, 512),
                                      (400, 2000)])
def test_a_token_at_a_time_is_the_step_as_defined(tokenizer, skip, low,
                                                  high):
    rng = np.random.default_rng(high * 2 + skip)
    prompt = rng.integers(low, high, 9).tolist()
    ids, mine, theirs = list(prompt), (None, 0, 0), (None, 0, 0)
    text = ""
    for token in rng.integers(low, high, 120).tolist():
        ids.append(token)
        want = _defined(tokenizer, ids, *theirs, skip)
        # once seeded, the last id is all that is read of the ids
        got = detokenize_incrementally(
            tokenizer, ids if mine[0] is None else [token], *mine,
            skip_special_tokens=skip)
        assert got == want
        mine = ((mine[0] or []) + got[0], got[2], got[3])
        theirs = ((theirs[0] or []) + want[0], want[2], want[3])
        text += got[1]
    assert high > 512 or skip or text        # the case says something


def test_a_call_reads_the_window_alone(tokenizer):
    """Ten thousand tokens behind the window are neither joined nor
    walked: the list is sliced from `prefix_offset` and measured."""

    class Watched(list):
        def __add__(self, other):
            raise AssertionError("the whole list was joined")

        def __iter__(self):
            raise AssertionError("the whole list was walked")

        def __getitem__(self, at):
            assert isinstance(at, slice) and at.start >= len(self) - 6
            return list.__getitem__(self, at)

    tokens = tokenizer.convert_ids_to_tokens(list(range(3, 259)) * 40)
    n = len(tokens)
    new, text, prefix, read = detokenize_incrementally(
        tokenizer, [300], Watched(tokens), n - 5, n - 1)
    assert new == tokenizer.convert_ids_to_tokens([300])
    assert (prefix, read) in (((n - 1), n + 1), (n - 5, n - 1))


def test_special_ids_are_read_once_a_tokenizer(tokenizer, monkeypatch):
    reads = []
    kind = type(tokenizer)
    prop = kind.all_special_ids
    monkeypatch.setattr(kind, "all_special_ids", property(
        lambda self: reads.append(1) or prop.fget(self)))
    tokenizer.__dict__.pop("_aphrodite_special_ids", None)
    tokens = tokenizer.convert_ids_to_tokens([5, 6, 7])
    for _ in range(50):
        detokenize_incrementally(tokenizer, [1], tokens, 0, 2,
                                 skip_special_tokens=True)
    assert len(reads) == 1


# the same ranges, and prompts that end inside a character: the whole
# text in one pass is the steps' pieces joined
@pytest.mark.parametrize("skip", [False, True])
@pytest.mark.parametrize("low,high", [(3, 512), (0, 6), (0, 512),
                                      (400, 2000), (3, 259), (130, 259),
                                      (0, 40960)])
def test_the_whole_text_is_the_steps_joined(tokenizer, skip, low, high):
    from aphrodite_tpu.transformers_utils.tokenizer import (
        decodes_bytes, detokenize_whole)
    assert decodes_bytes(tokenizer)
    rng = np.random.default_rng(high * 2 + skip + 7)
    said = 0
    for trial in range(60):
        prompt = rng.integers(low, high, int(rng.integers(1, 12))).tolist()
        outputs = rng.integers(low, high,
                               int(rng.integers(1, 90))).tolist()
        ids, state, text = list(prompt), (None, 0, 0), ""
        for n, token in enumerate(outputs, 1):
            ids.append(token)
            new, piece, prefix, read = detokenize_incrementally(
                tokenizer, ids if state[0] is None else [token], *state,
                skip_special_tokens=skip)
            state = ((state[0] or []) + new, prefix, read)
            text += piece
            if trial % 6 == 0 or n == len(outputs):
                assert detokenize_whole(
                    tokenizer, prompt, outputs[:n],
                    skip_special_tokens=skip) == text
        said += bool(text)
    assert said or (high > 512 and low >= 400) or (skip and high <= 6)
    assert detokenize_whole(tokenizer, prompt, [], skip) == ""


def test_a_decoder_that_joins_words_is_read_a_step_at_a_time():
    """`decodes_bytes` is the `ByteLevel` decoder alone: a decoder
    that puts spaces between its tokens gives a window's text that is
    no join of the tokens' own."""
    from tokenizers import Tokenizer, decoders, models
    from transformers import PreTrainedTokenizerFast
    from aphrodite_tpu.transformers_utils.tokenizer import decodes_bytes
    tok = Tokenizer(models.WordPiece({"[UNK]": 0, "a": 1, "##b": 2},
                                     unk_token="[UNK]"))
    tok.decoder = decoders.WordPiece()
    assert not decodes_bytes(PreTrainedTokenizerFast(tokenizer_object=tok))
