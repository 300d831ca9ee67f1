"""A decode batch of `_WIDE_ROWS` rows or more takes its tables as wide
ones (`ModelRunner._table_width`, `_work_length`): one program a batch
bucket while its rows grow through 2,048 tokens, where 8-page steps
made a program of every step. Batches under it keep the widths they
had (every cell the benchmark had runs at 48 rows or fewer), and what
a client sees does not depend on the width."""
import types

import pytest

from aphrodite_tpu.common.sampling_params import SamplingParams
from aphrodite_tpu.executor import model_runner
from aphrodite_tpu.executor.model_runner import ModelRunner
from aphrodite_tpu.ops.pallas.paged_attention import padded_work_length


def _width(pages, rows):
    return ModelRunner._table_width(
        types.SimpleNamespace(pages_bucket=model_runner._PAGES_BUCKET),
        pages, rows)


@pytest.mark.parametrize("rows", [1, 24, 48])
@pytest.mark.parametrize("pages,want", [
    (1, 8), (33, 40), (72, 72), (81, 88), (96, 96), (128, 128),
    (129, 192), (576, 576)])
def test_a_batch_under_the_wide_rows_keeps_its_widths(rows, pages, want):
    assert _width(pages, rows) == want == _width(pages, 1)


@pytest.mark.parametrize("rows", [64, 96, 128])
@pytest.mark.parametrize("pages,want", [
    (1, 128), (33, 128), (96, 128), (128, 128), (129, 192), (256, 256)])
def test_a_wide_batch_has_one_width_up_to_2048_tokens(rows, pages, want):
    assert _width(pages, rows) == want


def test_a_wide_batchs_work_list_is_dense_once_rows_pass_an_item():
    """128 rows of 513-2,048 tokens under 512-token items (32 pages):
    every list length is the dense 512, so the bucket is one program;
    48 rows keep `padded_work_length`'s lengths."""
    for items in (256, 300, 384, 512):
        assert ModelRunner._work_length(items, 128, 128, 32) == 512
    # rows of one item each (contexts of 512 tokens or fewer)
    assert ModelRunner._work_length(128, 128, 128, 32) == 128
    for items in (48, 96, 144):
        assert ModelRunner._work_length(items, 48, 88, 32) == \
            padded_work_length(items, 48, 88, 32)


def test_what_a_client_sees_does_not_depend_on_the_width(tiny_model_dir,
                                                          monkeypatch):
    """70 greedy requests in one decode batch (bucket 96, tables 128
    wide for contexts of some 30 tokens) give the tokens they give 35
    at a time (bucket 48, tables 8 wide)."""
    from aphrodite_tpu.engine.aphrodite_engine import AphroditeEngine
    from aphrodite_tpu.engine.args_tools import EngineArgs
    monkeypatch.setenv("APHRODITE_SPEC", "0")
    engine = AphroditeEngine(*EngineArgs(
        model=tiny_model_dir, load_format="dummy", dtype="float32",
        block_size=16, max_model_len=256, max_num_seqs=96,
        swap_space=0.01, disable_log_stats=True).create_engine_configs())
    widths = []
    own = engine.executor.model_runner._table_width
    monkeypatch.setattr(
        engine.executor.model_runner, "_table_width",
        lambda pages, rows=1: widths.append((rows, own(pages, rows)))
        or widths[-1][1])

    def serve(tag, ids):
        for i in ids:
            engine.add_request(
                f"{tag}-{i}", None,
                SamplingParams(temperature=0.0, max_tokens=6 + i % 5,
                               ignore_eos=True),
                prompt_token_ids=[(i * 7 + j * 3) % 90 + 5
                                  for j in range(20 + i % 4)])
        done = {}
        while engine.has_unfinished_requests():
            for out in engine.step():
                if out.finished:
                    done[int(out.request_id.split("-")[1])] = \
                        list(out.outputs[0].token_ids)
        return done

    together = serve("all", range(70))
    assert (96, 128) in widths
    del widths[:]
    apart = {**serve("a", range(35)), **serve("b", range(35, 70))}
    assert widths and all(rows <= 48 and width == 8
                          for rows, width in widths if rows > 1)
    assert together == apart
