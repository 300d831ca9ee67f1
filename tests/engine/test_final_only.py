"""A request nobody streams (`final_only`): the engine hands out its
finished output and builds none before, the tokens and the text are
what a streamed twin gets, and the handler that waits for it still
sees its client hang up."""
import asyncio
import types

import pytest

from aphrodite_tpu.common.sampling_params import SamplingParams
from aphrodite_tpu.endpoints import utils
from aphrodite_tpu.engine.aphrodite_engine import AphroditeEngine
from aphrodite_tpu.engine.args_tools import EngineArgs
from aphrodite_tpu.engine.async_aphrodite import AsyncStream


def _engine(model_dir):
    return AphroditeEngine(*EngineArgs(
        model=model_dir, load_format="dummy", dtype="float32",
        max_model_len=256, max_num_seqs=8, swap_space=0.01,
        disable_log_stats=True).create_engine_configs())


def test_one_output_the_finished_one_and_the_twins_text(tiny_model_dir,
                                                         monkeypatch):
    engine = _engine(tiny_model_dir)
    built = []
    from aphrodite_tpu.common import outputs
    made = outputs.RequestOutput.from_seq_group.__func__
    monkeypatch.setattr(
        outputs.RequestOutput, "from_seq_group", classmethod(
            lambda cls, group: built.append(group.request_id) or
            made(cls, group)))
    params = SamplingParams(temperature=0.0, max_tokens=12,
                            ignore_eos=True)
    for name, final in (("whole", True), ("streamed", False)):
        engine.add_request(name, "the quick brown fox", params,
                           final_only=final)
    got = {"whole": [], "streamed": []}
    while engine.has_unfinished_requests():
        for out in engine.step():
            got[out.request_id].append(out)
    assert len(got["streamed"]) == 12 and len(got["whole"]) == 1
    assert built.count("whole") == 1 and built.count("streamed") == 12
    (whole,), last = got["whole"], got["streamed"][-1]
    assert whole.finished and last.finished
    assert whole.outputs[0].token_ids == last.outputs[0].token_ids
    assert len(whole.outputs[0].token_ids) == 12
    assert whole.outputs[0].text == last.outputs[0].text
    assert whole.outputs[0].finish_reason == "length"


@pytest.mark.parametrize("case,extra,steps", [
    ("text at the end", {}, 0),
    ("a stop string reads the text a token", {"stop": ["\x00never"]}, 12),
    ("special tokens kept", {"skip_special_tokens": False}, 0),
])
def test_the_text_nobody_reads_is_made_when_the_row_ends(
        tiny_model_dir, monkeypatch, case, extra, steps):
    """An unstreamed row with no stop string runs no detokeniser step
    (two decoder calls a token a row): its text is made once, when it
    ends, and is its streamed twin's. A continuation, whose tokens
    are replayed a step at a time on arrival, goes on by steps."""
    from aphrodite_tpu.engine import aphrodite_engine
    engine = _engine(tiny_model_dir)
    stepped = []
    step = aphrodite_engine.detokenize_incrementally
    monkeypatch.setattr(
        aphrodite_engine, "detokenize_incrementally",
        lambda *a, **k: stepped.append(1) or step(*a, **k))
    params = SamplingParams(temperature=0.0, max_tokens=12,
                            ignore_eos=True, **extra)
    prompt = [7, 300, 131, 40, 200, 222]      # ends inside a character
    engine.add_request("whole", None, params, prompt_token_ids=prompt,
                       final_only=True)
    (whole,) = [o for _ in range(40) if engine.has_unfinished_requests()
                for o in engine.step()]
    assert len(stepped) == steps, case
    del stepped[:]
    engine.add_request("streamed", None, params, prompt_token_ids=prompt)
    engine.add_request("resumed", None, params, prompt_token_ids=prompt,
                       emitted_token_ids=whole.outputs[0].token_ids[:5],
                       final_only=True)
    last = {}
    while engine.has_unfinished_requests():
        last.update((o.request_id, o) for o in engine.step())
    assert len(stepped) == 12 + 12
    for twin in last.values():
        assert twin.outputs[0].token_ids == whole.outputs[0].token_ids
        assert twin.outputs[0].text == whole.outputs[0].text


def test_an_ignored_prompt_still_answers(tiny_model_dir):
    engine = _engine(tiny_model_dir)
    engine.add_request("long", None, SamplingParams(max_tokens=4),
                       prompt_token_ids=[5] * 300, final_only=True)
    (out,) = engine.step()
    assert out.finished and out.request_id == "long"


def _request(closing):
    return types.SimpleNamespace(transport=types.SimpleNamespace(
        is_closing=lambda: closing()))


def test_the_waiting_handler_sees_its_client_hang_up(monkeypatch):
    monkeypatch.setattr(utils, "DISCONNECT_POLL_S", 0.01)
    aborted = []

    async def go():
        stream = AsyncStream("r", abort_cb=aborted.append)
        looks = []
        request = _request(lambda: looks.append(1) or len(looks) > 2)
        return await utils.final_output(request, stream), looks

    got, looks = asyncio.run(go())
    assert got is None and aborted == ["r"] and len(looks) == 3


def test_the_waiting_handler_gets_the_finished_output_or_the_fault(
        monkeypatch):
    monkeypatch.setattr(utils, "DISCONNECT_POLL_S", 0.01)
    aborted = []
    done = types.SimpleNamespace(finished=True)

    async def go(items):
        stream = AsyncStream("r", abort_cb=aborted.append)

        async def feed():
            await asyncio.sleep(0.03)
            for item in items:
                stream.put(item)
            stream.finish()
        feeding = asyncio.ensure_future(feed())
        try:
            return await utils.final_output(_request(lambda: False),
                                            stream)
        finally:
            await feeding

    # an engine that sent outputs before the end would be waited out
    assert asyncio.run(go([types.SimpleNamespace(finished=False),
                           done])) is done
    assert aborted == []
    try:
        asyncio.run(go([TimeoutError("expired in the queue")]))
    except TimeoutError:
        pass
    else:
        raise AssertionError("the stream's fault was swallowed")
