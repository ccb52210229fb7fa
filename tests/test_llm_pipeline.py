"""Tokenizer / preprocessor / backend operator tests.

Uses a byte-level tokenizer (1 token = 1 byte) so multi-byte UTF-8 codepoints
split across tokens — the hard case for incremental detokenization."""

import pytest

from dynamo_tpu.llm.backend import Backend
from dynamo_tpu.llm.preprocessor import Preprocessor, PromptTemplate
from dynamo_tpu.llm.protocols import BackendOutput, PreprocessedRequest
from dynamo_tpu.llm.tokenizer import byte_tokenizer  # noqa: F401 — other test files import it from here
from dynamo_tpu.runtime.context import Context


# ----------------------------- tokenizer ----------------------------------


def test_encode_decode_roundtrip():
    tk = byte_tokenizer()
    ids = tk.encode("hello wörld")
    assert tk.decode(ids) == "hello wörld"
    assert len(ids) == len("hello wörld".encode())  # byte-level


def test_incremental_detok_multibyte():
    tk = byte_tokenizer()
    ids = tk.encode("héllo")  # é = 2 bytes = 2 tokens
    stream = tk.stream()
    text = ""
    deltas = []
    for t in ids:
        d = stream.push([t])
        deltas.append(d)
        text += d
    assert text == "héllo"
    # the first byte of é must NOT emit a replacement char
    assert all("�" not in d for d in deltas)
    # at least one push mid-codepoint returned empty
    assert "" in deltas


def test_detok_flush_incomplete():
    tk = byte_tokenizer()
    ids = tk.encode("é")
    stream = tk.stream()
    assert stream.push(ids[:1]) == ""      # half a codepoint: held back
    assert "�" in stream.flush() or stream.flush() == ""


def test_detok_emoji_4byte():
    tk = byte_tokenizer()
    ids = tk.encode("a🙂b")
    stream = tk.stream()
    text = "".join(stream.push([t]) for t in ids)
    assert text == "a🙂b"


# ---------------------------- preprocessor --------------------------------


def test_prompt_template_default():
    t = PromptTemplate()
    out = t.render([{"role": "user", "content": "hi"}])
    assert "<|user|>" in out and out.endswith("<|assistant|>\n")


def test_prompt_template_custom():
    t = PromptTemplate(
        "{% for m in messages %}[{{ m['role'] }}]{{ m['content'] }}"
        "{% endfor %}"
    )
    assert t.render([{"role": "user", "content": "x"}]) == "[user]x"


@pytest.mark.anyio
async def test_preprocessor_chat():
    tk = byte_tokenizer()
    pre = Preprocessor(tk, model_name="m", default_max_tokens=32)
    req = await pre.forward(
        {"messages": [{"role": "user", "content": "hi"}],
         "temperature": 0.5, "stop": "END", "max_tokens": 7},
        Context(),
    )
    assert isinstance(req, PreprocessedRequest)
    assert tk.decode(req.token_ids).startswith("<|user|>")
    assert req.sampling.temperature == 0.5
    assert req.stop.stop == ["END"]
    assert req.stop.max_tokens == 7


@pytest.mark.anyio
async def test_preprocessor_completion_text_and_tokens():
    tk = byte_tokenizer()
    pre = Preprocessor(tk)
    r1 = await pre.forward({"prompt": "abc"}, Context())
    assert tk.decode(r1.token_ids) == "abc"
    r2 = await pre.forward({"prompt": [5, 6, 7]}, Context())
    assert r2.token_ids == [5, 6, 7]


@pytest.mark.anyio
async def test_preprocessor_context_overflow():
    tk = byte_tokenizer()
    pre = Preprocessor(tk, max_context_len=4)
    with pytest.raises(ValueError):
        await pre.forward({"prompt": "too long prompt"}, Context())


# ------------------------------ backend -----------------------------------


async def _engine_stream(token_batches, finish="length"):
    for i, toks in enumerate(token_batches):
        last = i == len(token_batches) - 1
        yield {"token_ids": toks, "index": i, "finished": last,
               "finish_reason": finish if last else None,
               "num_prompt_tokens": 3}


async def _collect(backend, req, stream, ctx=None):
    out = []
    async for o in backend.backward(stream, req, ctx or Context()):
        out.append(o)
    return out


def _req(tk, text_prompt="xyz", **stop_kw):
    import dataclasses

    from dynamo_tpu.llm.protocols import StopConditions

    return PreprocessedRequest(
        token_ids=tk.encode(text_prompt),
        stop=StopConditions(**stop_kw),
    )


@pytest.mark.anyio
async def test_backend_detokenizes_stream():
    tk = byte_tokenizer()
    b = Backend(tk)
    ids = tk.encode("hello world")
    outs = await _collect(
        b, _req(tk), _engine_stream([[t] for t in ids])
    )
    assert "".join(o.text for o in outs) == "hello world"
    assert outs[-1].finish_reason == "length"
    assert outs[-1].cum_tokens == len(ids)


@pytest.mark.anyio
async def test_backend_stop_string_spanning_deltas():
    tk = byte_tokenizer()
    b = Backend(tk)
    ids = tk.encode("abcSTOPdef")
    ctx = Context()
    outs = await _collect(
        b, _req(tk, stop=["STOP"]), _engine_stream([[t] for t in ids]), ctx
    )
    text = "".join(o.text for o in outs)
    assert text == "abc"                      # truncated at the stop string
    assert outs[-1].finish_reason == "stop"
    assert ctx.is_stopped()                   # downstream cancelled


@pytest.mark.anyio
async def test_backend_forward_merges_stop_token_ids():
    tk = byte_tokenizer()
    b = Backend(tk)
    req = _req(tk, eos_token_ids=[1], stop_token_ids=[9])
    wire = await b.forward(req, Context())
    assert wire["eos_token_ids"] == [1, 9]
    assert wire["token_ids"] == req.token_ids
