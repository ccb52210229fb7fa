"""Multimodal EPD: vision encoder, prompt splicing, engine embedding
injection, content-addressed KV hashing, and the encode→prefill→decode
flow over the real pipeline
(ref: components/backends/trtllm multimodal_processor.py + the EPD
request_handlers/handler_base.py:64-234)."""

import asyncio
import base64
import io

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.engine import InferenceEngine, Request
from dynamo_tpu.multimodal import (
    EncodeHandler, MM_MARKER, VisionEncoder, VisionEncoderConfig,
)
from dynamo_tpu.multimodal.processor import (
    MultimodalProcessor, content_token, decode_image_part,
)

pytestmark = pytest.mark.anyio


def image(seed: int, size: int = 32) -> np.ndarray:
    return np.random.RandomState(seed).rand(size, size, 3).astype(np.float32)


def data_url(img: np.ndarray) -> str:
    buf = io.BytesIO()
    np.save(buf, img)
    b64 = base64.b64encode(buf.getvalue()).decode()
    return f"data:application/x-npy;base64,{b64}"


# ------------------------------ encoder -------------------------------


def test_encoder_shapes_and_determinism():
    cfg = VisionEncoderConfig.tiny(model_dim=64)
    enc1 = VisionEncoder(cfg, seed=0)
    enc2 = VisionEncoder(cfg, seed=0)
    img = image(0)
    a, b = enc1.encode(img), enc2.encode(img)
    assert a.shape == (cfg.tokens_per_image, 64)
    np.testing.assert_array_equal(a, b)        # same seed → same weights
    c = enc1.encode(image(1))
    assert not np.allclose(a, c)               # different image differs
    # arbitrary input sizes are resized; uint8 inputs are scaled
    d = enc1.encode((image(0, size=48) * 255).astype(np.uint8))
    assert d.shape == (cfg.tokens_per_image, 64)
    assert np.isfinite(d).all()


def test_image_part_decoding():
    img = image(3)
    part = {"type": "image_url", "image_url": {"url": data_url(img)}}
    np.testing.assert_array_equal(decode_image_part(part), img)
    np.testing.assert_allclose(
        decode_image_part({"type": "image", "array": img.tolist()}), img,
        rtol=1e-6,
    )
    with pytest.raises(ValueError):
        decode_image_part(
            {"type": "image_url", "image_url": {"url": "http://x/y.png"}}
        )


def test_content_token_is_content_addressed():
    a, b = image(0), image(1)
    assert content_token(a, 0) == content_token(a.copy(), 0)
    assert content_token(a, 0) != content_token(b, 0)
    assert content_token(a, 0) != content_token(a, 1)  # per-slot fold
    assert content_token(a, 0) >= (1 << 31)            # clear of vocab ids


# ------------------------------ splicing ------------------------------


class IdTokenizer:
    """ord()-based toy tokenizer for splice tests."""

    bos_token_id = None
    eos_token_ids = ()

    def encode(self, text):
        return [ord(c) % 500 for c in text]


def test_splice_positions_and_hash_ids():
    enc = VisionEncoder(VisionEncoderConfig.tiny(model_dim=64))
    proc = MultimodalProcessor(
        IdTokenizer(), tokens_per_image=enc.config.tokens_per_image,
        local_encoder=enc,
    )
    imgs = [image(0), image(1)]
    rendered = f"ab{MM_MARKER}cd{MM_MARKER}"
    ids, positions, hash_ids = proc.splice(rendered, imgs)
    n = enc.config.tokens_per_image
    assert len(ids) == len(hash_ids) == 4 + 2 * n
    assert positions == list(range(2, 2 + n)) + list(range(4 + n, 4 + 2 * n))
    # placeholder rows are id 0 in model inputs, content hashes in hash ids
    assert all(ids[p] == 0 for p in positions)
    assert all(hash_ids[p] >= (1 << 31) for p in positions)
    # text rows identical in both
    for i in (0, 1, 2 + n, 3 + n):
        assert ids[i] == hash_ids[i] < 500
    with pytest.raises(ValueError, match="markers"):
        proc.splice("no markers", imgs)


# --------------------------- engine injection -------------------------


def tiny_engine():
    return InferenceEngine(
        ModelConfig.tiny(vocab_size=256),
        EngineConfig(num_blocks=128, block_size=4, max_model_len=256,
                     max_num_batched_tokens=256, prefill_buckets=(256,),
                     decode_buckets=(4,), max_num_seqs=4),
    )


async def _mm_run(eng, prompt, positions, embeds, hash_ids, rid):
    req = Request(
        request_id=rid, token_ids=prompt, max_tokens=4, temperature=0.0,
        ignore_eos=True, mm_positions=positions, mm_embeddings=embeds,
        mm_hash_token_ids=hash_ids,
    )
    return [out.token_id async for out in eng.submit(req)]


async def test_engine_mm_injection_and_cache_correctness():
    """Different images behind identical placeholder prompts must produce
    different outputs AND different KV blocks (content-addressed hashing);
    the same image must reuse its blocks and reproduce its output."""
    eng = tiny_engine()
    D = 64
    n = 4
    prompt = [5, 6] + [0] * n + [7, 8]
    positions = list(range(2, 2 + n))
    rng = np.random.RandomState(0)
    emb_a = rng.randn(n, D).astype(np.float32)
    emb_b = rng.randn(n, D).astype(np.float32)
    hash_a = [5, 6] + [(1 << 31) + 100 + j for j in range(n)] + [7, 8]
    hash_b = [5, 6] + [(1 << 31) + 900 + j for j in range(n)] + [7, 8]

    out_a1 = await _mm_run(eng, prompt, positions, emb_a, hash_a, "a1")
    assert eng.num_mm_prefills >= 1
    out_b = await _mm_run(eng, prompt, positions, emb_b, hash_b, "b")
    assert out_a1 != out_b, "different images produced identical streams"
    out_a2 = await _mm_run(eng, prompt, positions, emb_a, hash_a, "a2")
    assert out_a2 == out_a1, "same image failed to reproduce"
    # text-only request with the same placeholder ids must not hit either
    # image's cached blocks
    plain = [out.token_id async for out in eng.submit(Request(
        request_id="plain", token_ids=list(prompt), max_tokens=4,
        temperature=0.0, ignore_eos=True,
    ))]
    assert plain != out_a1 or plain != out_b
    await eng.stop()


async def test_engine_mm_validation():
    eng = tiny_engine()
    with pytest.raises(ValueError, match="mm_hash_token_ids"):
        await _mm_run(eng, [1, 2, 0, 0], [2, 3],
                      np.zeros((2, 64), np.float32), None, "bad")
    await eng.stop()


# ------------------------------ pipeline ------------------------------


async def test_epd_pipeline_end_to_end():
    """Chat request with an image data URL through the REAL pipeline:
    multimodal preprocessor → encode worker endpoint → engine splicing →
    streamed completion; image identity changes the completion."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).parent))
    from test_llm_pipeline import byte_tokenizer

    from dynamo_tpu.llm.discovery import ModelDeploymentCard
    from dynamo_tpu.llm.entrypoint import build_routed_pipeline
    from dynamo_tpu.multimodal.processor import MultimodalProcessor
    from dynamo_tpu.runtime.component import DistributedRuntime
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.runtime.store import StoreServer
    from dynamo_tpu.utils.config import RuntimeConfig

    store = StoreServer(host="127.0.0.1", port=0)
    await store.start()
    cfg = RuntimeConfig(store_addr=f"127.0.0.1:{store.port}")

    worker_rt = await DistributedRuntime.from_settings(cfg)
    engine = tiny_engine()
    await engine.start()
    ns = worker_rt.namespace("mm")
    ep = ns.component("backend").endpoint("generate")
    await ep.serve_endpoint(engine)
    # the colocated encode worker endpoint (EPD encode stage)
    vcfg = VisionEncoderConfig.tiny(model_dim=64)
    await ns.component("backend").endpoint("encode").serve_endpoint(
        EncodeHandler(VisionEncoder(vcfg))
    )

    front_rt = await DistributedRuntime.from_settings(cfg)
    tk = byte_tokenizer()
    card = ModelDeploymentCard(
        name="tiny-mm", tokenizer_json=tk.to_json_str(),
        context_length=256, migration_limit=1,
    )
    gen_client = await (front_rt.namespace("mm").component("backend")
                        .endpoint("generate").client())
    enc_client = await (front_rt.namespace("mm").component("backend")
                        .endpoint("encode").client())
    await gen_client.wait_for_instances(1)
    await enc_client.wait_for_instances(1)
    pipeline = build_routed_pipeline(
        card, gen_client,
        mm_processor=MultimodalProcessor(
            card.load_tokenizer(),
            tokens_per_image=vcfg.tokens_per_image,
            encode_client=enc_client,
        ),
    )

    async def ask(img):
        body = {
            "model": "tiny-mm", "max_tokens": 4, "ignore_eos": True,
            "messages": [{"role": "user", "content": [
                {"type": "text", "text": "describe "},
                {"type": "image_url", "image_url": {"url": data_url(img)}},
            ]}],
        }
        text = ""
        async for out in pipeline.generate(body, Context()):
            text += out.text
        return text

    a1 = await ask(image(0))
    b = await ask(image(1))
    a2 = await ask(image(0))
    assert engine.num_mm_prefills >= 2  # a1 + b prefilled; a2 may hit cache
    assert a1 == a2
    assert a1 != b

    await gen_client.stop()
    await enc_client.stop()
    await engine.stop()
    await front_rt.shutdown()
    await worker_rt.shutdown()
    await store.stop()


async def test_epd_over_processes(tmp_path_factory):
    """Full process topology: worker --mm-encoder (serves generate+encode,
    advertises multimodal in the card) + frontend (wires the multimodal
    preprocessor from discovery) + HTTP chat request with an image."""
    import sys
    from pathlib import Path

    import aiohttp

    sys.path.insert(0, str(Path(__file__).parent))
    from test_llm_pipeline import byte_tokenizer
    from utils import ManagedProcess, free_port

    tok = tmp_path_factory.mktemp("tok") / "tokenizer.json"
    tok.write_text(byte_tokenizer().to_json_str())
    store_port = free_port()
    procs = []
    try:
        store = ManagedProcess(
            ["-m", "dynamo_tpu.runtime.store", "--host", "127.0.0.1",
             "--port", str(store_port)],
            name="store", ready_pattern=r"listening",
        )
        procs.append(store)
        store.wait_ready(20)
        env = {"DYNTPU_STORE_ADDR": f"127.0.0.1:{store_port}"}
        worker = ManagedProcess(
            ["-m", "dynamo_tpu.worker", "--model", "tiny",
             "--model-name", "tiny-mm", "--tokenizer", str(tok),
             "--block-size", "4", "--num-blocks", "128",
             "--max-model-len", "256", "--max-batched-tokens", "256",
             "--mm-encoder"],
            name="worker", env=env, ready_pattern=r"worker ready",
        )
        procs.append(worker)
        worker.wait_ready(90)
        # the frontend binds a port of its own choosing and says which: a
        # port picked before the store and the worker started (20-30 s
        # earlier) can be another process's by now
        frontend = ManagedProcess(
            ["-m", "dynamo_tpu.frontend", "--host", "127.0.0.1",
             "--port", "0"],
            name="frontend", env=env, ready_pattern=r"frontend ready",
        )
        procs.append(frontend)
        frontend.wait_ready(30)
        http_port = int(frontend.wait_log(
            r"frontend ready on \S+:(\d+)").group(1))

        async def ask(img):
            body = {
                "model": "tiny-mm", "max_tokens": 4,
                "messages": [{"role": "user", "content": [
                    {"type": "text", "text": "what is this? "},
                    {"type": "image_url",
                     "image_url": {"url": data_url(img)}},
                ]}],
            }
            async with aiohttp.ClientSession() as s:
                async with s.post(
                    f"http://127.0.0.1:{http_port}/v1/chat/completions",
                    json=body, timeout=aiohttp.ClientTimeout(total=120),
                ) as r:
                    assert r.status == 200, await r.text()
                    out = await r.json()
                    return out["choices"][0]["message"]["content"]

        a = await ask(image(0))
        b = await ask(image(1))
        assert a != b, "image identity did not affect the completion"
        # text-only requests still work through the same pipeline
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"http://127.0.0.1:{http_port}/v1/chat/completions",
                json={"model": "tiny-mm", "max_tokens": 4,
                      "messages": [{"role": "user", "content": "plain"}]},
                timeout=aiohttp.ClientTimeout(total=120),
            ) as r:
                assert r.status == 200, await r.text()
    finally:
        for p in reversed(procs):
            try:
                p.terminate()
            except Exception:
                pass
