"""CLI + HTTP endpoint tests.

The reference's HTTP endpoint answers every inference request with
"Inference not implemented yet" (``server.py:671-678``); ours must actually
infer — including streaming — and the CLI must cover the serve / worker /
plan / generate / bench roles (SURVEY.md §7.9).
"""

import json
import http.client
import io
import threading
from contextlib import redirect_stdout

import numpy as np
import pytest

import jax

from distributed_inference_demo_tpu import cli
from distributed_inference_demo_tpu.models import get_model_config
from distributed_inference_demo_tpu.models.decoder import init_full_params
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime import InferenceEngine
from distributed_inference_demo_tpu.runtime.batching import (
    ContinuousBatchingEngine)
from distributed_inference_demo_tpu.runtime.http_server import (
    InferenceHTTPServer)

GREEDY = SamplingParams(greedy=True)


@pytest.fixture(scope="module")
def http_server():
    cfg = get_model_config("llama-test")
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    engine = InferenceEngine(cfg, params, max_seq=64, sampling=GREEDY)
    server = InferenceHTTPServer(engine, port=0, model_name="llama-test")
    server.start()
    yield server, engine
    server.shutdown()


def _req(server, method, path, body=None):
    conn = http.client.HTTPConnection(server.host, server.port, timeout=60)
    conn.request(method, path,
                 body=json.dumps(body) if body is not None else None,
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def test_health(http_server):
    server, _ = http_server
    status, data = _req(server, "GET", "/health")
    assert status == 200
    body = json.loads(data)
    assert body["status"] == "ok" and body["model"] == "llama-test"


def test_generate_endpoint_matches_engine(http_server):
    server, engine = http_server
    prompt = [[5, 17, 42, 7]]
    status, data = _req(server, "POST", "/generate",
                        {"prompt_ids": prompt, "max_new_tokens": 6})
    assert status == 200
    got = json.loads(data)["tokens"]
    want = engine.generate(np.asarray(prompt), 6).tokens.tolist()
    assert got == want


def test_generate_endpoint_logprobs(http_server):
    server, engine = http_server
    prompt = [[5, 17, 42, 7]]
    status, data = _req(server, "POST", "/generate",
                        {"prompt_ids": prompt, "max_new_tokens": 5,
                         "logprobs": True})
    assert status == 200
    body = json.loads(data)
    assert len(body["logprobs"][0]) == 5
    assert all(lp <= 0 for lp in body["logprobs"][0])
    want = engine.generate(np.asarray(prompt), 5,
                           logprobs=True).logprobs[0]
    np.testing.assert_allclose(body["logprobs"][0], want, atol=1e-5)


def test_generate_endpoint_logprobs_unsupported_backend():
    """Backends without a logprobs parameter get a clean 501, not a 500."""
    from distributed_inference_demo_tpu.runtime.http_server import (
        InferenceHTTPServer)

    class NoLogprobs:
        max_seq = 64

        def generate(self, prompt_ids, max_new_tokens, seed=0):
            raise AssertionError("must not be called")

    server = InferenceHTTPServer(NoLogprobs(), port=0)
    server.start()
    try:
        status, data = _req(server, "POST", "/generate",
                            {"prompt_ids": [[1]], "max_new_tokens": 2,
                             "logprobs": True})
        assert status == 501
        assert "logprobs" in json.loads(data)["error"]
    finally:
        server.shutdown()


def test_generate_endpoint_stream_logprobs(http_server):
    """Streaming with logprobs: each JSONL line carries the step's token
    logprobs, matching the blocking path's values."""
    server, engine = http_server
    prompt = [[5, 17, 42, 7]]
    status, data = _req(server, "POST", "/generate",
                        {"prompt_ids": prompt, "max_new_tokens": 4,
                         "stream": True, "logprobs": True})
    assert status == 200
    lines = [json.loads(l) for l in data.decode().splitlines() if l.strip()]
    assert len(lines) == 4
    want = engine.generate(np.asarray(prompt), 4, logprobs=True).logprobs[0]
    got = np.asarray([l["logprobs"][0] for l in lines])
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_generate_endpoint_stream_logprobs_unsupported_backend():
    """Stream backends without logprobs support still get a clean 501."""
    from distributed_inference_demo_tpu.runtime.http_server import (
        InferenceHTTPServer)

    class NoLogprobsStream:
        max_seq = 64

        def generate_stream(self, prompt_ids, max_new_tokens, seed=0):
            raise AssertionError("must not be called")

    server = InferenceHTTPServer(NoLogprobsStream(), port=0)
    server.start()
    try:
        status, data = _req(server, "POST", "/generate",
                            {"prompt_ids": [[1]], "max_new_tokens": 2,
                             "stream": True, "logprobs": True})
        assert status == 501
        assert "logprobs" in json.loads(data)["error"]
    finally:
        server.shutdown()


def test_generate_endpoint_streaming(http_server):
    server, engine = http_server
    prompt = [[5, 17, 42, 7]]
    status, data = _req(server, "POST", "/generate",
                        {"prompt_ids": prompt, "max_new_tokens": 6,
                         "stream": True})
    assert status == 200
    lines = [json.loads(l) for l in data.decode().strip().splitlines()]
    assert [l["step"] for l in lines] == list(range(6))
    got = [[l["tokens"][0] for l in lines]]
    want = engine.generate(np.asarray(prompt), 6).tokens.tolist()
    assert got == want


def test_stream_capacity_error_is_clean_400(http_server):
    """A capacity error on a stream request must be a clean 400 —
    surfaced from the generator's first step BEFORE the 200 + chunked
    headers are committed (a late error would splice a status line into
    the open chunked body)."""
    server, _ = http_server
    status, data = _req(server, "POST", "/generate",
                        {"prompt_ids": [[1, 2, 3]], "max_new_tokens": 1000,
                         "stream": True})
    assert status == 400 and b"error" in data


def test_generate_endpoint_bad_requests(http_server):
    server, _ = http_server
    status, data = _req(server, "POST", "/generate", {"max_new_tokens": 4})
    assert status == 400 and b"prompt" in data
    status, data = _req(server, "POST", "/generate",
                        {"prompt_ids": [[1, 2]], "max_new_tokens": 1000})
    assert status == 400 and b"capacity" in data.lower() or status == 400
    status, _ = _req(server, "GET", "/nope")
    assert status == 404


def _run_cli(argv):
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli.main(argv)
    return rc, buf.getvalue()


def test_cli_generate_greedy():
    rc, out = _run_cli([
        "generate", "--model", "llama-test", "--prompt-ids", "5,17,42,7",
        "--max-new-tokens", "4", "--greedy", "--max-seq", "64",
        "--attn-backend", "jnp"])
    assert rc == 0
    body = json.loads(out)
    assert len(body["tokens"][0]) == 4

    cfg = get_model_config("llama-test")
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    engine = InferenceEngine(cfg, params, max_seq=64, sampling=GREEDY)
    want = engine.generate(np.asarray([[5, 17, 42, 7]]), 4).tokens.tolist()
    assert body["tokens"] == want


# slow lane: CLI twin of the engine-level self-draft pins in
# test_speculative; the generate surface stays quick via the greedy test
@pytest.mark.slow
def test_cli_generate_speculative_self_draft():
    """generate --draft-model with draft == target (same seed-init) must
    reproduce plain greedy output exactly with 100% acceptance."""
    argv_tail = ["--model", "llama-test", "--prompt-ids", "5,17,42,7",
                 "--max-new-tokens", "6", "--greedy", "--max-seq", "64",
                 "--attn-backend", "jnp"]
    rc, plain = _run_cli(["generate"] + argv_tail)
    assert rc == 0
    rc, spec = _run_cli(["generate"] + argv_tail +
                        ["--draft-model", "llama-test", "--num-draft", "3"])
    assert rc == 0
    plain, spec = json.loads(plain), json.loads(spec)
    assert spec["tokens"] == plain["tokens"]
    assert spec["speculative"]["acceptance_rate"] == 1.0
    assert spec["speculative"]["tokens_per_round"] > 1.0


@pytest.mark.slow
def test_cli_generate_prompt_lookup():
    """--prompt-lookup greedy must match plain greedy; exclusive with
    --draft-model."""
    argv_tail = ["--model", "llama-test", "--prompt-ids", "5,17,42,7",
                 "--max-new-tokens", "8", "--greedy", "--max-seq", "64",
                 "--attn-backend", "jnp"]
    rc, plain = _run_cli(["generate"] + argv_tail)
    assert rc == 0
    rc, pld = _run_cli(["generate"] + argv_tail + ["--prompt-lookup"])
    assert rc == 0
    plain, pld = json.loads(plain), json.loads(pld)
    assert pld["tokens"] == plain["tokens"]
    assert "speculative" in pld
    rc, _ = _run_cli(["generate"] + argv_tail +
                     ["--prompt-lookup", "--draft-model", "llama-test"])
    assert rc == 1


@pytest.mark.slow
def test_cli_generate_tp():
    """generate --tp 2 on the virtual mesh matches single-device greedy;
    --tp combined with another serve mode is rejected."""
    argv_tail = ["--model", "llama-test", "--prompt-ids", "5,17,42,7",
                 "--max-new-tokens", "6", "--greedy", "--max-seq", "64",
                 "--attn-backend", "jnp"]
    rc, plain = _run_cli(["generate"] + argv_tail)
    assert rc == 0
    rc, tp = _run_cli(["generate"] + argv_tail[:-2] + ["--tp", "2"])
    assert rc == 0
    assert json.loads(tp)["tokens"] == json.loads(plain)["tokens"]
    # --tp composes with speculation modes too
    rc, tp_pld = _run_cli(["generate"] + argv_tail[:-2] +
                          ["--tp", "2", "--prompt-lookup"])
    assert rc == 0
    assert json.loads(tp_pld)["tokens"] == json.loads(plain)["tokens"]


def test_cli_plan_and_cache(tmp_path):
    devices = [
        {"device_id": "cpu0", "address": "127.0.0.1:7000",
         "flops_per_sec": 1e11, "platform": "cpu"},
        {"device_id": "tpu0", "address": "127.0.0.1:7001",
         "flops_per_sec": 2e14, "platform": "tpu", "chips": 4},
    ]
    dev_file = tmp_path / "devices.json"
    dev_file.write_text(json.dumps(devices))
    plan_file = tmp_path / "plan.json"

    rc, out = _run_cli(["plan", "--model", "llama-test",
                        "--devices", str(dev_file),
                        "--save", str(plan_file)])
    assert rc == 0
    plan = json.loads(out)
    ranges = [tuple(s["layers"]) for s in plan["stages"]]
    assert ranges[0][0] == 0 and ranges[-1][1] == 4
    # the TPU device (2000x the FLOPs) must get at least as many layers
    n0 = ranges[0][1] - ranges[0][0]
    n1 = ranges[1][1] - ranges[1][0]
    assert n1 >= n0
    assert plan_file.exists()

    rc, out = _run_cli(["plan", "--model", "llama-test",
                        "--load", str(plan_file)])
    assert rc == 0
    assert json.loads(out) == plan


def test_chat_repl_streams_incrementally(http_server, monkeypatch):
    """The chat REPL (L7: the reference's ChatScreen loop as a terminal
    app) must render tokens chunk by chunk — incremental arrivals, ending
    with the exact greedy tokens the engine produces."""
    import time as _time

    server, engine = http_server
    prompt = [[5, 17, 42, 7]]
    want = engine.generate(np.asarray(prompt), 6).tokens

    # stream_generate yields one parsed line per arrived chunk
    arrivals = []
    lines = []
    for item in cli.stream_generate(server.host, server.port,
                                    {"prompt_ids": prompt,
                                     "max_new_tokens": 6}):
        arrivals.append(_time.perf_counter())
        lines.append(item)
    assert [l["step"] for l in lines] == list(range(6))
    assert [l["tokens"][0] for l in lines] == want[0].tolist()
    assert arrivals[0] < arrivals[-1]   # first chunk before completion

    # full REPL e2e: two turns then /quit, token ids rendered in order
    monkeypatch.setattr(cli.sys, "stdin",
                        io.StringIO("5,17,42,7\n5,17,42,7\n/quit\n"))
    rc, out = _run_cli(["chat", "--url",
                        f"http://{server.host}:{server.port}",
                        "--max-new-tokens", "6", "--ids"])
    assert rc == 0
    rendered = " ".join(str(t) for t in want[0].tolist())
    assert out.count(rendered) == 2


def test_load_full_params_honors_checkpoint(tmp_path):
    """ADVICE r1 #1: the serve --chain path must load --checkpoint weights,
    not silently seed-init.  Both serve branches go through
    _load_full_params; assert it returns the checkpointed tree, which is
    distinguishable from every seed-init."""
    import argparse

    from distributed_inference_demo_tpu.checkpoint import save_params

    cfg = get_model_config("llama-test")
    params = init_full_params(jax.random.PRNGKey(123), cfg)
    # perturb so the tree cannot equal ANY seed-init
    params.embed["tokens"] = params.embed["tokens"] + 1.5
    ckpt = str(tmp_path / "ckpt")
    save_params(ckpt, params, cfg, model_name="llama-test")

    args = argparse.Namespace(model="llama-test", checkpoint=ckpt,
                              weights_seed=0)
    loaded = cli._load_full_params(args, cfg)
    np.testing.assert_allclose(np.asarray(loaded.embed["tokens"]),
                               np.asarray(params.embed["tokens"]))

    args_no = argparse.Namespace(model="llama-test", checkpoint=None,
                                 weights_seed=0)
    seeded = cli._load_full_params(args_no, cfg)
    assert not np.allclose(np.asarray(seeded.embed["tokens"]),
                           np.asarray(loaded.embed["tokens"]))


def test_cli_bench_runs():
    rc, out = _run_cli([
        "bench", "--model", "llama-test", "--batch", "2",
        "--prompt-len", "8", "--max-new-tokens", "4", "--max-seq", "32",
        "--attn-backend", "jnp"])
    assert rc == 0
    body = json.loads(out)
    assert body["unit"] == "tokens/sec" and body["value"] > 0


@pytest.mark.slow
def test_cli_bench_prompt_lookup():
    """bench --prompt-lookup reports baseline + speculative tok/s with
    acceptance stats on one workload."""
    rc, out = _run_cli([
        "bench", "--model", "llama-test", "--batch", "2",
        "--prompt-len", "8", "--max-new-tokens", "8", "--greedy",
        "--max-seq", "64", "--attn-backend", "jnp", "--prompt-lookup",
        "--num-draft", "3"])
    assert rc == 0
    body = json.loads(out)
    assert body["value"] > 0
    spec = body["speculative"]
    assert spec["tokens_per_sec"] > 0 and spec["speedup"] > 0
    assert spec["rounds"] >= 1


def test_serve_mode_pairing_rules(capsys):
    """--batch-slots composes with --draft-model; every other mode pair
    stays an explicit one-line error."""
    base = ["serve", "--model", "llama-test"]
    assert cli.main(base + ["--chain", "w@127.0.0.1:1",
                            "--batch-slots", "2"]) == 1
    assert cli.main(base + ["--draft-model", "llama-test",
                            "--prompt-lookup"]) == 1
    assert cli.main(base + ["--chain", "w@127.0.0.1:1",
                            "--prompt-lookup"]) == 1
    # --no-spec-adaptive pins K_row in the mixed slot loop; outside
    # serve --batch-slots + a proposer it would silently do nothing
    assert cli.main(base + ["--no-spec-adaptive"]) == 1
    assert cli.main(base + ["--batch-slots", "2",
                            "--no-spec-adaptive"]) == 1
    assert cli.main(["generate", "--model", "llama-test",
                     "--prompt-ids", "1,2", "--no-spec-adaptive"]) == 1
    capsys.readouterr()


@pytest.mark.parametrize("argv", [
    ["serve", "--model", "llama-test", "--kv-layout", "paged"],
    ["worker", "--model", "llama-test", "--stage-id", "1",
     "--num-stages", "2", "--layer-start", "0", "--layer-end", "2",
     "--device-id", "w", "--port", "0", "--header", "h@127.0.0.1:1",
     "--kv-layout", "paged"],
], ids=["serve", "worker"])
def test_a_removed_flag_is_argparse_own_error(argv, capsys):
    """A KV cache has one layout and no flag asks which: ``--kv-layout``
    (one legal value since the dense layout went) is an unrecognized
    argument like any other, exit 2, before anything is built; ``serve``
    drops no flag it does not know."""
    with pytest.raises(SystemExit) as exc:
        cli.main(argv)
    assert exc.value.code == 2
    assert "unrecognized arguments: --kv-layout paged" in (
        capsys.readouterr().err)


@pytest.mark.slow
def test_http_batching_with_draft(http_server):
    """The composed serving shape (continuous batching x speculative
    decoding) over HTTP: greedy output matches the plain engine, /stats
    reports acceptance."""
    _, engine = http_server
    backend = ContinuousBatchingEngine(
        engine.cfg, engine.params, max_seq=64, max_batch=2,
        sampling=GREEDY, prompt_buckets=(16,), draft_cfg=engine.cfg,
        draft_params=engine.params, num_draft=3)
    server = InferenceHTTPServer(backend, port=0, model_name="llama-test")
    server.start()
    try:
        prompt = [[5, 17, 42, 7]]
        status, data = _req(server, "POST", "/generate",
                            {"prompt_ids": prompt, "max_new_tokens": 6})
        assert status == 200
        want = engine.generate(np.asarray(prompt), 6).tokens.tolist()
        assert json.loads(data)["tokens"] == want
        status, stats = _req(server, "GET", "/stats")
        assert status == 200
        assert json.loads(stats)["speculative"]["acceptance_rate"] == 1.0
    finally:
        server.shutdown()
        backend.close()


@pytest.mark.slow
def test_cli_generate_sp_matches_plain():
    """generate --sp 2 (ring AND ulysses) on the virtual mesh must equal
    plain greedy decode; non-divisible prompts and mode mixing are
    rejected with one-line errors."""
    ids = ",".join(str(i % 250) for i in range(16))
    argv = ["generate", "--model", "llama-test", "--prompt-ids", ids,
            "--max-new-tokens", "6", "--greedy", "--max-seq", "32"]
    rc, plain = _run_cli(argv + ["--attn-backend", "jnp"])
    assert rc == 0
    for strategy in ("ring", "ulysses"):
        rc, out = _run_cli(argv + ["--sp", "2", "--sp-strategy", strategy])
        assert rc == 0
        assert json.loads(out)["tokens"] == json.loads(plain)["tokens"]
    # --kv-cache-dtype composes with --sp: parity vs the plain engine
    # with the SAME reduced cache dtype (attention reads what the cache
    # stores on both sides)
    rc, plain_fp8 = _run_cli(argv + ["--kv-cache-dtype", "float8_e4m3fn"])
    assert rc == 0
    rc, out = _run_cli(argv + ["--sp", "2",
                               "--kv-cache-dtype", "float8_e4m3fn"])
    assert rc == 0
    assert json.loads(out)["tokens"] == json.loads(plain_fp8)["tokens"]
    # flags the sp paths have no plumbing for are rejected loudly
    for extra in (["--eos-id", "7"], ["--attn-backend", "jnp"]):
        rc, _ = _run_cli(argv + ["--sp", "2"] + extra)
        assert rc == 1
    # 15 tokens don't shard over sp=2
    bad = ",".join(str(i % 250) for i in range(15))
    rc, _ = _run_cli(["generate", "--model", "llama-test", "--prompt-ids",
                      bad, "--max-new-tokens", "4", "--greedy",
                      "--max-seq", "32", "--sp", "2"])
    assert rc == 1
    rc, _ = _run_cli(argv + ["--sp", "2", "--prompt-lookup"])
    assert rc == 1


# slow lane: HTTP twin of the engine-level pld parity pins in
# test_batching; the HTTP batching surface stays quick elsewhere
@pytest.mark.slow
def test_http_batching_with_prompt_lookup(http_server):
    """Continuous batching x draft-free speculation over HTTP: greedy
    output matches the plain engine, /stats names the proposer."""
    _, engine = http_server
    backend = ContinuousBatchingEngine(
        engine.cfg, engine.params, max_seq=64, max_batch=2,
        sampling=GREEDY, prompt_buckets=(16,), prompt_lookup=True,
        num_draft=3)
    server = InferenceHTTPServer(backend, port=0, model_name="llama-test")
    server.start()
    try:
        prompt = [[5, 17, 42, 7]]
        status, data = _req(server, "POST", "/generate",
                            {"prompt_ids": prompt, "max_new_tokens": 6})
        assert status == 200
        want = engine.generate(np.asarray(prompt), 6).tokens.tolist()
        assert json.loads(data)["tokens"] == want
        status, stats = _req(server, "GET", "/stats")
        assert json.loads(stats)["speculative"]["proposer"] == \
            "prompt_lookup"
    finally:
        server.shutdown()
        backend.close()


def test_chat_streaming_detok_holds_back_split_utf8(monkeypatch):
    """Incremental detokenization: a multi-byte UTF-8 char split across
    two tokens renders once, complete — never as replacement chars."""
    import io
    from contextlib import redirect_stdout

    class FakeTok:
        def encode(self, text):
            return [1]

        def decode(self, ids, skip_special=True):
            frag = {1: b"a", 2: b"\xc3", 3: b"\xa9"}   # 2+3 = "é"
            return b"".join(frag[int(i)] for i in ids).decode(
                "utf-8", errors="replace")

    def fake_stream(host, port, payload):
        yield {"step": 0, "tokens": [2]}
        yield {"step": 1, "tokens": [3]}

    monkeypatch.setattr(cli, "_load_tokenizer", lambda p: FakeTok())
    monkeypatch.setattr(cli, "stream_generate", fake_stream)
    monkeypatch.setattr(cli.sys, "stdin", io.StringIO("hi\n/quit\n"))
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli.main(["chat", "--url", "http://127.0.0.1:1",
                       "--tokenizer", "fake"])
    assert rc == 0
    out = buf.getvalue()
    assert "é" in out and "�" not in out


def test_stop_matcher_fuzz():
    """StopMatcher vs a whole-string reference over random texts, stop
    sets, and chunkings — INCLUDING per-token (1-char) feeds: identical
    cut positions regardless of chunking (the chunk-dependent-cut bug:
    a short stop completing while an earlier-starting longer stop is
    still a live prefix must defer, ADVICE r5), and emitted text never
    contains anything later retracted (the streaming holdback
    guarantee)."""
    import random

    from distributed_inference_demo_tpu.runtime.http_server import (
        StopMatcher)

    rng = random.Random(7)
    for _ in range(300):
        text = "".join(rng.choice("abc") for _ in range(rng.randint(0, 40)))
        stops = ["".join(rng.choice("abc")
                         for _ in range(rng.randint(1, 4)))
                 for _ in range(rng.randint(1, 3))]
        hits = [text.find(s) for s in stops if s in text]
        ref_pos = min(hits) if hits else None

        # every chunking — per-char, random, whole-string — must agree
        # with the whole-string reference on (pos, emitted)
        chunkings = [1, None, len(text) or 1]
        for chunk in chunkings:
            m = StopMatcher(stops)
            outs, matched = [], False
            i = 0
            while i < len(text) and not matched:
                j = i + (chunk if chunk else rng.randint(1, 5))
                out, matched = m.feed(text[i:j])
                outs.append(out)
                i = j
            if not matched:
                # stream over: resolve any deferred verdict
                out, matched = m.finish()
                outs.append(out)
            if ref_pos is None:
                assert not matched and m.pos is None
                assert "".join(outs) == text
            else:
                assert matched and m.pos == ref_pos, (text, stops, chunk)
                assert "".join(outs) == text[:ref_pos]


def test_stop_matcher_defers_short_stop_inside_longer_candidate():
    """The ADVICE r5 repro pinned: stop=["abc", "b"] fed "a" then "b"
    must NOT cut at 1 while "ab" can still become "abc" — the verdict
    defers (bounded by the longest stop) and resolves identically to
    whole-string feeding whichever way the tail goes."""
    from distributed_inference_demo_tpu.runtime.http_server import (
        StopMatcher)

    # tail completes the longer stop: cut at 0, like feeding "abc" whole
    m = StopMatcher(["abc", "b"])
    assert m.feed("a") == ("", False)
    out, matched = m.feed("b")
    assert not matched and out == ""      # deferred, nothing emitted
    out, matched = m.feed("c")
    assert matched and m.pos == 0 and out == ""

    # tail kills the longer candidate: the short stop's cut stands
    m = StopMatcher(["abc", "b"])
    m.feed("a")
    m.feed("b")
    out, matched = m.feed("x")
    assert matched and m.pos == 1 and out == "a"

    # stream ends while deferred: finish() resolves to the short stop
    m = StopMatcher(["abc", "b"])
    m.feed("a")
    m.feed("b")
    out, matched = m.finish()
    assert matched and m.pos == 1 and out == "a"


def test_cli_kvcache_flags():
    """--kv-cache-blocks plumbs into generate, defers to DWT_KVCACHE_*
    env knobs when unset, and is REJECTED (not silently ignored) by
    modes with no block-cache plumbing."""
    argv = ["generate", "--model", "llama-test", "--prompt-ids",
            ",".join(str(i) for i in range(20)), "--max-new-tokens", "4",
            "--greedy", "--max-seq", "64", "--attn-backend", "jnp"]
    rc, plain = _run_cli(argv)
    assert rc == 0
    rc, cached = _run_cli(argv + ["--kv-cache-blocks", "16",
                                  "--kv-block-tokens", "4"])
    assert rc == 0
    # single cold run: the cache changes nothing about the output
    assert json.loads(cached)["tokens"] == json.loads(plain)["tokens"]
    # the prompt-lookup engine gained block-cache plumbing with the
    # universal-paged refactor (docs/DESIGN.md §14): the flags compose
    rc, pld_out = _run_cli(argv + ["--kv-cache-blocks", "16",
                                   "--kv-block-tokens", "4",
                                   "--prompt-lookup"])
    assert rc == 0 and "tokens" in json.loads(pld_out)
    # stage workers still reject the flags loudly (activations have no
    # prompt key to match blocks by — a layout question, not this one)
    rc, _ = _run_cli(["worker", "--model", "llama-test", "--stage-id",
                      "0", "--num-stages", "1", "--layer-start", "0",
                      "--layer-end", "1", "--device-id", "w0", "--port",
                      "1", "--header", "h@127.0.0.1:1",
                      "--kv-cache-blocks", "8"])
    assert rc == 1


def test_cli_serve_batching_kvcache_env_default(monkeypatch):
    """DWT_KVCACHE_BLOCKS steers the batching engine when the flag is
    absent (env knob parity with --kv-cache-blocks)."""
    cfg = get_model_config("llama-test")
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    monkeypatch.setenv("DWT_KVCACHE_BLOCKS", "5")
    monkeypatch.setenv("DWT_KVCACHE_BLOCK_TOKENS", "4")
    with ContinuousBatchingEngine(cfg, params, max_seq=64, max_batch=2,
                                  sampling=GREEDY,
                                  prompt_buckets=(16,)) as eng:
        assert eng.kv_cache is not None
        assert eng.kv_cache.num_blocks == 5
        assert eng.kv_cache.block_tokens == 4
    monkeypatch.setenv("DWT_KVCACHE_BLOCKS", "0")
    with ContinuousBatchingEngine(cfg, params, max_seq=64, max_batch=2,
                                  sampling=GREEDY,
                                  prompt_buckets=(16,)) as eng:
        # 0 = the dense-equivalent default pool (the paged-native
        # scheduler has no cache-off mode: the pool IS the decode cache)
        assert (eng.kv_cache.num_blocks
                == eng.max_batch * eng._table_width)


def test_stop_matcher_empty_stop_list_passes_through():
    """An empty stop set is a valid no-op matcher (pure pass-through),
    not a construction error."""
    from distributed_inference_demo_tpu.runtime.http_server import (
        StopMatcher)
    m = StopMatcher([])
    assert m.feed("hello") == ("hello", False)
    out, matched = m.finish()
    assert out == "" and not matched and m.pos is None
