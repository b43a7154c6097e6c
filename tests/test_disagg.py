"""Disaggregated prefill/decode with KV page migration (DESIGN.md §15).

The ISSUE-8 invariants, pinned:

- a request served through prefill-worker → page-migration →
  decode-worker join produces a greedy stream BIT-IDENTICAL to the
  colocated engines (the migrated pages hold exactly the K/V the
  decode engine's own cold prefill would write);
- the decode-side join is an ownership ADOPTION: zero page leaks on
  both pools (idle ``used_blocks == tree.block_count``), and
  ``dwt_kvcache_h2d_bytes_total`` stays 0 on the decode side (the
  adopt is a device scatter + block-table reference, never a
  dense-row host gather);
- migration frames are idempotent under duplication (the (rid,
  attempt, seq) dedup) and stale attempts are discarded;
- both roles surface migration state on their debug surfaces.

The chaos-side invariants (faulted migration, prefill crash
rescheduling) live in tests/test_chaos.py.
"""

import threading
import time

import numpy as np
import pytest

import jax

from distributed_inference_demo_tpu.comm import wire
from distributed_inference_demo_tpu.comm.transport import (
    LoopbackNetwork, LoopbackTransport)
from distributed_inference_demo_tpu.models import get_model_config
from distributed_inference_demo_tpu.models.decoder import init_full_params
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime import InferenceEngine
from distributed_inference_demo_tpu.runtime.batching import (
    ContinuousBatchingEngine)
from distributed_inference_demo_tpu.runtime.disagg import (
    DecodeWorker, DisaggCoordinator, PrefillWorker, _meta_frame,
    _page_frame, _parse_meta_frame)

GREEDY = SamplingParams(greedy=True)
MODEL = "llama-test"


@pytest.fixture(scope="module")
def cfg_params():
    cfg = get_model_config(MODEL)
    return cfg, init_full_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def fabric(cfg_params):
    """One loopback disagg deployment shared by the e2e tests: a
    coordinator, one prefill worker, one decode worker (2 slots)."""
    cfg, params = cfg_params
    net = LoopbackNetwork()
    tc = LoopbackTransport("coord", net)
    tp = LoopbackTransport("p0", net)
    td = LoopbackTransport("d0", net)
    engine = ContinuousBatchingEngine(
        cfg, params, max_seq=64, max_batch=2, sampling=GREEDY,
        kv_cache_blocks=0)
    pw = PrefillWorker(cfg, params, tp, max_seq=64, prefill_chunk=8)
    dw = DecodeWorker(engine, td)
    threads = [threading.Thread(target=w.serve_forever, daemon=True)
               for w in (pw, dw)]
    for t in threads:
        t.start()
    coord = DisaggCoordinator(tc, ["p0"], "d0")
    yield coord, pw, dw, engine
    pw.stop()
    dw.stop()
    coord.close()
    engine.close()


@pytest.fixture(scope="module")
def reference(cfg_params):
    cfg, params = cfg_params
    eng = InferenceEngine(cfg, params, max_seq=64, sampling=GREEDY)

    def run(prompt, max_new):
        return eng.generate(prompt[None], max_new).tokens[0]
    return run


def _assert_no_pool_leaks(pw, engine):
    """Idle ownership invariant on BOTH pools: every allocated page is
    tree-owned (request pages freed at completion, adopted pages
    transferred) — bounded wait for the async completions."""
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        d = engine.kv_cache.snapshot()
        p = pw.kv_cache.snapshot()
        if (d["blocks_used"] == d["tree_blocks"]
                and p["blocks_used"] == p["tree_blocks"]):
            return
        time.sleep(0.05)
    raise AssertionError(
        f"page leak: decode {d['blocks_used']}/{d['tree_blocks']}, "
        f"prefill {p['blocks_used']}/{p['tree_blocks']}")


# ---------------------------------------------------------------------------
# frame codec + dedup units


def test_migration_frame_roundtrip_with_trace():
    k = np.arange(2 * 3 * 2 * 4 * 5, dtype=np.float32).reshape(
        2, 3, 2, 4, 5)
    v = -k
    body = _page_frame(k, v, first_block=7, trace=(0xABCD, 42))
    meta, tensors, ctx = _parse_meta_frame(body)
    assert meta == {"first_block": 7, "n_blocks": 2}
    np.testing.assert_array_equal(tensors[0], k)
    np.testing.assert_array_equal(tensors[1], v)
    assert ctx == (0xABCD, 42)
    # CRC: a flipped byte is detected, never decoded
    bad = bytearray(body)
    bad[len(bad) // 2] ^= 0x40
    with pytest.raises(wire.WireError):
        _parse_meta_frame(bytes(bad))


def test_decode_worker_dedups_and_discards_stale_attempts(cfg_params):
    """(rid, attempt, seq) dedup: a duplicated page frame is dropped
    (idempotent retries), a reorder hole is dropped (go-back-n
    refills), and a newer attempt supersedes the staged older one."""
    cfg, params = cfg_params

    class _FakeEngine:
        def submit_premigrated(self, *a, **k):
            raise AssertionError("no join expected in this test")

    net = LoopbackNetwork()
    td = LoopbackTransport("dx", net)
    LoopbackTransport("px", net)
    dw = DecodeWorker(_FakeEngine(), td)
    blk = np.zeros((1, cfg.num_layers, cfg.num_kv_heads, 16,
                    cfg.head_dim), np.float32)
    f0 = _page_frame(blk, blk, 0)
    assert dw.handle_message("pg:r9:0:0", f0)
    assert dw._staged["r9"]["expected"] == 1
    dw.handle_message("pg:r9:0:0", f0)          # duplicate: dropped
    assert dw._staged["r9"]["expected"] == 1
    dw.handle_message("pg:r9:0:3", f0)          # hole: dropped
    assert dw._staged["r9"]["expected"] == 1
    assert dw.stats["dropped_frames"] == 2
    # a NEWER attempt supersedes the staged one...
    dw.handle_message("pg:r9:1:0", f0)
    assert dw._staged["r9"]["attempt"] == 1
    assert dw._staged["r9"]["expected"] == 1
    # ...and the stale attempt's late frames are discarded
    dw.handle_message("pg:r9:0:1", f0)
    assert dw._staged["r9"]["attempt"] == 1
    assert dw.stats["dropped_frames"] == 3


# ---------------------------------------------------------------------------
# the loopback e2e (the -m quick disagg rep)


@pytest.mark.quick
def test_disagg_loopback_bit_identical_and_leak_free(reference, fabric):
    """THE tentpole scenario at test scale: prefill worker → per-chunk
    page migration → decode-side adopt + join, greedy output
    bit-identical to the colocated reference, zero page leaks on both
    pools, zero decode-side H2D for the migrated pages."""
    coord, pw, dw, engine = fabric
    prompt = (np.arange(37) % 50 + 3).astype(np.int32)
    want = reference(prompt, 8)
    req = coord.submit(prompt, 8)
    got = req.wait(timeout=120)
    np.testing.assert_array_equal(got, want)
    assert req.ttft_s is not None and req.ttft_s > 0
    assert pw.stats["migrated_pages"] >= 2
    assert dw.stats["adopted_pages"] == pw.stats["migrated_pages"]
    assert engine.kv_cache.snapshot()["h2d_bytes"] == 0
    assert engine.disagg_stats["premigrated_requests"] >= 1
    _assert_no_pool_leaks(pw, engine)


def test_disagg_repeat_prompt_migrates_from_prefill_cache(reference,
                                                          fabric):
    """A repeat prompt hits the prefill worker's radix tree: the pages
    migrate straight out of its pool (zero recompute) and the output
    stays bit-identical."""
    coord, pw, dw, engine = fabric
    prompt = (np.arange(41) % 61 + 2).astype(np.int32)
    want = reference(prompt, 6)
    hits_before = pw.kv_cache.stats["hits"]
    np.testing.assert_array_equal(
        coord.submit(prompt, 6).wait(timeout=120), want)
    np.testing.assert_array_equal(
        coord.submit(prompt, 6).wait(timeout=120), want)
    assert pw.kv_cache.stats["hits"] > hits_before
    _assert_no_pool_leaks(pw, engine)


def test_disagg_short_prompt_degrades_to_plain_submit(reference,
                                                      fabric):
    """A prompt with no migratable whole block (len <= block_tokens)
    ships zero pages and joins as an ordinary cold admission."""
    coord, pw, dw, engine = fabric
    prompt = np.asarray([7, 9, 11], np.int32)
    want = reference(prompt, 6)
    np.testing.assert_array_equal(
        coord.submit(prompt, 6).wait(timeout=120), want)
    _assert_no_pool_leaks(pw, engine)


def test_disagg_join_rejection_fails_request_not_worker(reference,
                                                        fabric):
    """A decode-side admission rejection (here: the capacity bound) is
    a per-REQUEST failure surfaced through fin — the decode worker's
    serve loop survives and keeps joining later migrations."""
    coord, pw, dw, engine = fabric
    prompt = (np.arange(37) % 50 + 3).astype(np.int32)
    req = coord.submit(prompt, 60)       # 37 + 60 > max_seq 64
    with pytest.raises(RuntimeError, match="exceeds KV-cache capacity"):
        req.wait(timeout=120)
    # the worker is alive: a well-sized request still serves
    want = reference(prompt, 4)
    np.testing.assert_array_equal(
        coord.submit(prompt, 4).wait(timeout=120), want)
    _assert_no_pool_leaks(pw, engine)


def test_disagg_debug_surfaces_migration_state(fabric):
    """The /debugz satellite: all three roles name their migration
    state — in-flight handoffs, staged/adopted pages, last migration
    latency — so a wedged handoff is observable from a scrape."""
    coord, pw, dw, engine = fabric
    p = pw.debug_state()
    assert p["role"] == "prefill"
    assert "inflight_handoff" in p and "handoff_backlog" in p
    assert p["migration"]["migrated_pages"] >= 1
    assert p["migration"]["last_migration_ms"] is not None
    assert p["kvcache"]["layout"] == "paged"
    d = dw.debug_state()
    assert d["role"] == "decode"
    assert d["staged_migrations"] == {}        # nothing mid-flight
    assert d["migration"]["adopted_pages"] >= 1
    assert d["migration"]["last_migration_ms"] is not None
    assert "kvcache" in d["engine"]
    c = coord.debug_state()
    assert c["role"] == "coordinator"
    assert c["handoff_queue_depth"] == 0
    assert c["alive_prefill_workers"] == ["p0"]


# ---------------------------------------------------------------------------
# the engine join seam


def test_submit_premigrated_validates_block_shapes(cfg_params, fabric):
    cfg, _ = cfg_params
    eng = fabric[3]        # rides the shared engine: validation raises
    bt = eng.kv_cache.block_tokens       # before anything is scheduled
    prompt = np.arange(2 * bt + 1, dtype=np.int32) + 2
    good = np.zeros((2, cfg.num_layers, cfg.num_kv_heads, bt,
                     cfg.head_dim), np.float32)
    with pytest.raises(ValueError, match="n, L, H, bt, D"):
        eng.submit_premigrated(prompt, 4, good[:, :, :, :-1],
                               good[:, :, :, :-1])
    with pytest.raises(ValueError, match="exceed the prompt"):
        eng.submit_premigrated(prompt[:bt], 4, good, good)
    # None blocks = plain submit (short-prompt degenerate)
    req = eng.submit_premigrated(prompt, 2, None, None)
    assert req.wait(timeout=120).shape == (2,)


@pytest.mark.slow
def test_submit_premigrated_matches_cold_engine(cfg_params):
    """The join seam in isolation: blocks exported from a prefill
    worker's row land via submit_premigrated and the stream matches a
    cold colocated run; the adopted pages are tree-owned afterwards.
    Slow lane: redundant-coverage twin of the loopback e2e bit-identity
    (which drives the same seam through the full migration path) — in
    the full lane it only re-buys ~6 s of engine builds."""
    cfg, params = cfg_params
    net = LoopbackNetwork()
    tp = LoopbackTransport("pp", net)
    pw = PrefillWorker(cfg, params, tp, max_seq=64, prefill_chunk=8)
    prompt = (np.arange(33) % 43 + 2).astype(np.int32)
    with ContinuousBatchingEngine(cfg, params, max_seq=64, max_batch=1,
                                  sampling=GREEDY,
                                  kv_cache_blocks=0) as eng:
        bt = eng.kv_cache.block_tokens
        want = eng.submit(prompt, 6).wait(timeout=120)
    # export via the worker's own seam (chunk prefill + block slices)
    import jax.numpy as jnp
    from distributed_inference_demo_tpu.models.base import KVCache
    n_mig = (len(prompt) - 1) // bt
    row = KVCache.create(cfg, cfg.num_layers, 1, 64)
    cache = KVCache(row.keys, row.values, jnp.int32(0))
    pos = 0
    while pos < n_mig * bt:
        step = min(8, n_mig * bt - pos)
        chunk = np.zeros((1, 8), np.int32)
        chunk[0, :step] = prompt[pos:pos + step]
        cache = pw._chunk_mid(pw.params, jnp.asarray(chunk), cache,
                              jnp.int32(pos))
        pos += step
    k, v = pw._export_blocks(cache.keys, cache.values, 0, n_mig)
    with ContinuousBatchingEngine(cfg, params, max_seq=64, max_batch=1,
                                  sampling=GREEDY,
                                  kv_cache_blocks=0) as eng2:
        req = eng2.submit_premigrated(prompt, 6, k, v)
        np.testing.assert_array_equal(req.wait(timeout=120), want)
        assert eng2.disagg_stats == {"premigrated_requests": 1,
                                     "adopted_pages": n_mig}
        snap = eng2.kv_cache.snapshot()
        assert snap["h2d_bytes"] == 0
        assert snap["blocks_used"] == snap["tree_blocks"]


# ---------------------------------------------------------------------------
# CLI role split + dense deprecation satellites


def test_worker_cli_stage_role_requires_stage_args(capsys):
    from distributed_inference_demo_tpu.runtime import worker_main
    rc = worker_main.main(["--model", MODEL, "--device-id", "w",
                           "--port", "0"])
    assert rc == 1
    err = capsys.readouterr().err
    assert "--role stage requires" in err and "--header" in err


def test_worker_cli_stage_role_still_rejects_kv_cache_flags(capsys):
    from distributed_inference_demo_tpu.runtime import worker_main
    rc = worker_main.main([
        "--model", MODEL, "--stage-id", "1", "--num-stages", "2",
        "--layer-start", "0", "--layer-end", "2", "--device-id", "w",
        "--port", "0", "--header", "h@127.0.0.1:1",
        "--kv-cache-blocks", "8"])
    assert rc == 1
    assert "not supported" in capsys.readouterr().err
