"""The two orders of the mixed loop, held to each other over whole runs
(they were ``tests/test_mixed_batching.py``'s two longest sweeps, and are
a file of their own so that the deal can place them: ``docs/DESIGN.md``,
"How tier-1 is dealt").

- PR 61's merged pass (a slab's forward carries the decoding rows' first
  step) against the old order (``old_order_mixed_step``), dispatch by
  dispatch on the engine's own plans, a case a family;
- §19's prepared dispatches against the same scripted traffic with every
  plan refused: nothing changes but the order.
"""

import time
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_inference_demo_tpu.models import get_model_config
from distributed_inference_demo_tpu.models.decoder import init_full_params
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime.batching import (
    ContinuousBatchingEngine)
from test_mixed_batching import (
    _OUT, GREEDY, KEEPER, old_order_mixed_step, scripted_run, settle)
from test_mixed_batching import params  # noqa: F401  (the fixture)


def _held_to_the_old_order(eng, args, new, old):
    """One slab-carrying dispatch with a row riding it, both ways: the
    facts the two orders must agree on (a sentence for each that they do
    not) and what kind of dispatch it was."""
    cfg, B = eng.cfg, eng.max_batch
    new, old = dict(zip(_OUT, new)), dict(zip(_OUT, old))
    seg, riding, budget = args[3], np.asarray(args[7]), np.asarray(args[10])
    installed = np.zeros((B + 1,), bool)
    installed[np.asarray(seg[4])] = True
    installed = installed[:B]
    wrong = []

    def same(what, a, b, exact=True):
        a, b = np.asarray(a), np.asarray(b)
        ok = ((a == b).all() if exact or a.dtype.kind != "f" else
              np.allclose(a, b, rtol=1e-5, atol=1e-5))
        if not ok and a.dtype.kind == "f":
            wrong.append(f"{what}: max |a - b| {np.abs(a - b).max():.3g} "
                         f"of {np.abs(b).max():.3g}")
        elif not ok:
            wrong.append(f"{what}: {a.tolist()} != {b.tolist()}")

    n_new, n_old = int(new["steps"]), int(old["steps"])
    same("final_toks", new["final_toks"], old["final_toks"])
    same("final_lps", new["final_lps"], old["final_lps"], exact=False)
    toks_n, toks_o = np.asarray(new["toks"]), np.asarray(old["toks"])
    lps_n, lps_o = np.asarray(new["lps"]), np.asarray(old["lps"])
    for i in np.flatnonzero(riding):
        n = min(n_new, n_old)
        same(f"row {i}'s tokens", toks_n[i, :n], toks_o[i, :n])
        same(f"row {i}'s lps", lps_n[i, :n], lps_o[i, :n], exact=False)
    for i in np.flatnonzero(installed):
        # token #1 + num_steps - 1: its tokens lie one column later
        n = min(n_new - 1, n_old)
        same(f"final {i}'s tokens", toks_n[i, 1:1 + n], toks_o[i, :n])
        same(f"final {i}'s lps", lps_n[i, 1:1 + n], lps_o[i, :n],
             exact=False)
    whole = not installed.any()
    if whole:   # the same steps of the same rows: the same state is left
        same("steps", n_new, n_old)
        same("lengths", new["lengths"], old["lengths"])
        same("last_tok", new["last_tok"], old["last_tok"])
        for a, b in zip(jax.tree.leaves((new["pk"], new["pv"])),
                        jax.tree.leaves((old["pk"], old["pv"]))):
            same("a pool's pages or states", a, b, exact=False)
        if cfg.num_experts:
            E = cfg.experts_here
            acc_n, acc_o = (np.asarray(x["moe_acc"]) for x in (new, old))
            same("moe_rows", acc_n[:E], acc_o[:E])
            calls = (cfg.total_layers - cfg.lead_dense_layers) * cfg.ut_steps
            # a layer call fewer a block: the slab's and the step's are one
            same("moe layer calls", acc_n[E + 2], acc_o[E + 2] - calls)
            if not acc_n[E] <= acc_o[E]:
                wrong.append("moe_touched grew")
    else:
        ride = riding & (budget >= 4)
        same("riding rows' lengths", np.asarray(new["lengths"])[ride],
             np.asarray(old["lengths"])[ride])
    return {"whole": whole, "steps": (n_new, n_old), "wrong": wrong,
            "riding": int(riding.sum())}


def _run_held_to_the_old_order(cfg, params, mesh=None, **kw):
    """A row decoding and a prompt of four segments admitted beside it,
    through an engine whose every slab-carrying dispatch with a riding row
    is also run in the old order on a copy of its pool."""
    kw = dict(dict(max_seq=128, max_batch=3, sampling=GREEDY,
                   kv_block_tokens=8, prefill_chunk=8, decode_block=4,
                   mixed_token_budget=24), **kw)
    with mock.patch.object(ContinuousBatchingEngine, "_warm_mixed_variants",
                           lambda self: None):
        eng = ContinuousBatchingEngine(cfg, params, mesh=mesh, **kw)
    held, inner, old = [], eng._mixed_step, old_order_mixed_step(eng)

    def both(*a):
        if a[3] is None or not np.asarray(a[7]).any():
            return inner(*a)
        ref = old(a[0], *jax.tree.map(jnp.copy, a[1:3]), *a[3:])
        ref = jax.tree.map(np.asarray, ref)
        out = inner(*a)
        try:
            held.append(_held_to_the_old_order(eng, a, out, ref))
        except Exception as e:          # the scheduler's thread: keep it
            held.append({"whole": False, "wrong": [repr(e)]})
        return out

    eng._mixed_step = both
    with eng:
        keeper = eng.submit([5, 4, 3, 2, 1], 40)
        while len(keeper.tokens) < 2:
            time.sleep(0.005)
        long = eng.submit(list(range(30, 59)), 6)
        toks = [r.wait(timeout=300).tolist() for r in (keeper, long)]
        settle(eng)
        st = eng.stats()
    return held, toks, st


@pytest.mark.parametrize("model", [
    "qwen2-test",           # dense, rope, grouped heads, q / k / v biases
    "bloom-test",           # ALiBi, every head its own keys, LayerNorm
    "olmoe-test",           # experts, with their counters
    "kanana-test",          # latent attention, a leading dense block
    "laguna-test",          # a period: window and full kinds, two pools
    "evabyte-test",         # EVA: summaries pooled as windows close
    "solar-open2-test",     # KDA: a recurrent state a request
    "xing-bench-test",      # four residual streams a token
    "ouro-test",            # a looped stack: three passes a token
    "qwen2-test/tp2",       # two devices, one shard_map
])
def test_the_merged_pass_is_the_old_order_s_arithmetic(model):
    """In a dispatch that packed a slab, the rows that were decoding take
    their first step inside the slab's forward and the loop runs the other
    ``num_steps - 1``.  Held, dispatch by dispatch and on the engine's own
    plans, to the old order on a copy of the same pool: the same greedy
    tokens (a final installed by the dispatch has token #1 and ``num_steps
    - 1`` of the old order's four, one column later), and where no final
    was installed the same lengths, the same pages and states to float32
    rounding (two shapes of one matmul: see above; a model of several
    streams mixes them in one call over the pass's rows, padded to whole
    tiles, where the old order made two: 3e-6 on pages of order 4), the
    same rows to every expert, in one layer call fewer a block."""
    name, _, tp = model.partition("/tp")
    cfg, mesh = get_model_config(name), None
    if tp:
        from distributed_inference_demo_tpu.models.loader import load_or_init
        from distributed_inference_demo_tpu.parallel.mesh import local_tp_mesh
        mesh = local_tp_mesh(int(tp))
        params = load_or_init(name, cfg, seed=0, mesh=mesh)
    else:
        params = init_full_params(jax.random.PRNGKey(0), cfg)
    held, toks, st = _run_held_to_the_old_order(cfg, params, mesh)
    assert [w for h in held for w in h["wrong"]] == []
    # a slab of chunks alone under the riding row, and one with the final
    assert [h["whole"] for h in held].count(True) >= 1
    assert [h["whole"] for h in held].count(False) >= 1
    assert all(h["riding"] == 1 and h["steps"][0] == 4 for h in held)
    assert [len(t) for t in toks] == [40, 6]
    dt = st["dispatch_trace"]
    recs = [dict(zip(dt["fields"], r)) for r in dt["recent"]]
    carried = [r for r in recs if r["slab_carried_step"]]
    assert len(carried) == len(held) == dt["slab_carried_steps"]
    assert dt["slab_carried_rows"] == sum(
        r["slab_carried_step"] for r in carried) == len(held)
    # every slab under a decoding row carried its step; the keeper's own
    # (nothing was decoding) did not
    assert all(bool(r["slab_carried_step"]) == (r["active_rows"] > 0)
               for r in recs if r["segments"])
    assert all(r["steps"] == 4 for r in carried)
    assert st["device_loop"]["device_loop_steps"] == sum(
        r["steps"] for r in recs)


_PROBES = {}


def scripted_pair(params, sampled, with_eos, case="base"):
    """The as-it-is run and the every-plan-refused run of one case.
    With ``eos``: a token of the case's own streams, the first that ends
    a row unannounced and lets the keeper outlive the script."""
    sampling = (SamplingParams(greedy=False, temperature=0.9, top_k=40)
                if sampled else GREEDY)
    if not with_eos:
        if (sampled, case) not in _PROBES:
            _PROBES[sampled, case] = scripted_run(params, sampling, None,
                                                  False, case)
        return (_PROBES[sampled, case],
                scripted_run(params, sampling, None, True, case))
    probe = scripted_pair(params, sampled, False)[0]["same"]["streams"]
    spared = probe["keeper"][0][:48] + probe["share"][0]
    seen = []
    for name in ("mid", "parked", "late"):
        for tok in probe[name][0][1:-1]:
            if tok not in spared and tok not in seen:
                seen.append(tok)
    for eos in seen[:4]:
        run = scripted_run(params, sampling, eos, False)
        ended = [n for n, (toks, *_) in run["same"]["streams"].items()
                 if toks and toks[-1] == eos]
        if (run["script_done"] and ended
                and run["trace"]["ahead_misses"]["cancel"]):
            return run, scripted_run(params, sampling, eos, True)
    pytest.fail(f"no token of {seen[:4]} ends a row unannounced and "
                f"spares the keeper and the row the script cancels")


def _base_script_did_what_it_says(ahead, with_eos):
    streams, recs, dt = ahead["same"]["streams"], ahead["recs"], ahead["trace"]
    assert len(streams["one"][0]) == 1
    assert streams["share"][2] and len(streams["share"][0]) < 30
    if not with_eos:
        assert len(streams["mid"][0]) == 6
    # a full batch while a final waited, and a prefix found in the tree
    assert any(r["active_rows"] == 3 and r["segments"] > r["finals"]
               for r in recs) or streams["parked"][5] > streams["parked"][4]
    assert (sum(r["prefill_tokens"] for r in recs)
            < sum(len(x) for x in (KEEPER, KEEPER[:16] + [77, 78, 79],
                                   [9, 2, 6], [5, 4, 3, 2],
                                   list(range(40, 62)), [8, 8, 1],
                                   [7, 1, 7, 1, 7])))
    assert dt["ahead_hits"] >= 5
    assert dt["ahead_misses"]["arrival"] >= 3
    assert dt["ahead_misses"]["cancel"] >= 1
    assert dt["ahead_misses"]["finish"] >= (1 if with_eos else 0)


def _long_prompt_was_prepared(ahead, old):
    long = ahead["same"]["streams"]["long"]
    assert len(long[0]) == 6 and long[5] == long[4] + 2
    # its three dispatches: two chunks packed in the gap (it had just
    # arrived), two more and then the final prepared under them
    slabs = [r for r in ahead["recs"] if long[4] <= r["seq"] <= long[5]]
    assert [(r["segments"], r["finals"], r["ahead"] > 0) for r in slabs] == [
        (2, 0, False), (2, 0, True), (1, 1, True)]
    assert all(r["active_rows"] == 1 for r in slabs)


def _two_admissions_were_packed_in_order(ahead, old):
    first, second = (ahead["same"]["streams"][n] for n in ("first", "second"))
    # the dispatch that ends the first's prompt is the one before the
    # second's first, and both were launched as prepared
    assert first[4] < first[5] == second[4] - 1 < second[5]
    recs = {r["seq"]: r for r in ahead["recs"]}
    assert recs[first[5]]["finals"] == 1 and recs[first[5]]["ahead"] > 0
    assert recs[second[4]]["segments"] == 2 and recs[second[4]]["ahead"] > 0
    # (the second's final is packed in the gap: the first row, installed
    # behind the step its slab carried, ends one dispatch later, under
    # the execution that plan would have been made in)
    assert ahead["trace"]["ahead_hits_slab"] >= 2


def _an_ended_rows_slot_waited_for_its_drain(ahead, old):
    streams, recs = ahead["same"]["streams"], ahead["recs"]
    assert len(streams["brief"][0]) == 18 and len(streams["parked"][0]) == 7
    parked = streams["parked"]
    final = next(r for r in recs if r["seq"] == parked[5])
    # the final waited for a slot (dispatches between its last chunk and
    # its final carried no segment), took the one `brief` left, and was
    # packed in the gap after the drain that cleared it: a `finish` miss
    waited = [r for r in recs if parked[4] < r["seq"] < parked[5]]
    assert any(r["segments"] == 0 and r["active_rows"] == 3 for r in waited)
    assert final["finals"] == 1 and final["ahead"] == 0
    assert final["active_rows"] == 2
    assert ahead["trace"]["ahead_misses"]["finish"] >= 1
    assert parked[0] == old["same"]["streams"]["parked"][0]


def _a_cancelled_admission_packs_no_more(ahead, old):
    streams, recs = ahead["same"]["streams"], ahead["recs"]
    for name in ("seen", "unseen"):
        toks, _, cancelled, error, first_seq, final_seq = streams[name]
        assert cancelled and toks == [] and error == "None"
        assert first_seq > 0 and final_seq == 0
    # two chunks of the one were launched and four of the other, none
    # after its cancel: the slab tokens are theirs, the keeper's, the
    # tail's
    assert sum(r["prefill_tokens"] for r in recs) == 16 + 32 + len(KEEPER) + 5
    assert ahead["trace"]["ahead_misses"]["cancel"] >= 2


def _a_failed_slab_fails_its_request_alone(ahead, old):
    streams = ahead["same"]["streams"]
    toks, _, _, error, first_seq, final_seq = streams["victim"]
    assert toks == [] and "scripted launch failure" in error
    assert first_seq > 0 and final_seq == 0
    assert len(streams["keeper"][0]) == 60 and len(streams["tail"][0]) == 3
    assert streams["keeper"][3] == streams["tail"][3] == "None"
    # the slab of the same prompt backwards, sent later, was prepared too
    assert streams["tail"][5] == streams["tail"][4] + 2
    # prepared under an execution that was still to be drained, where
    # the old order had drained it first
    assert ahead["raised"] == [2] and old["raised"] == [1]
    # the dispatch that never reached the device is no record
    assert len(ahead["recs"]) == ahead["trace"]["seq"]


def _full_slabs_went_behind_their_predecessors(ahead, old):
    streams = ahead["same"]["streams"]
    recs = {r["seq"]: r for r in ahead["recs"]}
    assert len(streams["row"][0]) == 40 and len(streams["keeper"][0]) == 60
    assert len(streams["long"][0]) == 6 and len(streams["next"][0]) == 4
    for name in ("long", "next"):
        first, final = streams[name][4:]
        # two segments a dispatch, the first two packed in the gap (the
        # prompt had just arrived, or waited for a slot) and every other
        # pair enqueued while its predecessor ran; the final rode alone,
        # a segment to spare: prepared, and launched when that returned
        assert final == first + 4
        slabs = [recs[n] for n in range(first, final + 1)]
        assert [r["segments"] for r in slabs] == [2, 2, 2, 2, 1]
        assert [r["ahead"] > 0 for r in slabs] == [False] + [True] * 4
        assert [r["early"] for r in slabs] == [0, 1, 1, 1, 0]
        assert all(r["active_rows"] >= 1 for r in slabs)
    assert ahead["trace"]["ahead_early"] == 6
    # a plan that packs nothing is never closed
    assert any(r["ahead"] > 0 and not r["segments"] for r in recs.values())


_CASE_CHECKS = {
    "full_slab": _full_slabs_went_behind_their_predecessors,
    "long_prompt": _long_prompt_was_prepared,
    "two_admissions": _two_admissions_were_packed_in_order,
    "parked_final": _an_ended_rows_slot_waited_for_its_drain,
    "cancelled_admission": _a_cancelled_admission_packs_no_more,
    "failed_launch": _a_failed_slab_fails_its_request_alone,
}


@pytest.mark.quick
@pytest.mark.parametrize("case", ["no_eos", "eos", *_CASE_CHECKS])
@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_prepared_dispatches_change_nothing_but_the_order(params, sampled,
                                                          case):
    """The reordering's whole contract: the same scripted traffic, once
    as it is and once with every prepared dispatch refused (every
    iteration then runs drain, intake, pack, launch), gives identical
    token streams, log-probabilities, dispatch records field by field,
    rng spend, counters, decode tables and page accounting.  The base
    script (``no_eos``, ``eos``) has an arrival and a cancel during an
    execution, a ``max_new = 1`` final, a row whose budget ends
    mid-block, a full batch with a parked final, prefix-sharing prompts,
    and (``eos``) rows that end unannounced; the other cases (``SCRIPTS``)
    are about the dispatches that carry a slab and are prepared under
    their predecessors all the same."""
    with_eos = case == "eos"
    script = "base" if case in ("no_eos", "eos") else case
    ahead, old = scripted_pair(params, sampled, with_eos, script)
    assert ahead["script_done"] and old["script_done"]
    for key in ahead["same"]:
        assert ahead["same"][key] == old["same"][key], key
    if script == "base":
        _base_script_did_what_it_says(ahead, with_eos)
    else:
        _CASE_CHECKS[case](ahead, old)
    # a plan commits nothing, launched or not
    assert ahead["touched"] == [] and old["touched"] == []
    # every dispatch is counted once, and only the first run has hits
    for run in (ahead, old):
        dt = run["trace"]
        assert (dt["ahead_hits"] + sum(dt["ahead_misses"].values())
                + dt["ahead_first"] == dt["seq"] == len(run["recs"]))
        # budget 24 / chunk 8: no slab or one of 1, 2, 3 segments, every
        # one launched before the first request, none added by traffic
        assert run["compile"]["cache_entries"] == 4
        assert [r["ahead"] > 0 for r in run["recs"]].count(True) == dt[
            "ahead_hits"]
    assert old["trace"]["ahead_hits"] == old["trace"]["ahead_hits_slab"] == 0
    # hits that carried a slab, and hits that carried none
    dt = ahead["trace"]
    assert (script != "base") <= dt["ahead_hits_slab"] < dt["ahead_hits"]
    assert dt["ahead_hits_slab"] == sum(
        r["ahead"] > 0 and r["segments"] > 0 for r in ahead["recs"])
    # a hit was prepared inside its predecessor's wait
    for a, b in zip(ahead["recs"], ahead["recs"][1:]):
        if b["ahead"] > 0:
            assert b["active_rows"] > 0 or b["segments"] > 0
            assert 0 < b["ahead"] <= a["wait"] + 2e-5
    _early_launches_are_counted(ahead, old, with_eos)


def _early_launches_are_counted(ahead, old, with_eos):
    """``ahead_early`` counts the hits whose record says ``early``: they
    carried a slab, were launched before their predecessor's ``t_done``
    and every other dispatch after it; none with every plan refused, none
    on an engine with an ``eos``."""
    dt, recs = ahead["trace"], ahead["recs"]
    assert dt["fields"][-1] == "early"
    assert dt["ahead_early"] == sum(r["early"] for r in recs)
    assert dt["ahead_early"] <= dt["ahead_hits_slab"]
    assert old["trace"]["ahead_early"] == 0
    assert not any(r["early"] for r in old["recs"])
    if with_eos:
        assert dt["ahead_early"] == 0
    for a, b in zip(recs, recs[1:]):
        if b["early"]:
            assert b["ahead"] > 0 and b["segments"] > 0
            assert b["t_launch"] < a["t_done"] < b["t_done"]
        else:
            assert b["t_launch"] >= a["t_done"]


