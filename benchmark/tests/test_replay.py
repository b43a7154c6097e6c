"""A family may bring its own replay: the reference check then scores a
served request the way the family generated it, from the record the
server's reply carried (``generation``), and not by one causal forward.

The family here lives in this file alone and is injected through
``sys.modules`` (as ``test_families.py`` does): it generates in blocks of
four.  A block starts as four copies of the mask id; a pass over the block
(positions see every position of their own block and of the blocks before
it) gives logits at the masked positions themselves; the two most
confident are fixed to their best id; two passes fill a block.  The record
says at which pass each token was fixed.

Held: the replay agrees with the same procedure written by hand in NumPy
float64; a record with its passes permuted, and a record withheld, are
caught (the record is used, not decorative); lists of unequal length are
refused; and the record's path from the reply to the replay (``client.ask``
-> ``run.reference_check`` -> the ``REFERENCE`` control line ->
``replica_main`` -> ``reference.emitted_logprobs`` -> the records' entry)
carries it unread, and is byte for byte what it was where there is none.
"""
import io
import json
import sys
import threading
import types
from http.server import BaseHTTPRequestHandler, HTTPServer
from pathlib import Path

import numpy as np
import pytest

import client
import run as bench_run
from stack import BenchFailure

BENCH = Path(__file__).resolve().parent.parent
TOLERANCE = json.loads(
    (BENCH / "tolerance.json").read_text())["max_abs_logprob_err"]

BLOCK, A_PASS, MASK = 4, 2, 12
TOY = {"family": "toyblocks", "vocab_size": 13, "hidden_size": 8,
       "num_layers": 2, "num_heads": 1, "num_kv_heads": 1,
       "intermediate_size": 8, "block_length": BLOCK,
       "mask_token_id": MASK, "fixed_a_pass": A_PASS}
PROMPT = [3, 1, 4, 1, 5, 9, 2, 6]
NEW = 8


def block_family():
    """``families/toyblocks.py``, had it a file: a layer is one attention
    head with no norm, ``x + softmax(mask(q k^T / sqrt(H))) v``, position
    ``i`` sees ``j`` iff ``j // block <= i // block``; ``embed`` adds a
    learned position to the token's row (four masked positions of one
    block would otherwise read alike)."""
    mod = types.ModuleType("families.toyblocks")

    def equations(mc):
        import jax
        import jax.numpy as jnp
        from reference import F32

        def embed(params, ids):
            return (params.embed["tokens"][ids]
                    + params.embed["positions"][: ids.shape[0]]).astype(F32)

        def layer(p, x):
            at = jnp.arange(x.shape[0]) // mc["block_length"]
            s = (x @ p["wq"]) @ (x @ p["wk"]).T / mc["hidden_size"] ** 0.5
            s = jnp.where(at[None, :] <= at[:, None], s, -jnp.inf)
            return x + jax.nn.softmax(s, -1) @ (x @ p["wv"])

        return embed, layer, (lambda params, x: x)

    def replay(mc):
        blk, mask = mc["block_length"], mc["mask_token_id"]
        schedule = sorted(p for p in range(blk // mc["fixed_a_pass"])
                          for _ in range(mc["fixed_a_pass"]))

        def score(params, ids, n_prompt, generation):
            import jax.numpy as jnp
            import reference
            emitted = ids[n_prompt:]
            if generation is None:
                return {"error": "the reply carries no generation record: "
                                 "at which pass was each token fixed?"}
            fixed_at = generation["fixed_at_pass"]
            if (n_prompt % blk or len(emitted) % blk
                    or len(fixed_at) != len(emitted)):
                return {"error": f"{n_prompt} prompt ids, {len(emitted)} "
                                 f"emitted, {len(fixed_at)} in the record: "
                                 f"not whole blocks of {blk}"}
            rows, score_rows = reference.halves(params, mc)
            out = {k: [None] * len(emitted)
                   for k in ("logprobs", "best_ids", "best_logprobs")}
            for lo in range(0, len(emitted), blk):
                passes = fixed_at[lo: lo + blk]
                if sorted(passes) != schedule:
                    return {"error": f"block at {lo} fixed at passes "
                                     f"{passes}: the schedule fixes "
                                     f"{mc['fixed_a_pass']} a pass"}
                for p in sorted(set(passes)):
                    now = [i for i in range(blk) if passes[i] == p]
                    stood = [emitted[lo + i] if passes[i] < p else mask
                             for i in range(blk)]
                    x = rows(ids[: n_prompt + lo] + stood)
                    got = score_rows(
                        x[jnp.asarray([n_prompt + lo + i for i in now])],
                        [emitted[lo + i] for i in now])
                    for k, values in got.items():
                        for i, v in zip(now, values):
                            out[k][lo + i] = v
            return out

        return score

    mod.equations, mod.replay = equations, replay
    mod.layer_matrix_elements = lambda mc: 3 * mc["hidden_size"] ** 2
    mod.layer_scale_elements = lambda mc: 3 * mc["hidden_size"]
    return mod


def weights(seed=0):
    rng = np.random.default_rng(seed)
    h, v, n = TOY["hidden_size"], TOY["vocab_size"], TOY["num_layers"]
    return {"tokens": rng.normal(size=(v, h)), "positions":
            rng.normal(size=(len(PROMPT) + NEW, h)),
            "wq": rng.normal(size=(n, h, h)), "wk": rng.normal(size=(n, h, h)),
            "wv": rng.normal(size=(n, h, h)) / h ** 0.5,
            "head": rng.normal(size=(h, v))}


def as_params(w):
    """The weights as the program's parameter tree would hold them."""
    import jax.numpy as jnp
    f32 = {k: jnp.asarray(v.astype(np.float32)) for k, v in w.items()}
    return types.SimpleNamespace(
        embed={"tokens": f32["tokens"], "positions": f32["positions"]},
        layers={k: f32[k] for k in ("wq", "wk", "wv")},
        final_norm={}, lm_head={"w": f32["head"]})


def logprobs_by_hand(w, ids):
    """Float64 NumPy: the log-probabilities ``[T, V]`` at every position."""
    w = {k: v.astype(np.float32).astype(np.float64) for k, v in w.items()}
    x = w["tokens"][ids] + w["positions"][: len(ids)]
    at = np.arange(len(ids)) // BLOCK
    for q, k, v in zip(w["wq"], w["wk"], w["wv"]):
        s = (x @ q) @ (x @ k).T / TOY["hidden_size"] ** 0.5
        s = np.where(at[None, :] <= at[:, None], s, -np.inf)
        e = np.exp(s - s.max(-1, keepdims=True))
        x = x + (e / e.sum(-1, keepdims=True)) @ (x @ v)
    logits = x @ w["head"]
    return logits - np.log(np.exp(logits).sum(-1, keepdims=True))


def generate_by_hand(w, prompt, n_new):
    """What a server of this family would reply: the tokens, the
    log-probability each had at the pass that fixed it, the record, and
    (for the comparison) the best id and its log-probability there."""
    tokens, lps, fixed_at, best_ids, best_lps = [], [], [], [], []
    for _ in range(n_new // BLOCK):
        stood, at_pass, lp_of = [MASK] * BLOCK, [None] * BLOCK, {}
        for p in range(BLOCK // A_PASS):
            lp = logprobs_by_hand(w, prompt + tokens + stood)[-BLOCK:]
            masked = [i for i in range(BLOCK) if stood[i] == MASK]
            # the two most confident masked positions; never the mask id
            masked.sort(key=lambda i: -lp[i, :MASK].max())
            for i in masked[:A_PASS]:
                stood[i], at_pass[i] = int(lp[i, :MASK].argmax()), p
                lp_of[i] = (lp[i, stood[i]], int(lp[i].argmax()),
                            lp[i].max())
        tokens += stood
        fixed_at += at_pass
        lps += [float(lp_of[i][0]) for i in range(BLOCK)]
        best_ids += [lp_of[i][1] for i in range(BLOCK)]
        best_lps += [float(lp_of[i][2]) for i in range(BLOCK)]
    return {"tokens": tokens, "logprobs": lps,
            "generation": {"fixed_at_pass": fixed_at},
            "best_ids": best_ids, "best_logprobs": best_lps}


def permuted(generation):
    """The same record with every block's passes the other way round:
    still two a pass, so only the numbers can tell."""
    last = BLOCK // A_PASS - 1
    return {"fixed_at_pass": [last - p for p in generation["fixed_at_pass"]]}


@pytest.fixture(params=[0, 1, 2])
def toy(request, monkeypatch):
    monkeypatch.setitem(sys.modules, "families.toyblocks", block_family())
    w = weights(request.param)
    return w, as_params(w), generate_by_hand(w, PROMPT, NEW)


def test_the_replay_agrees_with_the_procedure_by_hand(toy):
    import reference
    _, params, served = toy
    # both passes fix tokens in every block, and not in the order of the ids
    assert sorted(served["generation"]["fixed_at_pass"]) == [0] * 4 + [1] * 4
    assert served["generation"]["fixed_at_pass"] != [0, 0, 1, 1] * 2
    got = reference.emitted_logprobs(
        params, TOY, PROMPT + served["tokens"], len(PROMPT),
        served["generation"])
    assert got["logprobs"] == pytest.approx(served["logprobs"], abs=1e-5)
    assert got["best_ids"] == served["best_ids"]
    assert got["best_logprobs"] == pytest.approx(served["best_logprobs"],
                                                 abs=1e-5)


def test_a_record_permuted_or_withheld_is_caught(toy):
    """Fault injection on the record: the same ids, the passes the other
    way round, move a log-probability by more than ``tolerance.json``
    allows; no record, and a record that breaks the schedule, answer
    ``error``.  One causal forward (what a family without a replay gets)
    is no account of these tokens either."""
    import reference
    _, params, served = toy
    ids, n = PROMPT + served["tokens"], len(PROMPT)
    wrong = reference.emitted_logprobs(params, TOY, ids, n,
                                       permuted(served["generation"]))
    moved = [abs(a - b) for a, b in zip(wrong["logprobs"],
                                        served["logprobs"])]
    assert max(moved) > TOLERANCE
    assert "no generation record" in reference.emitted_logprobs(
        params, TOY, ids, n)["error"]
    assert "the schedule fixes 2 a pass" in reference.emitted_logprobs(
        params, TOY, ids, n, {"fixed_at_pass": [0] * NEW})["error"]
    rows, score = reference.halves(params, TOY)
    causal = score(rows(ids)[n - 1: -1], ids[n:])
    assert max(abs(a - b) for a, b in zip(
        causal["logprobs"], served["logprobs"])) > TOLERANCE


class Replica:
    """Where ``stack.Stack`` stands in a run: a control line goes through
    ``replica_main._control`` itself, in this process, on held parameters."""

    def __init__(self, params, monkeypatch, capsys):
        import replica_main
        monkeypatch.setitem(replica_main._HELD, "params", params)
        self.main, self.patch, self.capsys = replica_main, monkeypatch, capsys
        self.lines = []

    def control(self, line, reply, timeout):
        self.lines.append(line)
        self.patch.setattr(sys, "stdin", io.StringIO(line + "\n"))
        self.capsys.readouterr()
        self.main._control(TOY)
        said = [row for row in self.capsys.readouterr().out.splitlines()
                if row.startswith(reply + " ")]
        return said[-1][len(reply):].strip()


def canary_of(served, **changed):
    return dict({"prompt": PROMPT, **{k: served[k] for k in (
        "tokens", "logprobs", "generation")}}, **changed)


def test_the_record_reaches_the_replay_through_the_control_line(
        toy, monkeypatch, capsys):
    _, params, served = toy
    replica = Replica(params, monkeypatch, capsys)
    ref = bench_run.reference_check(replica, canary_of(served), TOLERANCE)
    assert ref["ok"] and ref["max_abs_err"] < 1e-5
    assert ref["generation"] == served["generation"]
    assert ref["reference_best_ids"] == served["best_ids"]
    sent = json.loads(replica.lines[-1].partition(" ")[2])
    assert sent == {"ids": PROMPT + served["tokens"],
                    "n_prompt": len(PROMPT),
                    "generation": served["generation"]}
    # the passes the other way round: the run would not be correct
    ref = bench_run.reference_check(
        replica, canary_of(served, generation=permuted(
            served["generation"])), TOLERANCE)
    assert not ref["ok"] and ref["max_abs_err"] > TOLERANCE
    # withheld: the replay's sentence fails the run
    with pytest.raises(BenchFailure, match="no generation record"):
        bench_run.reference_check(
            replica, canary_of(served, generation=None), TOLERANCE)


class Canned:
    """A replica that answers what it is told to."""

    def __init__(self, reply):
        self.reply, self.lines = reply, []

    def control(self, line, reply, timeout):
        self.lines.append(line)
        return json.dumps(self.reply)


def canned(n):
    return {"logprobs": [-1.0] * n, "best_ids": [1] * n,
            "best_logprobs": [-0.5] * n, "seconds": 0.0}


@pytest.mark.parametrize("served, reference, tokens", [
    (3, 4, 4), (4, 3, 4), (4, 4, 3), (0, 4, 4)])
def test_lists_of_unequal_length_are_refused(served, reference, tokens):
    canary = {"prompt": [5, 6], "tokens": [7] * tokens,
              "logprobs": [-1.0] * served}
    with pytest.raises(BenchFailure, match=(
            f"{served} served log-probabilities, {reference} of the "
            f"reference's and {tokens} tokens")):
        bench_run.reference_check(Canned(canned(reference)), canary, 0.1)


def test_without_a_record_the_control_line_is_what_it_was():
    """No ``generation`` in the reply (every cell of PR 51's benchmark):
    the line's bytes are the parent's, and the record gains no key."""
    for canary in ({"prompt": [5, 6], "tokens": [7, 8],
                    "logprobs": [-1.0, -1.25]},
                   {"prompt": [5, 6], "tokens": [7, 8], "generation": None,
                    "logprobs": [-1.0, -1.25]}):
        replica = Canned(canned(2))
        ref = bench_run.reference_check(replica, canary, 0.5)
        assert replica.lines == ["REFERENCE " + json.dumps(
            {"ids": [5, 6, 7, 8], "n_prompt": 2})]
        assert sorted(ref) == sorted([
            "max_abs_err", "errs", "tolerance", "ok", "served", "reference",
            "reference_best_ids", "reference_best_logprobs", "tokens",
            "seconds"])
        assert ref["ok"] and ref["errs"] == [0.0, 0.25]


class Generate(BaseHTTPRequestHandler):
    """``POST /generate`` as the gateway answers an unstreamed request."""
    protocol_version = "HTTP/1.1"
    extra = {}

    def log_message(self, *a):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        n = body["max_new_tokens"]
        out = {"tokens": [[7] * n], **self.extra}
        if body.get("logprobs"):
            out["logprobs"] = [[-1.0] * n]
        data = json.dumps(out).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)


@pytest.mark.parametrize("extra, kept", [
    ({}, None), ({"generation": None}, None), ({"generation": []}, None),
    ({"generation": [None]}, None),
    ({"generation": [{"fixed_at_pass": [1, 0]}, "another sequence's"]},
     {"fixed_at_pass": [1, 0]})])
def test_ask_keeps_the_first_sequence_s_record(extra, kept, monkeypatch):
    monkeypatch.setattr(Generate, "extra", extra)
    srv = HTTPServer(("127.0.0.1", 0), Generate)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        res = client.ask(srv.server_address[1], [1, 2], 2, logprobs=True)
    finally:
        srv.shutdown()
        srv.server_close()
        t.join(timeout=5)
    assert not t.is_alive()
    assert res == {"status": 200, "tokens": [7, 7], "logprobs": [-1.0, -1.0],
                   "generation": kept, "error": ""}
